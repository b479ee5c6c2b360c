"""Plain reference of the ``nemotron_3_nano_30b_a3b`` configuration, cut as its
file says: a ``nemotron_h`` decoder (Nemotron-H / Nemotron 3; the family's
published modelling code gives the layers, whose state-space layer is
Mamba-2 with groups) in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: loss, every gradient and plain
SGD.  No kernel, no sort, no grouped product, nothing of the program
imported, and **the state-space recurrence literally, position by position**
with its groups: a ``lax.scan`` over ``t`` that carries the state, with no
chunk, no running sum of decays and no quadratic form, so that an error in
the program's chunk or group algebra cannot hide in it.  The walk is
checkpointed in blocks of positions, so that 8,192 fit.

With ``d`` the hidden size and ``RMSNorm(a; g) = a / sqrt(mean(a^2) + eps) *
g``:

- *The model.*  ``x_0 = emb[tokens]``; the layers; ``h = RMSNorm(x;
  norm_g)``; ``logits = h head`` (untied; the embedding's transpose where
  ``tie_word_embeddings``), taken in chunks of tokens; the loss the mean
  next-token cross-entropy.
- *A layer* is ONE sub-layer behind one norm, by its character of
  ``hybrid_override_pattern``: ``x <- x + f(RMSNorm(x; g))``, the gain
  ``ln1_g`` of an ``M`` or ``*`` layer and ``ln2_g`` of an ``E`` layer.
- *``M``, a Mamba-2 mixer* (``H`` heads of ``P``, state ``N``, ``G`` groups
  of ``H / G`` heads): ``[z | xBC | dt] = u ssm_in`` (``H P``, ``H P + 2 G
  N``, ``H`` columns); ``xBC = silu(conv(xBC) + ssm_conv_b)``, depthwise and
  causal, ``c_t = sum_j ssm_conv_k[j] xBC_{t - taps + 1 + j}``, zeros before
  the sequence; ``[x | B | C] = xBC`` with ``B``, ``C`` as ``(G, N)``; ``dt =
  softplus(dt + ssm_dt_b)``; ``A = -exp(ssm_a_log)``; then for ``t = 0, 1,
  ...`` with ``h = 0`` before the sequence, head ``i`` reading group ``i //
  (H / G)``:

      h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T         (P, N)
      y_t = h_t C_t + ssm_d x_t

  ``y <- y * silu(z)``, then RMSNorm over EACH GROUP's ``H P / G`` entries
  (the gate first, ``norm_before_gate`` false) with the gain ``ssm_g`` (``(G,
  H P / G)``: the published ``(H P,)`` gain a group a row); ``out = y
  ssm_out``.  ``chunk_size`` is read by nothing here.
- *``*``, attention*: ``q, k, v = u wq, u wk, u wv`` as ``heads`` /
  ``kv_heads`` / ``kv_heads`` heads, no bias, NO positional encoding, causal
  ``softmax(q k^T / sqrt(head_dim)) v`` (a block of queries at a time),
  ``wo``.
- *``E``, experts*: ``s = sigmoid(u gate)`` over ALL ``router`` experts; the
  selected set is the top k of ``s + ebias`` (``e_score_correction_bias``: no
  gradient, no update; one group); ``w_e = scale * s_e / (sum over the
  selected of s + 1e-20)``; the sum over the selected experts *this chip
  holds* of ``w_e ew2_e relu(u ew1_e)^2``: a loop over the held experts with
  masks.  What the absent experts would add is left out, here as in the
  program, the weights still normalised over all k selected.  Beside it the
  shared expert ``sw2 relu(u sw1)^2``, once, for every token.

Departures from the published code, the first the program's and the rest
under ``assumed`` in the configuration file: the program's router
(``moe.route_top_k``) adds 1e-6 to the selected scores' sum where the family
adds 1e-20 (this reference keeps 1e-20: six sigmoid scores sum to about 3, so
the program's weights stand 3e-7 under, far inside every tolerance); how the
weights are seeded (Mamba-2's own start for ``A``, ``dt`` and ``D``, a
router balanced over the chips: :func:`_make_leaf`); no clamp on ``dt``
(the family's ``time_step_limit`` is ``(0, inf)``); plain SGD for the
model's own optimizer.

The guide's share test is ``tests/test_nemotron_h_arch.py``'s: the routed
parts of all 8 shares of an ``E`` layer and the shared expert counted once
add up to the uncut layer (:func:`_layer` with ``first`` 0 and every expert
held).

It also owns the seeded weights and token rows.  One jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, group by group, and runs a row and a layer at a time, keeping
every layer's input for the backward walk, the rows' gradients summed.

``first_steps``: the first three steps' loss, each leaf's first gradient as
plain SGD applied it (``(w0 - w1) / lr``), the small leaves' first gradients
themselves (``grad_first``), each leaf's change after three steps, and a
step's state-space readings (``ssm``: the mean over positions, heads and
``M`` layers of ``exp(dt A)``, and the RMS of the state behind the last
position, mean over the ``M`` layers).
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own
#: size (a scratch loop over benchmark/limits.py's own call, which prints the
#: rates too; my chip runs, PR 45; PERF.md section 2 has the table): the
#: bfloat16 program over 8 seeds with the routers seeded as they are now (and
#: 9 with independent columns, inside the same ranges but for a lower
#: ``grad_diff_gap``, 0.261-0.321) against the fp8 control over 2 (and 2).
#: ``grad_diff_gap`` decides (the worst small leaf's first gradient, norm of
#: the difference; the LAST ``E`` layer's router ``gate`` on every seed: of
#: 16,384 x 6 selections a few per cent differ between a bfloat16 stream and
#: the float32 one, behind eight layers): 0.294-0.365 against 0.670-0.699,
#: the limit at their geometric mean, 1.37 times over the sound runs' largest
#: and 1.34 under the control's least.  The two norm gaps are second order in
#: that difference (0.3^2 / 2 is 0.045) and scatter with the seed as much as
#: with the precision: ``grad_norm_gap`` 0.0063-0.0574 against 0.024-0.044
#: (no order), ``delta_norm_gap`` 0.0040-0.0290 against 0.047-0.052 (1.6
#: times, inside one seed's scatter: 0.0089-0.0173 with the earlier seeding).
#: They stand where the precision hardly moves a number: between the
#: readings and 1, the more room above (2.1 and 3.1 times the largest
#: reading), and hold what they can: a row of the batch left out, a state
#: returned unchanged (1), a layer skipped.  ``loss_gap`` reads
#: 3.2e-5-1.4e-4 against 1.4e-4-2.9e-4 and takes the accepted cells' 0.0015,
#: eleven times the largest reading.  So the control is refused by ONE
#: limit, the first, in every run.
LIMITS = {
    "loss_gap": 0.0015,
    "grad_norm_gap": 0.12,
    "delta_norm_gap": 0.09,
    "grad_diff_gap": 0.5,
}

#: leaves small enough to keep whole for ``grad_diff_gap``: the gains, the
#: routers, the state-space layers' decay rates, step-size biases, skips,
#: convolution taps and biases, and the attention layer's key and value
#: projections
KEEP = ("ln1_g", "ln2_g", "norm_g", "gate", "ssm_g", "ssm_a_log", "ssm_dt_b",
        "ssm_d", "ssm_conv_k", "ssm_conv_b", "wk", "wv")

#: queries a block of the reference's attention, tokens a chunk of its head,
#: positions a checkpointed block of its recurrence
_Q_BLOCK, _HEAD_CHUNK, _T_BLOCK = 512, 1024, 128

#: deviation of the embedding's entries: a token's own vector leads the
#: residual stream, so that the routers' inputs differ token by token (a
#: common component favours some experts for every token, and the pairs a
#: chip's share receives then swing with the seed: PERF.md, PR 32)
_EMB_STD = 1.0

#: a character of ``hybrid_override_pattern`` -> the layer's kind
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


@functools.lru_cache(maxsize=None)
def _dims_of(key: str):
    import json
    return json.loads(key)


def dims(cfg: dict) -> dict:
    """The sizes the reference runs, from the configuration as run."""
    pattern = str(cfg["hybrid_override_pattern"])
    if set(pattern) - set(KINDS) or \
            int(cfg["num_hidden_layers"]) != len(pattern):
        raise ValueError(f"hybrid_override_pattern {pattern!r} against "
                         f"num_hidden_layers {cfg['num_hidden_layers']}: M, "
                         f"E or * a layer")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    router = int(cfg.get("router_width", cfg["n_routed_experts"]))
    held = cfg.get("experts_held") or {"first": 0, "count": router}
    h, g = int(cfg["mamba_num_heads"]), int(cfg["n_groups"])
    if h % g:
        raise ValueError(f"n_groups {g} does not divide mamba_num_heads {h}")
    return {
        "d": d, "heads": heads,
        "kv_heads": int(cfg.get("num_key_value_heads", heads)),
        "hd": int(cfg.get("head_dim") or d // heads),
        "vocab": int(cfg["vocab_size"]), "pattern": pattern,
        "eps": float(cfg["layer_norm_epsilon"]),
        "H": h, "P": int(cfg["mamba_head_dim"]),
        "N": int(cfg["ssm_state_size"]), "G": g,
        "taps": int(cfg["conv_kernel"]),
        "router": router, "first": int(held["first"]),
        "held": int(held["count"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "moe_ff": int(cfg["moe_intermediate_size"]),
        "shared_ff": int(cfg.get("n_shared_experts", 0)) *
        int(cfg.get("moe_shared_expert_intermediate_size", 0)),
        "norm_topk": bool(cfg.get("norm_topk_prob", True)),
        "scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
    }


def leaf_groups(cfg: dict) -> dict:
    """``{group: path in the step's parameter pytree}``, in the order the
    readings walk them; a group is one array or a dict of them."""
    dm = dims(cfg)
    out = {"emb": ("emb",), "norm_g": ("norm_g",)}
    if not dm["tied"]:
        out["head"] = ("head",)
    out.update({f"B{li}": ("blocks", li) for li in range(len(dm["pattern"]))})
    return out


def scan_flops_per_token(dm: dict, chunk: int) -> float:
    """Matrix-unit operations a token of ONE state-space layer's scan needs
    in one pass, as the chunked form's least (two operations a
    multiply-accumulate): inside a chunk of ``chunk`` positions the causal
    half of ``C B^T`` (once a GROUP) and of the masked scores times ``dt x``
    (a head), the chunk's closing state and the carried state's contribution
    (``N P`` multiply-accumulates a head each)."""
    half = (chunk + 1) / 2.0
    return 2.0 * (dm["G"] * half * dm["N"] +
                  dm["H"] * dm["P"] * (half + 2 * dm["N"]))


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Matrix-unit operations a token needs in ONE forward pass, by part (two
    operations a multiply-accumulate): an ``M`` layer's two projections and
    its scan (:func:`scan_flops_per_token` at ``chunk_size``); an ``E``
    layer's router, shared expert (two products) and the routed experts'
    two products over the ``top_k x held / router`` pairs a token sends to
    the held experts on average; a ``*`` layer's four projections and causal
    attention at the half it needs; the head pass.  Each is of ONE layer."""
    dm = dims(cfg)
    d, hd, inner = dm["d"], dm["hd"], dm["H"] * dm["P"]
    chunk = min(int(cfg.get("chunk_size", 128)), seq_len)
    width = 2 * inner + 2 * dm["G"] * dm["N"] + dm["H"]
    return {
        "M": 2.0 * d * width + 2.0 * inner * d +
        scan_flops_per_token(dm, chunk),
        "E_router": 2.0 * d * dm["router"],
        "E_shared": 4.0 * d * dm["shared_ff"],
        "E_routed": dm["top_k"] * dm["held"] / dm["router"] * 4.0 * d *
        dm["moe_ff"],
        # projections; QK^T and PV, each 2 t heads head_dim a token, halved
        "*": 2.0 * d * hd * (2 * dm["heads"] + 2 * dm["kv_heads"]) +
        2.0 * seq_len * dm["heads"] * hd,
        "head": 2.0 * d * dm["vocab"],
    }


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens needs,
    forward and backward (three passes): :func:`forward_flops_per_token` by
    the pattern's counts.  The embedding lookup, the convolution, the gates
    and the norms are no products, and nothing that is recomputed counts."""
    parts = forward_flops_per_token(cfg, seq_len)
    pattern = dims(cfg)["pattern"]
    per_token = pattern.count("M") * parts["M"] + pattern.count("E") * (
        parts["E_router"] + parts["E_shared"] + parts["E_routed"]) + \
        pattern.count("*") * parts["*"] + parts["head"]
    return 3.0 * seq_len * per_token


# -- seeded weights and tokens ------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _layer_shapes(dm: dict, kind: str) -> dict:
    d, hd, inner = dm["d"], dm["hd"], dm["H"] * dm["P"]
    conv = inner + 2 * dm["G"] * dm["N"]
    if kind == "attention":
        return {"ln1_g": (d,), "wq": (d, dm["heads"] * hd),
                "wk": (d, dm["kv_heads"] * hd),
                "wv": (d, dm["kv_heads"] * hd), "wo": (dm["heads"] * hd, d)}
    if kind == "mamba":
        return {"ln1_g": (d,), "ssm_in": (d, inner + conv + dm["H"]),
                "ssm_conv_k": (dm["taps"], conv), "ssm_conv_b": (conv,),
                "ssm_dt_b": (dm["H"],), "ssm_a_log": (dm["H"],),
                "ssm_d": (dm["H"],),
                "ssm_g": (inner,) if dm["G"] == 1 else
                (dm["G"], inner // dm["G"]),
                "ssm_out": (inner, d)}
    out = {"ln2_g": (d,), "gate": (d, dm["router"]),
           "ebias": (dm["router"],),
           "ew1": (dm["held"], d, dm["moe_ff"]),
           "ew2": (dm["held"], dm["moe_ff"], d)}
    if dm["shared_ff"]:
        out.update({"sw1": (d, dm["shared_ff"]), "sw2": (dm["shared_ff"], d)})
    return out


def _make_leaf(key, name: str, shape, share: int = 0):
    """Projections normal ``1/sqrt(fan_in)``; gains ``1 + normal 0.05`` (so
    that no gain's gradient hides behind another's); the convolution's taps
    normal ``1/sqrt(taps)`` and its bias normal 0.1 (so that it bites); as
    Mamba-2 starts them: the decay rates ``A`` uniform 1 .. 16
    (``ssm_a_log`` their log), the step sizes log-uniform 0.001 .. 0.1
    (``ssm_dt_b`` their inverse softplus), the skip ``ssm_d`` 1.

    The router is seeded balanced over the chips, as a trained one is.  The
    selection bias is at the scale of the scores' spread (0.1 against a
    deviation of 0.2), so that the selection differs from the plain top k
    of the scores: every chip's share of ``share`` experts carries the same
    values, 0.1 x the normal quantiles, in an order of its own from the
    seed.  And a share's ``gate`` columns are ``share / 2`` random
    directions and their NEGATIVES, the two experts of such a pair carrying
    one bias value: the normed stream behind squared-ReLU layers has a
    component all tokens share (2 % of its energy at the first ``E`` layer,
    13 % at the fourth, seeded), which favours one expert of a pair as it
    disfavours the other, so the pairs a chip's share receives depend on
    the seed in second order only.  Drawn independently they ranged over
    40,096-57,668 pairs a step with the seed and the rate followed them to
    the fourth digit (3.7515-3.7148 samples/s, my chip runs, PR 45; PERF.md
    section 6)."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, sum(ord(c) * (i + 1)
                                    for i, c in enumerate(name)))
    paired = share and share % 2 == 0
    if name == "ssm_d":
        return jnp.ones(shape, jnp.float32)
    if name == "ssm_a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if name == "ssm_dt_b":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "ebias":
        n = share // 2 if paired else share
        values = np.float32(0.1) * jax.scipy.special.ndtri(
            (jnp.arange(n, dtype=jnp.float32) + 0.5) / n)
        return jnp.concatenate([
            jnp.tile(jax.random.permutation(jax.random.fold_in(k, chip),
                                            values), 2 if paired else 1)
            for chip in range(shape[0] // share)])
    if name == "gate" and paired:
        half = jax.random.normal(k, (shape[0], shape[1] // share, 1,
                                     share // 2), jnp.float32)
        return jnp.concatenate([half, -half], axis=2).reshape(shape) / \
            np.float32(np.sqrt(shape[0]))
    n = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + np.float32(0.05) * n
    if name == "ssm_conv_b":
        return np.float32(0.1) * n
    return n / np.float32(np.sqrt(shape[-2] if len(shape) > 1 else shape[0]))


@functools.lru_cache(maxsize=None)
def _makers(dims_key: str):
    import jax
    import jax.numpy as jnp

    dm = _dims_of(dims_key)
    d = dm["d"]

    def layer(key, li):
        k = jax.random.fold_in(key, li + 1)
        return {name: _make_leaf(k, name, shape, dm["held"])
                for name, shape in _layer_shapes(
                    dm, KINDS[dm["pattern"][li]]).items()}

    def emb(key):
        return jax.random.normal(jax.random.fold_in(key, 0x0E),
                                 (dm["vocab"], d), jnp.float32) * \
            np.float32(_EMB_STD)

    def small(name, tag, shape):
        return lambda key: _make_leaf(jax.random.fold_in(key, tag), name,
                                      shape)

    mk = {"layer": layer, "emb": emb,
          "norm_g": small("norm_g", 0x4E, (d,))}
    if not dm["tied"]:
        mk["head"] = small("head", 0x4D, (d, dm["vocab"]))

    def whole(key):
        out = {g: fn(key) for g, fn in mk.items() if g != "layer"}
        out["blocks"] = [layer(key, li) for li in range(len(dm["pattern"]))]
        return out

    return {**{g: jax.jit(fn) for g, fn in mk.items() if g != "layer"},
            "layer": jax.jit(layer, static_argnums=1),
            "whole": jax.jit(whole)}


def _key_of(cfg: dict) -> str:
    import json
    return json.dumps(dims(cfg), sort_keys=True)


def init_params(seed: int, cfg: dict):
    """The whole float32 pytree (``emb``, ``norm_g``, ``blocks``; ``head``
    where untied) on the default device, in one jitted call."""
    return _makers(_key_of(cfg))["whole"](_root_key(seed))


def init_leaf_group(seed: int, cfg: dict, group: str):
    """One group of :func:`leaf_groups` -> its leaves as the step's pytree
    holds them there, bit-identical with :func:`init_params`."""
    mk, key = _makers(_key_of(cfg)), _root_key(seed)
    if group.startswith("B"):
        return mk["layer"](key, int(group[1:]))
    return mk[group](key)


def make_tokens(seed: int, cfg: dict, seq_len: int, start: int, stop: int):
    """Rows ``[start, stop)`` of the seeded token set, ``seq_len + 1`` ids
    each, uniform over the vocabulary slice; every row has a generator of
    its own.  Inputs are ``row[:-1]``, labels ``row[1:]``."""
    vocab = int(cfg["vocab_size"])
    rows = [np.random.default_rng([int(seed), 0x1F2, r]).integers(
        0, vocab, seq_len + 1).astype(np.int32) for r in range(start, stop)]
    return np.stack(rows)


# -- the layers ------------------------------

def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _relu2(v):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(v, 0))


def _attention(p, u, dm, q, out):
    """Causal attention of one row ``u (t, d)`` with no positional encoding,
    a block of queries at a time (``lax.map`` over blocks, each
    checkpointed)."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads, kv, hd = dm["heads"], dm["kv_heads"], dm["hd"]
    qh = out(q(u) @ q(p["wq"])).reshape(t, heads, hd)
    kh = out(q(u) @ q(p["wk"])).reshape(t, kv, hd)
    vh = out(q(u) @ q(p["wv"])).reshape(t, kv, hd)
    if kv != heads:
        kh, vh = (jnp.repeat(a, heads // kv, axis=1) for a in (kh, vh))
    block = min(_Q_BLOCK, t)
    fill = -t % block
    qp = jnp.pad(qh, ((0, fill), (0, 0), (0, 0))).reshape(-1, block, heads, hd)
    pos = jnp.arange(t + fill).reshape(-1, block)
    keys = jnp.arange(t)

    @jax.checkpoint
    def one_block(args):
        qb, at = args
        s = out(jnp.einsum("qhd,khd->hqk", q(qb), q(kh))) / \
            np.float32(np.sqrt(hd))
        a = jax.nn.softmax(jnp.where(keys[None, None, :] <= at[None, :, None],
                                     s, -jnp.inf), axis=-1)
        return out(jnp.einsum("hqk,khd->qhd", q(a), q(vh)))

    o = jax.lax.map(one_block, (qp, pos)).reshape(-1, heads * hd)[:t]
    return out(q(o) @ q(p["wo"]))


def recurrence(x, dt, a, bm, cm, skip):
    """The state-space recurrence of one row, literally: ``x (t, H, P)``,
    ``dt (t, H)`` (after the softplus), ``a (H,)``, ``bm``, ``cm`` ``(t, G,
    N)`` (head ``i`` reads group ``i // (H / G)``), ``skip (H,)`` -> ``(y (t,
    H, P), the state behind the last position (H, P, N))``.  One position a
    step of a ``lax.scan``; blocks of ``_T_BLOCK`` positions are
    checkpointed."""
    import jax
    import jax.numpy as jnp

    t, heads = x.shape[:2]
    per = heads // bm.shape[1]
    block = min(_T_BLOCK, t)
    fill = -t % block

    def one(h, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h, c_h = (jnp.repeat(v, per, axis=0) for v in (b_t, c_t))  # (H, N)
        h = jnp.exp(dt_t * a)[:, None, None] * h + \
            (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return h, (h * c_h[:, None, :]).sum(-1) + skip[:, None] * x_t

    @jax.checkpoint
    def positions(h, inp):
        return jax.lax.scan(one, h, inp)

    # positions that fill the last block have dt = 0: the state passes them
    chunks = tuple(jnp.pad(v, ((0, fill),) + ((0, 0),) * (v.ndim - 1)
                           ).reshape(-1, block, *v.shape[1:])
                   for v in (x, dt, bm, cm))
    h0 = jnp.zeros((heads, x.shape[2], bm.shape[2]), jnp.float32)
    last, y = jax.lax.scan(positions, h0, chunks)
    return y.reshape(-1, *x.shape[1:])[:t], last


def _mamba(p, u, dm, q, out):
    """A state-space mixer on one row ``u (t, d)`` -> ``(out (t, d), (mean
    of exp(dt A), RMS of the last state))``.  In the control precision the
    operands of what the chunked form turns into products (``x``, ``B``,
    ``C``, the convolution's input and taps) are rounded."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads, pd, n, g, taps = dm["H"], dm["P"], dm["N"], dm["G"], dm["taps"]
    inner = heads * pd
    proj = out(q(u) @ q(p["ssm_in"]))
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * g * n], axis=-1)
    xp = jnp.pad(q(xbc), ((taps - 1, 0), (0, 0)))
    kq = q(p["ssm_conv_k"])
    xbc = jax.nn.silu(out(sum(kq[j] * xp[j:j + t] for j in range(taps)))
                      + p["ssm_conv_b"])
    x, bm, cm = jnp.split(q(xbc), [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt + p["ssm_dt_b"])
    a = -jnp.exp(p["ssm_a_log"])
    y, last = recurrence(x.reshape(t, heads, pd), dt, a,
                         bm.reshape(t, g, n), cm.reshape(t, g, n), p["ssm_d"])
    y = out(y).reshape(t, inner) * jax.nn.silu(z)
    stats = jax.lax.stop_gradient(jnp.stack(
        [jnp.exp(dt * a).mean(), jnp.sqrt((last * last).mean())]))
    # the gated norm a group: each group's entries have a statistic of
    # their own
    y = _rms(y.reshape(t, g, inner // g), p["ssm_g"].reshape(g, inner // g),
             dm["eps"]).reshape(t, inner)
    return out(q(y) @ q(p["ssm_out"])), stats


def _unit(v, w1, w2, q, out):
    """The family's expert, routed or shared: ``w2 relu(v w1)^2``."""
    return out(q(_relu2(out(q(v) @ q(w1)))) @ q(w2))


def _experts(p, v, dm, q, out):
    """The routed experts' part of one row ``v (t, d)`` for the experts
    ``first .. first + held`` and, once, the shared expert."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(out(q(v) @ q(p["gate"])))            # (t, router)
    sel = s + jax.lax.stop_gradient(p["ebias"])
    _, choice = jax.lax.top_k(jax.lax.stop_gradient(sel), dm["top_k"])
    w = jnp.take_along_axis(s, choice, axis=1)
    if dm["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * np.float32(dm["scale"])

    @jax.checkpoint
    def one(y, args):              # one held expert, masked
        e, w1, w2 = args
        we = (w * (choice == e)).sum(-1)                    # (t,)
        return y + we[:, None] * _unit(v, w1, w2, q, out), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(v), (
        dm["first"] + jnp.arange(p["ew1"].shape[0]), p["ew1"], p["ew2"]))
    if "sw1" in p:
        y = y + _unit(v, p["sw1"], p["sw2"], q, out)
    return y


def _layer(p, x, dm, kind, q, out):
    """One layer on one row ``x (t, d)`` -> ``(y, the state-space readings
    (2,), zeros for another kind)``."""
    import jax.numpy as jnp

    stats = jnp.zeros(2, jnp.float32)
    if kind == "attention":
        f = _attention(p, _rms(x, p["ln1_g"], dm["eps"]), dm, q, out)
    elif kind == "mamba":
        f, stats = _mamba(p, _rms(x, p["ln1_g"], dm["eps"]), dm, q, out)
    else:
        f = _experts(p, _rms(x, p["ln2_g"], dm["eps"]), dm, q, out)
    return x + f, stats


def _close(tp, x, labels, dm, n_tokens, q, out):
    """The final norm and the head pass of one row -> the row's part of
    the loss; ``tp`` holds ``norm_g`` and the head's matrix (``head (d,
    vocab)``; the embedding where tied)."""
    import jax
    import jax.numpy as jnp

    h = _rms(x, tp["norm_g"], dm["eps"])
    head = q(tp["emb"]).T if dm["tied"] else q(tp["head"])
    total = jnp.zeros((), jnp.float32)
    for lo in range(0, x.shape[0], _HEAD_CHUNK):
        hi = lo + _HEAD_CHUNK
        logits = out(q(h[lo:hi]) @ head)
        logp = jax.nn.log_softmax(logits, axis=-1)
        total = total - jnp.take_along_axis(logp, labels[lo:hi, None],
                                            axis=-1).sum()
    return total / n_tokens


@functools.lru_cache(maxsize=None)
def _programs(dims_key: str, precision: str):
    import jax

    from reference.precision import operand, product

    dm = _dims_of(dims_key)
    q, out = operand(precision), product(precision)

    def layer(p, x, kind):
        return _layer(p, x, dm, kind, q, out)

    def layer_vjp(p, x, ct, kind):
        _, vjp, _ = jax.vjp(lambda p_, x_: layer(p_, x_, kind), p, x,
                            has_aux=True)
        return vjp(ct)                                   # (dp, dx)

    def close_grad(tp, x, labels, n_tokens):
        return jax.value_and_grad(
            lambda tp_, x_: _close(tp_, x_, labels, dm, n_tokens, q, out),
            (0, 1))(tp, x)                               # loss, (d_tp, dx)

    return {"layer": jax.jit(layer, static_argnums=2),
            "layer_vjp": jax.jit(layer_vjp, static_argnums=3),
            "close_grad": jax.jit(close_grad, static_argnums=3)}


# -- training ------------------------------

def _flat(tree, prefix: str) -> dict:
    """``{dotted name: leaf}`` of an array or a nested dict of them."""
    if not isinstance(tree, dict):
        return {prefix: tree}
    out = {}
    for k, v in tree.items():
        out.update(_flat(v, f"{prefix}.{k}"))
    return out


def first_steps(seed: int, cfg: dict, traffic: dict, chips: int,
                precision: str = "f32", steps: int = 3) -> dict:
    """Follow the program's first ``steps`` steps on rows in storage
    order: ``minibatch_size`` sequences a step, plain SGD at the
    configuration's learning rate; a row and a layer at a time, forward
    through the layers and back through them, the rows' gradients summed."""
    import jax
    import jax.numpy as jnp

    if chips != 1:
        raise ValueError("the reference follows a one-chip step")
    dm = dims(cfg)
    kinds = [KINDS[c] for c in dm["pattern"]]
    layers, n_ssm = len(kinds), max(kinds.count("mamba"), 1)
    lr = float(cfg["hyper"]["lr"])
    batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    n_tokens = batch * t
    prog = _programs(_key_of(cfg), precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    take = jax.jit(lambda e, i: e[i])
    scatter = jax.jit(lambda d, i, ct: d.at[i].add(ct))
    result = {"loss": [], "ssm": [], "grad_norm": {}, "delta_norm": {},
              "grad_first": {}}
    tail_groups = tuple(g for g in leaf_groups(cfg) if not g.startswith("B"))

    def accumulate(acc, g):
        return g if acc is None else add(acc, g)

    with jax.default_matmul_precision("highest"):
        params = init_params(seed, cfg)
        blocks = params["blocks"]
        for s in range(steps):
            rows = make_tokens(seed, cfg, t, s * batch, (s + 1) * batch)
            tokens, labels = rows[:, :-1], rows[:, 1:]
            tp = {g: params[g] for g in tail_groups}
            d_tp, d_blocks = None, [None] * layers
            d_emb = jnp.zeros_like(params["emb"])
            loss, readings = 0.0, np.zeros(2)
            for r in range(batch):
                ids = jnp.asarray(tokens[r])
                h, ins = take(params["emb"], ids), []
                for li in range(layers):
                    ins.append(h)
                    h, stats = prog["layer"](blocks[li], h, kinds[li])
                    readings += np.asarray(stats, np.float64)
                part, (g_tp, ct) = prog["close_grad"](
                    tp, h, jnp.asarray(labels[r]), n_tokens)
                loss += float(part)
                d_tp = accumulate(d_tp, g_tp)
                for li in reversed(range(layers)):
                    dp, ct = prog["layer_vjp"](blocks[li], ins[li], ct,
                                               kinds[li])
                    d_blocks[li] = accumulate(d_blocks[li], dp)
                del g_tp, dp
                d_emb = scatter(d_emb, ids, ct)
                del ins, ct
            # a tied head's gradient reached ``emb`` through ``tp``
            d_tp["emb"] = add(d_tp["emb"], d_emb)
            result["loss"].append(loss)
            result["ssm"].append({
                "decay_mean": float(readings[0]) / (batch * n_ssm),
                "final_state_rms": float(readings[1]) / (batch * n_ssm)})

            def step_leaf(name, w, g):
                new = sgd(w, g)
                if s == 0:
                    # the gradient as SGD applied it: (w0 - w1) / lr
                    result["grad_norm"][name] = float(norm(w, new)) / lr
                    if name.rsplit(".", 1)[-1] in KEEP:
                        result["grad_first"][name] = np.asarray(
                            (w - new) / np.float32(lr))
                return new

            for g in tail_groups:
                params[g] = step_leaf(g, params[g], d_tp[g])
            for li in range(layers):
                blocks[li] = {k: step_leaf(f"B{li}.{k}", w, d_blocks[li][k])
                              for k, w in blocks[li].items()}
            del d_emb, d_tp, d_blocks, tp
        for group, path in leaf_groups(cfg).items():
            new = params[path[0]] if len(path) == 1 else blocks[path[1]]
            old = _flat(init_leaf_group(seed, cfg, group), group)
            for name, w in _flat(new, group).items():
                result["delta_norm"][name] = float(norm(w, old[name]))
            del old
    return result
