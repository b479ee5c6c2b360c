"""Plain reference of the ``solar_open2_250b`` configuration, cut as its file
says: a ``solar_open2`` decoder (Upstage's Solar Open 2: Kimi-delta linear
attention and gated position-free grouped-query attention mixed, routed
experts beside a shared one in every layer) in straightforward ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``: loss, every
gradient and plain SGD.  No kernel, no chunked form, no sort, no grouped
product, nothing of the program imported: a linear layer's state is WALKED
POSITION BY POSITION (:func:`recurrence`), so that an error in the program's
chunked rule (its decay factors, its triangular inverse, its carry) cannot
hide in it.

With ``d`` the hidden size and ``RMSNorm(a; g) = a / sqrt(mean(a^2) + eps) *
g``:

- *The model.*  ``x_0 = emb[tokens]``; the layers; ``h = RMSNorm(x; norm_g)``;
  ``logits = h head`` (untied; the embedding's transpose where
  ``tie_word_embeddings``), taken in chunks of tokens; the loss the mean
  next-token cross-entropy.
- *A layer* has two norms: ``h = x + Mix(RMSNorm(x; ln1_g))``, ``x' = h +
  F(RMSNorm(h; ln2_g))``.  No bias anywhere unless said.
- *Mix on a linear-attention layer* (every layer ``gqa_layers`` does not
  list), ``H`` heads of ``K`` entries, on the normed input ``u``: ``[q~ | k~
  | v] = silu(conv(u kda_in))``, the convolution depthwise, causal, ``taps``
  taps a channel, zeros before the sequence; ``q = q~ / sqrt(sum(q~^2) +
  1e-6) / sqrt(K)``, ``k = k~ / sqrt(sum(k~^2) + 1e-6)`` over a head's
  entries; the log-decay A KEY CHANNEL ``g = -exp(kda_a_log[head])
  softplus((u kda_f1) kda_f2 + kda_dt_b)``; ``beta = 2 sigmoid(u kda_b)`` a
  head (``kda_allow_neg_eigval``: the 2; 1 without); the state ``(K, K)`` a
  head, zero before the sequence, ``S_t = (I - beta_t k_t k_t^T) Diag(exp
  g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``; ``Mix = (RMSNorm_K(o;
  kda_norm_g) * sigmoid((u kda_g1) kda_g2 + kda_g_b)) kda_out``, the norm over
  each head's entries with ONE gain of ``K``, first, then the gate.
- *Mix on a grouped-query layer*: ``q = u wq`` as ``heads`` heads of
  ``head_dim``, ``k = u wk`` and ``v = u wv`` as ``kv_heads`` heads (query
  head ``j`` reads key/value head ``j // (heads / kv_heads)``), NO rotation,
  no QK-norm, causal softmax of ``q k^T / sqrt(head_dim)``, ``o = (sigmoid(u
  wg) * heads' output) wo``.
- *F* in every layer: ``s = sigmoid(u gate)`` over ALL ``router`` experts; the
  selected set is the top k of ``s + ebias`` (the selection bias: no
  gradient, no update; one group); ``w_e = scale * s_e / (sum over the
  selected of s + 1e-20)``; the sum over the selected experts *this chip
  holds* of ``w_e SwiGLU_e(u)``: a loop over the held experts with masks.
  What the absent experts would add is left out, here as in the program, the
  weights still normalised over all k selected.  Beside it the shared SwiGLU
  ``sw2 (silu(u sw1) * (u sw3))``, once, for every token.

Departures from the published description, the first the program's and the
rest under ``assumed`` in the configuration file: the program's router
(``moe.route_top_k``) adds 1e-6 to the selected scores' sum where this
reference adds 1e-20 (eight sigmoid scores sum to about 4: 2.5e-7 relative,
far inside every tolerance); ``kda_use_full_proj`` false is read as the
low-rank pairs of Kimi Linear; ``use_gqa_gate`` as a sigmoid gate of the
layer's normed input on the heads' output; the router's scores as sigmoid
with a selection bias (the DeepSeek-V3 keys the config carries);
``hidden_act`` as silu; how the weights are seeded (:func:`_make_leaf`);
plain SGD for the model's own optimizer.

The guide's share test is ``tests/test_solar_open2_arch.py``'s: the routed
parts of all the shares of a layer and the shared expert counted once add up
to the uncut layer (:func:`_experts` with ``first`` 0 and every expert held).

It also owns the seeded weights and token rows.  One jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, group by group, and runs a row and a layer at a time, keeping
every layer's input for the backward walk, the rows' gradients summed: 5.7 GB
of float32 weights and one layer's gradients are all it holds.  A linear
layer's backward walk holds the opening state of every stretch of
:data:`_T_BLOCK` positions and one stretch's states (0.5 + 0.3 GB at 8,192
positions), not every position's.

``first_steps``: the first three steps' loss, each leaf's first gradient as
plain SGD applied it (``(w0 - w1) / lr``), the small leaves' first gradients
themselves (``grad_first``), each leaf's change after three steps, and each
step's linear-attention readings (``kda``: the mean over positions, heads and
channels of ``exp(g)``, the mean ``beta`` and the RMS of a row's last state,
means over the linear layers and rows).
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own size
#: (``benchmark/limits.py`` and ``run.py``; my chip runs, PR 52; PERF.md
#: section 2 has the table): the bfloat16 program over 12 seeds against the
#: fp8 control over 3.  Three numbers decide, each near the geometric mean of
#: its two readings with room on both sides: ``grad_norm_gap`` (the worst
#: leaf's gap in the norm of its first gradient, a router's ``gate`` or a held
#: expert's weight that saw about 205 pairs) reads 0.0014-0.0031 against
#: 0.0156-0.0188: 0.007, 2.2 times over the sound runs' largest and 2.2 under
#: the control's least; ``delta_norm_gap`` (the worst leaf's gap in the norm
#: of its change after three steps, a router's ``gate`` on every seed)
#: 0.0011-0.0044 against 0.0146-0.0153: 0.010, 2.3 times over and 1.46 under
#: (the sound runs scatter four times, a flipped selection's cost, where the
#: control's three stand within 5 %: the more room above);
#: ``grad_diff_gap`` (the worst small leaf's first gradient, norm of the
#: difference; layer 1's router ``gate`` on every seed: of 8,192 x 8
#: selections a few per cent differ between a bfloat16 stream and the float32
#: one) 0.085-0.135 against 0.237-0.244: 0.18, 1.33 times over and 1.31
#: under.  The other two stand where the precision hardly moves a number
#: against its own scatter, between the readings and 1 with the more room
#: above, and hold what they can.  ``loss_gap`` reads 8.7e-6-5.6e-5 against
#: 1.3e-4-2.6e-4, apart by 2.4 times where the seeds scatter it 6 times: the
#: accepted cells' 0.0015, 27 times the largest reading.  ``kda_state_gap``
#: (builder ``lm_train_kda``: the first step's RMS of a linear layer's state
#: behind a row's last position, relative gap to this file's walk) reads
#: 7.9e-6-1.1e-4 against 1.7e-4-1.2e-3, which overlap within a seed's scatter:
#: 0.01, 90 times the largest reading, where a carry dropped at one chunk's
#: edge reads percents and more.  So the control is refused by three limits
#: in every run, and by any one of them alone.
LIMITS = {
    "loss_gap": 0.0015,
    "grad_norm_gap": 0.007,
    "delta_norm_gap": 0.010,
    "grad_diff_gap": 0.18,
    "kda_state_gap": 0.01,
}

#: leaves small enough to keep whole for ``grad_diff_gap``: the gains, the
#: routers, the grouped-query layer's key and value projections, and the
#: leaves that see a linear layer's state and little else: the decay's rates
#: and bias, ``beta``'s projection, the convolutions' taps, the head norm's
#: gain, the low-rank pairs and the gate's bias
KEEP = ("ln1_g", "ln2_g", "norm_g", "gate", "wk", "wv", "kda_a_log",
        "kda_dt_b", "kda_b", "kda_conv_k", "kda_norm_g", "kda_f1", "kda_f2",
        "kda_g1", "kda_g2", "kda_g_b")

#: queries a block of the reference's attention, tokens a chunk of its head,
#: positions a checkpointed stretch of the recurrence
_Q_BLOCK, _HEAD_CHUNK, _T_BLOCK = 512, 1024, 64


@functools.lru_cache(maxsize=None)
def _dims_of(key: str):
    import json
    return json.loads(key)


def dims(cfg: dict) -> dict:
    """The sizes the reference runs, from the configuration as run."""
    layers = int(cfg["num_hidden_layers"])
    full = cfg.get("gqa_layers")
    if full is None:
        full = range(0, layers, int(cfg.get("gqa_interval", 3)) + 1)
    full = sorted(int(i) for i in full)
    if full and not 0 <= full[0] <= full[-1] < layers:
        raise ValueError(f"gqa_layers {full} outside the {layers} layers")
    lin = cfg["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("linear_attn_config.num_kv_heads: as many key/value "
                         "as query heads is what is written")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    router = int(cfg.get("router_width", cfg.get("n_routed_experts", 0)))
    held = cfg.get("experts_held") or {"first": 0, "count": router}
    moe_ff = int(cfg.get("moe_intermediate_size", 0))
    return {
        "d": d, "heads": heads,
        "kv_heads": int(cfg.get("num_key_value_heads", heads)),
        "hd": int(cfg.get("head_dim") or d // heads),
        "vocab": int(cfg["vocab_size"]),
        "kinds": ["gqa" if i in full else "kda" for i in range(layers)],
        "H": int(lin["num_heads"]), "K": int(lin["head_dim"]),
        "rank": int(lin["head_dim"]),
        "taps": int(lin.get("short_conv_kernel_size", 4)),
        "neg_eigval": bool(cfg.get("kda_allow_neg_eigval", False)),
        "eps": float(cfg.get("rms_norm_eps", 1e-5)),
        "router": router, "first": int(held["first"]),
        "held": int(held["count"]),
        "top_k": int(cfg.get("num_experts_per_tok", 1)),
        "moe_ff": moe_ff,
        "shared_ff": int(cfg.get("n_shared_experts", 0)) * moe_ff,
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
    out.update({f"B{li}": ("blocks", li) for li in range(len(dm["kinds"]))})
    return out


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Matrix-unit operations a token needs in ONE forward pass, by part (two
    operations a multiply-accumulate), each of ONE layer.  ``kda``: the ``q |
    k | v`` and output projections, the two low-rank pairs, ``beta``'s
    projection, and the delta rule counted by the RECURRENCE's own products,
    ``7 K^2`` a head a position (the decay ``K^2``, ``k^T S``, the rank-one
    update and ``S^T q`` ``2 K^2`` each), whatever a chunked form does
    beside them; ``gqa``: the five projections (q, k, v, the gate, the
    output) and ``QK^T`` and ``PV`` over the causal pairs; a layer's router,
    shared expert (three products) and the routed experts' three products
    over the ``top_k x held / router`` pairs a token sends to the held
    experts on average; the head pass."""
    dm = dims(cfg)
    d, hd, heads = dm["d"], dm["hd"], dm["heads"]
    inner = dm["H"] * dm["K"]
    pairs = seq_len * (seq_len + 1) // 2
    return {
        "kda": 2.0 * d * 4 * inner + 4.0 * dm["rank"] * (d + inner) +
        2.0 * d * dm["H"] + 7.0 * dm["H"] * dm["K"] * dm["K"],
        "gqa": 2.0 * d * hd * (3 * heads + 2 * dm["kv_heads"]) +
        4.0 * heads * hd * pairs / seq_len,
        "router": 2.0 * d * dm["router"],
        "shared": 6.0 * d * dm["shared_ff"],
        "routed": dm["top_k"] * dm["held"] / max(dm["router"], 1) * 6.0 * d *
        dm["moe_ff"],
        "head": 2.0 * d * dm["vocab"],
    }


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens needs,
    forward and backward (three passes): :func:`forward_flops_per_token` by
    the layers' kinds.  The embedding lookup, the convolutions, the gates'
    sigmoids and the norms are no products, and nothing that is recomputed
    or that only a chunked form does (its triangular inverses) counts."""
    parts = forward_flops_per_token(cfg, seq_len)
    per_token = parts["head"] + sum(
        parts[kind] + parts["router"] + parts["shared"] + parts["routed"]
        for kind in dims(cfg)["kinds"])
    return 3.0 * seq_len * per_token


# -- seeded weights and tokens ------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _layer_shapes(dm: dict, li: int) -> dict:
    d, hd, heads, kv = dm["d"], dm["hd"], dm["heads"], dm["kv_heads"]
    out = {"ln1_g": (d,), "ln2_g": (d,)}
    if dm["kinds"][li] == "gqa":
        out.update({"wq": (d, heads * hd), "wk": (d, kv * hd),
                    "wv": (d, kv * hd), "wo": (heads * hd, d),
                    "wg": (d, heads * hd)})
    else:
        inner, rank = dm["H"] * dm["K"], dm["rank"]
        out.update({"kda_in": (d, 3 * inner),
                    "kda_conv_k": (dm["taps"], 3 * inner),
                    "kda_f1": (d, rank), "kda_f2": (rank, inner),
                    "kda_dt_b": (inner,), "kda_a_log": (dm["H"],),
                    "kda_b": (d, dm["H"]), "kda_g1": (d, rank),
                    "kda_g2": (rank, inner), "kda_g_b": (inner,),
                    "kda_norm_g": (dm["K"],), "kda_out": (inner, d)})
    f, e = dm["moe_ff"], dm["held"]
    out.update({"gate": (d, dm["router"]), "ebias": (dm["router"],),
                "ew1": (e, d, f), "ew3": (e, d, f), "ew2": (e, f, d)})
    if dm["shared_ff"]:
        out.update({"sw1": (d, dm["shared_ff"]), "sw3": (d, dm["shared_ff"]),
                    "sw2": (dm["shared_ff"], d)})
    return out


def _make_leaf(key, name: str, shape, share: int = 0):
    """Projections (the convolutions' taps and the low-rank pairs among them)
    normal ``1/sqrt(fan_in)``; gains ``1 + normal 0.05`` (so that no gain's
    gradient hides behind another's); the gate's bias normal 0.1.  A linear
    layer's decay as Kimi Linear's code starts it: ``kda_a_log =
    log(uniform(1, 16))`` a head and ``kda_dt_b`` the inverse softplus of a
    step log-uniform in [0.001, 0.1] a channel, so that the decays a
    position lie near 1 and a state reaches back hundreds of positions.

    The router is seeded balanced over the shares, as a trained one is, the
    way ``reference/afmoe.py`` balances its own.  The selection bias is at
    the scale of the scores' spread (0.1 against a deviation of 0.2), so
    that the selection differs from the plain top k of the scores: every
    share of ``share`` experts carries the same values, 0.1 x the normal
    quantiles, in an order of its own from the seed.  And a share's ``gate``
    columns are ``share / 2`` random directions and their NEGATIVES, the two
    experts of such a pair carrying one bias value: a component of the normed
    stream that all tokens share favours one expert of a pair as it
    disfavours the other, so the pairs a share receives depend on the seed in
    second order only."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, sum(ord(c) * (i + 1)
                                    for i, c in enumerate(name)))
    paired = share and share % 2 == 0
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
    if name == "kda_a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if name == "kda_dt_b":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    n = jax.random.normal(k, shape, jnp.float32)
    if name == "kda_g_b":
        return np.float32(0.1) * n
    if name.endswith("_g"):
        return 1.0 + np.float32(0.05) * n
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
                for name, shape in _layer_shapes(dm, li).items()}

    def emb(key):
        # normal 1.0: a token's own vector leads the stream entering layer 0
        return jax.random.normal(jax.random.fold_in(key, 0x0E),
                                 (dm["vocab"], d), jnp.float32)

    def small(name, tag, shape):
        return lambda key: _make_leaf(jax.random.fold_in(key, tag), name,
                                      shape)

    mk = {"layer": layer, "emb": emb,
          "norm_g": small("norm_g", 0x4E, (d,))}
    if not dm["tied"]:
        mk["head"] = small("head", 0x4D, (d, dm["vocab"]))

    def whole(key):
        out = {g: fn(key) for g, fn in mk.items() if g != "layer"}
        out["blocks"] = [layer(key, li) for li in range(len(dm["kinds"]))]
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


def recurrence(q, k, v, g, beta):
    """The delta rule of one row, literally: ``q``, ``k`` ``(t, H, K)``, ``v
    (t, H, V)``, ``g (t, H, K)`` (the log-decays), ``beta (t, H)`` -> ``(o
    (t, H, V), the state behind the last position (H, K, V))``: ``S_t = (I -
    beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t =
    S_t^T q_t``.  One position a step of a ``lax.scan``; stretches of
    ``_T_BLOCK`` positions are checkpointed."""
    import jax
    import jax.numpy as jnp

    t, heads, width = q.shape
    block = min(_T_BLOCK, t)
    fill = -t % block

    def one(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s
        seen = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    @jax.checkpoint
    def positions(s, inp):
        return jax.lax.scan(one, s, inp)

    # positions that fill the last stretch have g = 0 and beta = 0: the
    # state passes them
    stretches = tuple(jnp.pad(a, ((0, fill),) + ((0, 0),) * (a.ndim - 1)
                              ).reshape(-1, block, *a.shape[1:])
                      for a in (q, k, v, g, beta))
    s0 = jnp.zeros((heads, width, v.shape[2]), jnp.float32)
    last, o = jax.lax.scan(positions, s0, stretches)
    return o.reshape(-1, *v.shape[1:])[:t], last


def _kda(p, u, dm, q, out):
    """A linear-attention mixer on one row ``u (t, d)`` -> ``(out (t, d),
    (mean of exp(g), mean beta, RMS of the last state))``.  In the control
    precision the operands of what a chunked form turns into products (q, k,
    v, the convolution's input and taps) are rounded."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads, width, taps = dm["H"], dm["K"], dm["taps"]
    inner = heads * width
    proj = out(q(u) @ q(p["kda_in"]))
    xp = jnp.pad(q(proj), ((taps - 1, 0), (0, 0)))
    kq = q(p["kda_conv_k"])
    qkv = jax.nn.silu(out(sum(kq[j] * xp[j:j + t] for j in range(taps))))
    qh, kh, vh = (a.reshape(t, heads, width)
                  for a in jnp.split(qkv, 3, axis=-1))
    qh = qh / jnp.sqrt((qh * qh).sum(-1, keepdims=True) + 1e-6) / \
        np.float32(np.sqrt(width))
    kh = kh / jnp.sqrt((kh * kh).sum(-1, keepdims=True) + 1e-6)
    pre = out(q(out(q(u) @ q(p["kda_f1"]))) @ q(p["kda_f2"])) + p["kda_dt_b"]
    g = -jnp.exp(p["kda_a_log"])[:, None] * \
        jax.nn.softplus(pre).reshape(t, heads, width)
    beta = jax.nn.sigmoid(out(q(u) @ q(p["kda_b"])))
    if dm["neg_eigval"]:
        beta = 2.0 * beta
    o, last = recurrence(q(qh), q(kh), q(vh), g, beta)
    stats = jax.lax.stop_gradient(jnp.stack(
        [jnp.exp(g).mean(), beta.mean(), jnp.sqrt((last * last).mean())]))
    gate = jax.nn.sigmoid(
        out(q(out(q(u) @ q(p["kda_g1"]))) @ q(p["kda_g2"])) + p["kda_g_b"])
    y = _rms(out(o), p["kda_norm_g"], dm["eps"]).reshape(t, inner) * gate
    return out(q(y) @ q(p["kda_out"])), stats


def _attention(p, u, dm, q, out):
    """The grouped-query mixer of one row ``u (t, d)``, a block of queries at
    a time against every key (``lax.map`` over blocks, each checkpointed),
    the causal cut as a mask; no rotation."""
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
        a = jax.nn.softmax(jnp.where(
            at[None, :, None] >= keys[None, None, :], s, -jnp.inf), axis=-1)
        return out(jnp.einsum("hqk,khd->qhd", q(a), q(vh)))

    o = jax.lax.map(one_block, (qp, pos)).reshape(-1, heads * hd)[:t]
    o = o * jax.nn.sigmoid(out(q(u) @ q(p["wg"])))
    return out(q(o) @ q(p["wo"]))


def _swiglu(v, w1, w3, w2, q, out):
    """The family's feed-forward unit, routed or shared."""
    import jax

    return out(q(jax.nn.silu(out(q(v) @ q(w1))) * out(q(v) @ q(w3))) @ q(w2))


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
        e, w1, w3, w2 = args
        we = (w * (choice == e)).sum(-1)                    # (t,)
        return y + we[:, None] * _swiglu(v, w1, w3, w2, q, out), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(v), (
        dm["first"] + jnp.arange(p["ew1"].shape[0]), p["ew1"], p["ew3"],
        p["ew2"]))
    if "sw1" in p:
        y = y + _swiglu(v, p["sw1"], p["sw3"], p["sw2"], q, out)
    return y


def _layer(p, x, dm, kind: str, q, out):
    """One layer on one row ``x (t, d)``: both sub-layers, two norms ->
    ``(x', the linear-attention readings (3,), zeros of a grouped-query
    layer)``."""
    import jax.numpy as jnp

    u = _rms(x, p["ln1_g"], dm["eps"])
    stats = jnp.zeros(3, jnp.float32)
    if kind == "kda":
        mix, stats = _kda(p, u, dm, q, out)
    else:
        mix = _attention(p, u, dm, q, out)
    h = x + mix
    return h + _experts(p, _rms(h, p["ln2_g"], dm["eps"]), dm, q, out), stats


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
        _, vjp = jax.vjp(lambda p_, x_: layer(p_, x_, kind)[0], p, x)
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
    kinds = dm["kinds"]
    layers, n_kda = len(kinds), max(kinds.count("kda"), 1)
    lr = float(cfg["hyper"]["lr"])
    batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    n_tokens = batch * t
    prog = _programs(_key_of(cfg), precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    take = jax.jit(lambda e, i: e[i])
    scatter = jax.jit(lambda d, i, ct: d.at[i].add(ct))
    result = {"loss": [], "kda": [], "grad_norm": {}, "delta_norm": {},
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

            def step_leaf(name, w, g):
                new = sgd(w, g)
                if s == 0:
                    # the gradient as SGD applied it: (w0 - w1) / lr
                    result["grad_norm"][name] = float(norm(w, new)) / lr
                    if name.rsplit(".", 1)[-1] in KEEP:
                        result["grad_first"][name] = np.asarray(
                            (w - new) / np.float32(lr))
                return new

            # forward, a row at a time, every layer's input kept; the head
            # pass gives each row's cotangent
            d_tp, ins, cts, loss = None, [], [], 0.0
            readings = np.zeros(3)
            for r in range(batch):
                h, kept = take(params["emb"], jnp.asarray(tokens[r])), []
                for li in range(layers):
                    kept.append(h)
                    h, stats = prog["layer"](blocks[li], h, kinds[li])
                    readings += np.asarray(stats, np.float64)
                part, (g_tp, ct) = prog["close_grad"](
                    tp, h, jnp.asarray(labels[r]), n_tokens)
                loss += float(part)
                d_tp = accumulate(d_tp, g_tp)
                ins.append(kept)
                cts.append(ct)
                del g_tp, h, ct
            result["loss"].append(loss)
            result["kda"].append(dict(zip(
                ("decay_mean", "beta_mean", "final_state_rms"),
                (readings / (batch * n_kda)).tolist())))
            # backward, a layer at a time over the rows, and the layer's
            # update as soon as its gradient is whole: no other layer reads
            # its weights any more in this step, so one layer's gradients
            # are all that is held beside the weights
            for li in reversed(range(layers)):
                d_layer = None
                for r in range(batch):
                    dp, cts[r] = prog["layer_vjp"](
                        blocks[li], ins[r].pop(), cts[r], kinds[li])
                    d_layer = accumulate(d_layer, dp)
                    del dp
                blocks[li] = {k: step_leaf(f"B{li}.{k}", w, d_layer[k])
                              for k, w in blocks[li].items()}
                del d_layer
            d_emb = jnp.zeros_like(params["emb"])
            for r in range(batch):
                d_emb = scatter(d_emb, jnp.asarray(tokens[r]), cts[r])
            del ins, cts
            # a tied head's gradient reached ``emb`` through ``tp``
            d_tp["emb"] = add(d_tp["emb"], d_emb)
            del d_emb
            for g in tail_groups:
                params[g] = step_leaf(g, params[g], d_tp[g])
            del d_tp, tp
        for group, path in leaf_groups(cfg).items():
            new = params[path[0]] if len(path) == 1 else blocks[path[1]]
            old = _flat(init_leaf_group(seed, cfg, group), group)
            for name, w in _flat(new, group).items():
                result["delta_norm"][name] = float(norm(w, old[name]))
            del old
    return result
