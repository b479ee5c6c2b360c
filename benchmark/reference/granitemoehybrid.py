"""Plain reference of the ``granite_4_0_h_micro`` configuration, cut as its
file says: a ``granitemoehybrid`` decoder (Granite 4.0-H; the family's
published modelling code gives the layer, whose state-space layer is Bamba's
Mamba-2) in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: loss, every gradient and plain
SGD.  No kernel, nothing of the program imported, and **the state-space
recurrence literally, position by position**: a ``lax.scan`` over ``t`` that
carries the state, with no chunk, no running sum of decays and no quadratic
form, so that an error in the program's chunk algebra cannot hide in it.
The walk over the positions is checkpointed in blocks of positions (a block's
opening state is kept, its positions' states are made again), so that 8,192
fit.

With ``d`` the hidden size, ``RMSNorm(a; g) = a / sqrt(mean(a^2) + eps) *
g`` and the four multipliers ``m_e`` (``embedding_multiplier``), ``m_a``
(``attention_multiplier``), ``m_r`` (``residual_multiplier``), ``m_l``
(``logits_scaling``):

- *The model.*  ``x_0 = m_e emb[tokens]``; the layers; ``h = RMSNorm(x;
  norm_g)``; ``logits = h emb^T / m_l`` (tied; an untied ``head`` where
  ``tie_word_embeddings`` is false), taken in chunks of tokens; the loss
  the mean next-token cross-entropy.
- *A layer*: ``a = x + m_r Mixer(RMSNorm(x; ln1_g))``; ``y = a + m_r
  SwiGLU(RMSNorm(a; ln2_g))``, ``SwiGLU(m) = (silu(m w1) * (m w3)) w2``, no
  bias (the family's fused ``input_linear`` is ``[w1 | w3]``).
- *An ``attention`` mixer*: ``q, k, v = u wq, u wk, u wv`` as ``heads`` /
  ``kv_heads`` / ``kv_heads`` heads, no bias, NO positional encoding, causal
  ``softmax(q k^T m_a) v`` (a block of queries at a time), ``wo``.
- *A ``mamba`` mixer* (``H`` heads of ``P``, state ``N``, one group):
  ``[z | xBC | dt] = u ssm_in`` (``H P``, ``H P + 2 N``, ``H`` columns);
  ``xBC = silu(conv(xBC) + ssm_conv_b)``, depthwise and causal, ``c_t =
  sum_j ssm_conv_k[j] xBC_{t - taps + 1 + j}``, zeros before the sequence;
  ``[x | B | C] = xBC``; ``dt = softplus(dt + ssm_dt_b)``; ``A =
  -exp(ssm_a_log)``; then for ``t = 0, 1, ...`` with ``h = 0`` before the
  sequence, a head at a time:

      h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T         (P, N)
      y_t = h_t C_t + ssm_d x_t

  ``out = RMSNorm(y * silu(z); ssm_g) ssm_out`` over all ``H P`` entries,
  the gate first.  ``mamba_chunk_size`` is read by nothing here.

Departures from the published description, all under ``assumed`` in the
configuration file: how the weights are seeded (Mamba-2's own start for
``A``, ``dt`` and ``D``), no clamp on ``dt``, plain SGD for the model's own
optimizer.

The guide's share test does not apply: no layer is divided (the chip holds
one pipeline stage's ten whole layers).

It also owns the seeded weights and token rows.  One jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, group by group, and runs a row and a layer at a time, keeping
every layer's input for the backward walk.

``first_steps``: the first three steps' loss, each leaf's first gradient as
plain SGD applied it (``(w0 - w1) / lr``), the small leaves' first
gradients themselves (``grad_first``), each leaf's change after three
steps, and a step's state-space readings (``ssm``: the mean over positions,
heads and layers of ``exp(dt A)``, and the RMS of the state behind the last
position, mean over the layers).
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own
#: size (benchmark/limits.py and the cell's first run; my chip runs, PR 41;
#: PERF.md section 2 has the table): the bfloat16 program over 10 seeds
#: (18 by the PR's end, inside the same ranges but for ``grad_diff_gap``
#: up to 0.0478 and ``delta_norm_gap`` up to 0.0029) against the fp8
#: control over 3; every limit lies between its two
#: readings, near their geometric mean.  ``grad_diff_gap`` (the worst small
#: leaf's first gradient, norm of the difference; the last state-space
#: layer's convolution taps on every seed: no discrete choice here, the
#: worst leaf is the smallest one in front of the widest chain)
#: 0.0449-0.0474 against 0.132-0.139: 1.69 times over the sound runs'
#: largest, 1.65 under the control's least.  The norms separate too, twice:
#: ``grad_norm_gap`` 0.0020-0.0027 against 0.0054-0.0061 and
#: ``delta_norm_gap`` 0.0023-0.0028 against 0.0054-0.0057 (the worst leaf
#: the attention layer's ``wv`` or ``wo``), each limit 1.4 times over the
#: one and 1.4 under the other.  ``loss_gap`` reads 4.2e-4-5.7e-4 on EVERY
#: seed, ten times the accepted cells' and of one sign (the program's loss
#: is the higher): this seeded model's loss is first order in the input
#: token's own logit (about 17 through the tied head; the loss is 16.8
#: where ln 100,352 is 11.5), and bfloat16 ACTIVATIONS move that logit
#: (with activations float32 and only the weights rounded the CPU reads
#: -0.6e-4 at 256 positions where bfloat16 activations read +2.8e-4); the
#: control reads 9.7e-4-1.0e-3, so the limit stands 1.31 times over the
#: one and 1.29 under the other, and takes no other cell's number.  A step
#: that returns its state unchanged reads a change of 1.
LIMITS = {
    "loss_gap": 7.5e-4,
    "grad_norm_gap": 0.0038,
    "delta_norm_gap": 0.0039,
    "grad_diff_gap": 0.08,
}

#: leaves small enough to keep whole for ``grad_diff_gap``: the gains, the
#: state-space layers' decay rates, step-size biases, skips, convolution taps
#: and biases, and the attention layer's four projections
KEEP = ("ln1_g", "ln2_g", "norm_g", "ssm_g", "ssm_a_log", "ssm_dt_b",
        "ssm_d", "ssm_conv_k", "ssm_conv_b", "wq", "wk", "wv", "wo")

#: queries a block of the reference's attention, tokens a chunk of its head,
#: positions a checkpointed block of its recurrence
_Q_BLOCK, _HEAD_CHUNK, _T_BLOCK = 512, 1024, 128


@functools.lru_cache(maxsize=None)
def _dims_of(key: str):
    import json
    return json.loads(key)


def dims(cfg: dict) -> dict:
    """The sizes the reference runs, from the configuration as run."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    types = list(cfg["layer_types"])
    if set(types) - {"mamba", "attention"} or \
            int(cfg["num_hidden_layers"]) != len(types):
        raise ValueError(f"layer_types {types} against num_hidden_layers "
                         f"{cfg['num_hidden_layers']}: mamba or attention")
    if int(cfg.get("num_local_experts") or 0) or \
            int(cfg.get("mamba_n_groups", 1)) != 1:
        raise ValueError("the reference has no routed experts and one group")
    return {
        "d": d, "heads": heads,
        "kv_heads": int(cfg.get("num_key_value_heads", heads)),
        "hd": int(cfg.get("head_dim") or d // heads),
        "ff": int(cfg["shared_intermediate_size"]),
        "vocab": int(cfg["vocab_size"]), "types": types,
        "eps": float(cfg["rms_norm_eps"]),
        "H": int(cfg["mamba_n_heads"]), "P": int(cfg["mamba_d_head"]),
        "N": int(cfg["mamba_d_state"]), "taps": int(cfg["mamba_d_conv"]),
        "m_e": float(cfg.get("embedding_multiplier", 1.0)),
        "m_a": float(cfg["attention_multiplier"]),
        "m_r": float(cfg.get("residual_multiplier", 1.0)),
        "m_l": float(cfg.get("logits_scaling", 1.0)),
        "tied": bool(cfg.get("tie_word_embeddings", True)),
    }


def leaf_groups(cfg: dict) -> dict:
    """``{group: path in the step's parameter pytree}``, in the order the
    readings walk them; a group is one array or a dict of them."""
    dm = dims(cfg)
    out = {"emb": ("emb",), "norm_g": ("norm_g",)}
    if not dm["tied"]:
        out["head"] = ("head",)
    out.update({f"B{li}": ("blocks", li) for li in range(len(dm["types"]))})
    return out


def scan_flops_per_token(dm: dict, chunk: int) -> float:
    """Matrix-unit operations a token of ONE state-space layer's scan needs
    in one pass, as the chunked form's least (two operations a
    multiply-accumulate): inside a chunk of ``chunk`` positions the causal
    half of ``C B^T`` (once for all heads) and of the masked scores times
    ``dt x`` (a head), the chunk's closing state and the carried state's
    contribution (``N P`` multiply-accumulates a head each)."""
    half = (chunk + 1) / 2.0
    return 2.0 * (half * dm["N"] + dm["H"] * dm["P"] * (half + 2 * dm["N"]))


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens needs,
    forward and backward (three passes, two operations a
    multiply-accumulate): every layer's SwiGLU (three products); a
    state-space layer's two projections and its scan as the chunked form's
    least at ``mamba_chunk_size`` (:func:`scan_flops_per_token`: 2.1 % of
    the layer at the published sizes; the literal recurrence would need the
    same ``2 N P`` a head for the update and the read, and nothing for the
    quadratic form); an attention layer's four projections and causal
    attention at the half it needs; the head pass.  The embedding lookup,
    the convolution, the gates and the norms are no products, and nothing
    that is recomputed counts."""
    dm = dims(cfg)
    d, hd, inner = dm["d"], dm["hd"], dm["H"] * dm["P"]
    chunk = min(int(cfg.get("mamba_chunk_size", 256)), seq_len)
    mamba = 2.0 * d * (2 * inner + 2 * dm["N"] + dm["H"]) + \
        2.0 * inner * d + scan_flops_per_token(dm, chunk)
    attn = 2.0 * d * hd * (2 * dm["heads"] + 2 * dm["kv_heads"])
    n_attn = dm["types"].count("attention")
    per_token = len(dm["types"]) * 6.0 * d * dm["ff"] + \
        dm["types"].count("mamba") * mamba + n_attn * attn + \
        2.0 * d * dm["vocab"]
    # QK^T and PV, each 2 * t * t * heads * head_dim operations, halved
    attention = seq_len * seq_len * dm["heads"] * 2.0 * hd
    return 3.0 * (seq_len * per_token + n_attn * attention)


# -- seeded weights and tokens ------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _layer_shapes(dm: dict, kind: str) -> dict:
    d, hd, inner, n = dm["d"], dm["hd"], dm["H"] * dm["P"], dm["N"]
    out = {"ln1_g": (d,), "ln2_g": (d,)}
    if kind == "attention":
        out.update({"wq": (d, dm["heads"] * hd),
                    "wk": (d, dm["kv_heads"] * hd),
                    "wv": (d, dm["kv_heads"] * hd),
                    "wo": (dm["heads"] * hd, d)})
    else:
        out.update({"ssm_in": (d, 2 * inner + 2 * n + dm["H"]),
                    "ssm_conv_k": (dm["taps"], inner + 2 * n),
                    "ssm_conv_b": (inner + 2 * n,), "ssm_dt_b": (dm["H"],),
                    "ssm_a_log": (dm["H"],), "ssm_d": (dm["H"],),
                    "ssm_g": (inner,), "ssm_out": (inner, d)})
    out.update({"w1": (d, dm["ff"]), "w3": (d, dm["ff"]),
                "w2": (dm["ff"], d)})
    return out


def _make_leaf(key, name: str, shape):
    """Projections normal ``1/sqrt(fan_in)``; gains ``1 + normal 0.05`` (so
    that no gain's gradient hides behind another's); the convolution's taps
    normal ``1/sqrt(taps)`` and its bias normal 0.1 (so that it bites); and
    as Mamba-2 starts them: the decay rates ``A`` uniform 1 .. 16
    (``ssm_a_log`` their log), the step sizes log-uniform 0.001 .. 0.1
    (``ssm_dt_b`` their inverse softplus), the skip ``ssm_d`` 1."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, sum(ord(c) * (i + 1)
                                    for i, c in enumerate(name)))
    if name == "ssm_d":
        return jnp.ones(shape, jnp.float32)
    if name == "ssm_a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if name == "ssm_dt_b":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
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
    # the embedding's deviation: the stream entering layer 0, ``m_e emb``,
    # has deviation 1, as the other references' embeddings give it
    emb_std = np.float32(1.0 / dm["m_e"])

    def layer(key, li):
        k = jax.random.fold_in(key, li + 1)
        return {name: _make_leaf(k, name, shape) for name, shape
                in _layer_shapes(dm, dm["types"][li]).items()}

    def emb(key):
        return jax.random.normal(jax.random.fold_in(key, 0x0E),
                                 (dm["vocab"], d), jnp.float32) * emb_std

    def small(name, tag, shape):
        return lambda key: _make_leaf(jax.random.fold_in(key, tag), name,
                                      shape)

    mk = {"layer": layer, "emb": emb,
          "norm_g": small("norm_g", 0x4E, (d,))}
    if not dm["tied"]:
        mk["head"] = small("head", 0x4D, (d, dm["vocab"]))

    def whole(key):
        out = {g: fn(key) for g, fn in mk.items() if g != "layer"}
        out["blocks"] = [layer(key, li) for li in range(len(dm["types"]))]
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
    each, uniform over the whole vocabulary; every row has a generator of
    its own.  Inputs are ``row[:-1]``, labels ``row[1:]``."""
    vocab = int(cfg["vocab_size"])
    rows = [np.random.default_rng([int(seed), 0x1F2, r]).integers(
        0, vocab, seq_len + 1).astype(np.int32) for r in range(start, stop)]
    return np.stack(rows)


# -- the layer ------------------------------

def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _attention(p, u, dm, q, out):
    """Causal attention of one row ``u (t, d)`` with no positional
    encoding and the score scale ``m_a``, a block of queries at a time
    (``lax.map`` over blocks, each checkpointed)."""
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
        s = out(jnp.einsum("qhd,khd->hqk", q(qb), q(kh))) * \
            np.float32(dm["m_a"])
        a = jax.nn.softmax(jnp.where(keys[None, None, :] <= at[None, :, None],
                                     s, -jnp.inf), axis=-1)
        return out(jnp.einsum("hqk,khd->qhd", q(a), q(vh)))

    o = jax.lax.map(one_block, (qp, pos)).reshape(-1, heads * hd)[:t]
    return out(q(o) @ q(p["wo"]))


def recurrence(x, dt, a, bm, cm, skip):
    """The state-space recurrence of one row, literally: ``x (t, H, P)``,
    ``dt (t, H)`` (after the softplus), ``a (H,)``, ``bm``, ``cm`` ``(t,
    N)``, ``skip (H,)`` -> ``(y (t, H, P), the state behind the last
    position (H, P, N))``.  One position a step of a ``lax.scan``; blocks
    of ``_T_BLOCK`` positions are checkpointed."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    block = min(_T_BLOCK, t)
    fill = -t % block

    def one(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t * a)[:, None, None] * h + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return h, (h * c_t[None, None, :]).sum(-1) + skip[:, None] * x_t

    @jax.checkpoint
    def positions(h, inp):
        return jax.lax.scan(one, h, inp)

    # positions that fill the last block have dt = 0: the state passes them
    chunks = tuple(jnp.pad(v, ((0, fill),) + ((0, 0),) * (v.ndim - 1)
                           ).reshape(-1, block, *v.shape[1:])
                   for v in (x, dt, bm, cm))
    h0 = jnp.zeros((x.shape[1], x.shape[2], bm.shape[1]), jnp.float32)
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
    heads, pd, n, taps = dm["H"], dm["P"], dm["N"], dm["taps"]
    inner = heads * pd
    proj = out(q(u) @ q(p["ssm_in"]))
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * n], axis=-1)
    xp = jnp.pad(q(xbc), ((taps - 1, 0), (0, 0)))
    kq = q(p["ssm_conv_k"])
    xbc = jax.nn.silu(out(sum(kq[j] * xp[j:j + t] for j in range(taps)))
                      + p["ssm_conv_b"])
    x, bm, cm = jnp.split(q(xbc), [inner, inner + n], axis=-1)
    dt = jax.nn.softplus(dt + p["ssm_dt_b"])
    a = -jnp.exp(p["ssm_a_log"])
    y, last = recurrence(x.reshape(t, heads, pd), dt, a, bm, cm, p["ssm_d"])
    y = out(y).reshape(t, inner) * jax.nn.silu(z)
    stats = jax.lax.stop_gradient(jnp.stack(
        [jnp.exp(dt * a).mean(), jnp.sqrt((last * last).mean())]))
    return out(q(_rms(y, p["ssm_g"], dm["eps"])) @ q(p["ssm_out"])), stats


def _glu(v, w1, w3, w2, q, out):
    import jax

    return out(q(jax.nn.silu(out(q(v) @ q(w1))) * out(q(v) @ q(w3))) @ q(w2))


def _layer(p, x, dm, kind, q, out):
    """One layer on one row ``x (t, d)`` -> ``(y, the state-space readings
    (2,), zeros for an attention layer)``."""
    import jax.numpy as jnp

    eps, m_r = dm["eps"], np.float32(dm["m_r"])
    u = _rms(x, p["ln1_g"], eps)
    if kind == "attention":
        mixed, stats = _attention(p, u, dm, q, out), jnp.zeros(2, jnp.float32)
    else:
        mixed, stats = _mamba(p, u, dm, q, out)
    a = x + m_r * mixed
    m = _rms(a, p["ln2_g"], eps)
    return a + m_r * _glu(m, p["w1"], p["w3"], p["w2"], q, out), stats


def _close(tp, x, labels, dm, n_tokens, q, out):
    """The final norm and the head pass of one row -> the row's part of
    the loss; ``tp`` holds ``norm_g`` and the head's matrix ``(vocab, d)``
    (the embedding where tied)."""
    import jax
    import jax.numpy as jnp

    h = _rms(x, tp["norm_g"], dm["eps"])
    head = q(tp["emb"]).T if dm["tied"] else q(tp["head"])
    total = jnp.zeros((), jnp.float32)
    for lo in range(0, x.shape[0], _HEAD_CHUNK):
        hi = lo + _HEAD_CHUNK
        logits = out(q(h[lo:hi]) @ head) / np.float32(dm["m_l"])
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
    through the layers and back through them."""
    import jax
    import jax.numpy as jnp

    if chips != 1:
        raise ValueError("the reference follows a one-chip step")
    dm = dims(cfg)
    types = dm["types"]
    layers, n_ssm = len(types), max(types.count("mamba"), 1)
    lr = float(cfg["hyper"]["lr"])
    batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    n_tokens = batch * t
    prog = _programs(_key_of(cfg), precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    scaled = jax.jit(lambda e, i: np.float32(dm["m_e"]) * e[i])
    scatter = jax.jit(lambda d, i, ct: d.at[i].add(np.float32(dm["m_e"]) * ct))
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
                h, ins = scaled(params["emb"], ids), []
                for li in range(layers):
                    ins.append(h)
                    h, stats = prog["layer"](blocks[li], h, types[li])
                    readings += np.asarray(stats, np.float64)
                part, (g_tp, ct) = prog["close_grad"](
                    tp, h, jnp.asarray(labels[r]), n_tokens)
                loss += float(part)
                d_tp = accumulate(d_tp, g_tp)
                for li in reversed(range(layers)):
                    dp, ct = prog["layer_vjp"](blocks[li], ins[li], ct,
                                               types[li])
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
