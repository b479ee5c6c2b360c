"""Plain reference of the ``trinity_large_preview`` configuration, cut as its
file says: an ``afmoe`` decoder (Arcee's Trinity family) in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``:
loss, every gradient and plain SGD.  No kernel, no sort, no grouped product,
nothing of the program imported; the window is a MASK on blocked scores (a
block of queries against every key), so that an error in the program's visit
tables or in its two cuts cannot hide in it.

With ``d`` the hidden size and ``RMSNorm(a; g) = a / sqrt(mean(a^2) + eps) *
g``:

- *The model.*  ``x_0 = sqrt(d) emb[tokens]`` where ``mup_enabled``; the
  layers; ``h = RMSNorm(x; norm_g)``; ``logits = h head`` (untied; the
  embedding's transpose where ``tie_word_embeddings``), taken in chunks of
  tokens; the loss the mean next-token cross-entropy.
- *A layer* has four norms, each with a gain of its own: ``h = x +
  RMSNorm(Attn(RMSNorm(x; ln1_g)); ln1o_g)``, ``x' = h + RMSNorm(F(RMSNorm(h;
  ln2_g)); ln2o_g)``.
- *Attention* on the normed input ``u``: ``q = RMSNorm(u wq; q_g)`` as
  ``heads`` heads of ``head_dim``, ``k = RMSNorm(u wk; k_g)`` and ``v = u
  wv`` as ``kv_heads`` heads (the norm over a head's entries, one gain for
  all heads; query head ``j`` reads key/value head ``j // (heads /
  kv_heads)``); ``g = sigmoid(u wg)``, as wide as the heads' output.  On a
  ``sliding_attention`` layer q and k are rotated (rotate-half over the whole
  head, ``rope_theta``, positions from 0) and query ``i`` sees key ``j`` iff
  ``0 <= i - j < sliding_window``; on a ``full_attention`` layer they are NOT
  rotated and the mask is causal alone.  ``o = (g * softmax(q k^T /
  sqrt(head_dim)) v) wo``.  No bias.
- *F* in the first ``num_dense_layers`` layers: the SwiGLU ``w2 (silu(u w1) *
  (u w3))`` of ``intermediate_size``.  After them: ``s = sigmoid(u gate)``
  over ALL ``router`` experts; the selected set is the top k of ``s + ebias``
  (the selection bias: no gradient, no update; one group); ``w_e = scale *
  s_e / (sum over the selected of s + 1e-20)``; the sum over the selected
  experts *this chip holds* of ``w_e SwiGLU_e(u)``: a loop over the held
  experts with masks.  What the absent experts would add is left out, here as
  in the program, the weights still normalised over all k selected.  Beside
  it the shared SwiGLU ``sw2 (silu(u sw1) * (u sw3))``, once, for every
  token; the sum of the two passes the fourth norm.

Departures from the published code, the first the program's and the rest
under ``assumed`` in the configuration file: the program's router
(``moe.route_top_k``) adds 1e-6 to the selected scores' sum where the family
adds 1e-20 (this reference keeps 1e-20: four sigmoid scores sum to about 2, so
the program's weights stand 5e-7 under, far inside every tolerance); no
auxiliary loss (``load_balance_coeff`` is read by nothing: the family's
modelling code returns none); how the weights are seeded (a router balanced
over the 32 shares: :func:`_make_leaf`); plain SGD for the model's own
optimizer.

The guide's share test is ``tests/test_afmoe_arch.py``'s: the routed parts of
all the shares of a sparse layer and the shared expert counted once add up
to the uncut layer (:func:`_experts` with ``first`` 0 and every expert held).

It also owns the seeded weights and token rows.  One jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, group by group, and runs a row and a layer at a time, keeping
every layer's input for the backward walk, the rows' gradients summed: 6.4 GB
of float32 weights and one layer's gradients are all it holds.

``first_steps``: the first three steps' loss, each leaf's first gradient as
plain SGD applied it (``(w0 - w1) / lr``), the small leaves' first gradients
themselves (``grad_first``) and each leaf's change after three steps.
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own size
#: (``benchmark/limits.py`` and ``run.py``; my chip runs, PR 48; PERF.md
#: section 2 has the table): the bfloat16 program over 12 seeds against the
#: fp8 control over 3.  ``delta_norm_gap`` decides (the worst leaf's gap in
#: the norm of its change after three steps, most often a held expert's
#: weight): 0.00155-0.00247 against 0.00635-0.00785, the limit near their
#: geometric mean, 1.62 times over the sound runs' largest and 1.59 under the
#: control's least.  The other three stand where the precision hardly moves a
#: number against its own scatter, between the readings and 1 with the more
#: room above, and hold what they can (a row left out, a layer skipped, a
#: state returned unchanged, a wrong mask: tens of per cent and more).
#: ``grad_norm_gap`` reads 0.0017-0.0043 against 0.0076-0.0100: 1.8 times
#: apart where the seeds alone scatter it 2.5 times (the worst leaf a held
#: expert's weight that saw about 128 pairs), so a limit between them would
#: refuse one sound seed in thirty: 0.012, 2.8 times the largest reading.
#: ``grad_diff_gap`` (the worst small leaf's first gradient, norm of the
#: difference; a router's ``gate`` on every seed: of 8,192 x 4 selections a
#: few per cent differ between a bfloat16 stream and the float32 one) reads
#: 0.126-0.195 against 0.218-0.246: the flips cost the sound runs nearly
#: what they cost the control, so it cannot decide: 0.4, twice the largest
#: reading, where another router or another mask reads 1 and more.
#: ``loss_gap`` reads 3.8e-6-2.6e-5 against 7.4e-5-9.8e-5, apart by 2.8
#: times where the seeds scatter it 7 times: the accepted cells' 0.0015.  So
#: the control is refused by ONE limit, ``delta_norm_gap``, in every run.
LIMITS = {
    "loss_gap": 0.0015,
    "grad_norm_gap": 0.012,
    "delta_norm_gap": 0.0040,
    "grad_diff_gap": 0.4,
}

#: leaves small enough to keep whole for ``grad_diff_gap``: the gains (four
#: a layer, the QK-norms', the final one), the routers, and the key and value
#: projections
KEEP = ("ln1_g", "ln1o_g", "ln2_g", "ln2o_g", "q_g", "k_g", "norm_g", "gate",
        "wk", "wv")

#: queries a block of the reference's attention, tokens a chunk of its head
_Q_BLOCK, _HEAD_CHUNK = 512, 1024

#: a ``layer_types`` entry -> whether the layer's scores have a window (and
#: its q and k are rotated)
WINDOWED = {"sliding_attention": True, "full_attention": False}


@functools.lru_cache(maxsize=None)
def _dims_of(key: str):
    import json
    return json.loads(key)


def dims(cfg: dict) -> dict:
    """The sizes the reference runs, from the configuration as run."""
    types = list(cfg["layer_types"])
    if set(types) - set(WINDOWED) or \
            int(cfg["num_hidden_layers"]) != len(types):
        raise ValueError(f"layer_types {types} against num_hidden_layers "
                         f"{cfg['num_hidden_layers']}: sliding_attention or "
                         f"full_attention a layer")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    router = int(cfg.get("router_width", cfg.get("num_experts", 0)))
    held = cfg.get("experts_held") or {"first": 0, "count": router}
    moe_ff = int(cfg.get("moe_intermediate_size", 0))
    n_dense = int(cfg.get("num_dense_layers", 0)) if router else len(types)
    return {
        "d": d, "heads": heads,
        "kv_heads": int(cfg.get("num_key_value_heads", heads)),
        "hd": int(cfg.get("head_dim") or d // heads),
        "vocab": int(cfg["vocab_size"]),
        "windowed": [WINDOWED[t] for t in types],
        "window": int(cfg.get("sliding_window") or 0),
        "theta": float(cfg.get("rope_theta", 1e4)),
        "eps": float(cfg.get("rms_norm_eps", 1e-5)),
        "n_dense": n_dense, "ff": int(cfg["intermediate_size"]),
        "router": router, "first": int(held["first"]),
        "held": int(held["count"]),
        "top_k": int(cfg.get("num_experts_per_tok", 1)),
        "moe_ff": moe_ff,
        "shared_ff": int(cfg.get("num_shared_experts", 0)) * moe_ff,
        "norm_topk": bool(cfg.get("route_norm", True)),
        "scale": float(cfg.get("route_scale", 1.0)),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
        "emb_mult": float(np.sqrt(d)) if cfg.get("mup_enabled", False)
        else 1.0,
        # what the second and fourth norms' gains start at: depth-scaled, by
        # the PUBLISHED depth where the file states one (:func:`_make_leaf`)
        "out_gain": float(1.0 / np.sqrt(2.0 * int(
            (cfg.get("published") or {}).get("num_hidden_layers",
                                             cfg["num_hidden_layers"])))),
    }


def leaf_groups(cfg: dict) -> dict:
    """``{group: path in the step's parameter pytree}``, in the order the
    readings walk them; a group is one array or a dict of them."""
    dm = dims(cfg)
    out = {"emb": ("emb",), "norm_g": ("norm_g",)}
    if not dm["tied"]:
        out["head"] = ("head",)
    out.update({f"B{li}": ("blocks", li)
                for li in range(len(dm["windowed"]))})
    return out


def attended_pairs(t: int, window: int | None) -> int:
    """(query, key) pairs a head's scores hold over ``t`` positions: the
    causal triangle's, or under a ``window`` the band's (a query sees at
    most ``window`` keys, itself among them)."""
    w = t if window is None else min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Matrix-unit operations a token needs in ONE forward pass, by part (two
    operations a multiply-accumulate), each of ONE layer: an attention
    sub-layer's five projections (q, k, v, the gate, the output) and ``QK^T``
    and ``PV`` over the pairs it needs (:func:`attended_pairs`: the band's on
    a window layer, not the triangle's), the dense SwiGLU's three products, a
    sparse layer's router, shared expert (three products) and the routed
    experts' three products over the ``top_k x held / router`` pairs a token
    sends to the held experts on average; the head pass."""
    dm = dims(cfg)
    d, hd, heads = dm["d"], dm["hd"], dm["heads"]
    proj = 2.0 * d * hd * (3 * heads + 2 * dm["kv_heads"])

    def scores(window):
        return 4.0 * heads * hd * attended_pairs(seq_len, window) / seq_len

    return {
        "attn_window": proj + scores(dm["window"] or None),
        "attn_full": proj + scores(None),
        "dense": 6.0 * d * dm["ff"],
        "router": 2.0 * d * dm["router"],
        "shared": 6.0 * d * dm["shared_ff"],
        "routed": dm["top_k"] * dm["held"] / max(dm["router"], 1) * 6.0 * d *
        dm["moe_ff"],
        "head": 2.0 * d * dm["vocab"],
    }


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens needs,
    forward and backward (three passes): :func:`forward_flops_per_token` by
    the layers' kinds.  The embedding lookup, the gates' sigmoids, the rotary
    embedding and the norms are no products, and nothing that is recomputed
    counts."""
    parts = forward_flops_per_token(cfg, seq_len)
    dm = dims(cfg)
    per_token = parts["head"]
    for li, windowed in enumerate(dm["windowed"]):
        per_token += parts["attn_window" if windowed else "attn_full"]
        per_token += parts["dense"] if li < dm["n_dense"] else \
            parts["router"] + parts["shared"] + parts["routed"]
    return 3.0 * seq_len * per_token


# -- seeded weights and tokens ------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _layer_shapes(dm: dict, li: int) -> dict:
    d, hd, heads, kv = dm["d"], dm["hd"], dm["heads"], dm["kv_heads"]
    out = {"ln1_g": (d,), "ln1o_g": (d,), "ln2_g": (d,), "ln2o_g": (d,),
           "wq": (d, heads * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
           "wo": (heads * hd, d), "wg": (d, heads * hd),
           "q_g": (hd,), "k_g": (hd,)}
    if li < dm["n_dense"]:
        out.update({"w1": (d, dm["ff"]), "w3": (d, dm["ff"]),
                    "w2": (dm["ff"], d)})
        return out
    f, e = dm["moe_ff"], dm["held"]
    out.update({"gate": (d, dm["router"]), "ebias": (dm["router"],),
                "ew1": (e, d, f), "ew3": (e, d, f), "ew2": (e, f, d)})
    if dm["shared_ff"]:
        out.update({"sw1": (d, dm["shared_ff"]), "sw3": (d, dm["shared_ff"]),
                    "sw2": (dm["shared_ff"], d)})
    return out


def _make_leaf(key, name: str, shape, share: int = 0, out_gain: float = 1.0):
    """Projections normal ``1/sqrt(fan_in)``; gains ``1 + normal 0.05`` (so
    that no gain's gradient hides behind another's), those of the second and
    fourth norm (``ln1o_g``, ``ln2o_g``: a sub-layer's OUTPUT on its way into
    the residual stream) times ``out_gain``, ``1 / sqrt(2 x layers)`` of the
    published depth: the family's sandwich norm is depth-scaled, and with
    gains of one a norm lifts every sub-layer's output to the stream's own
    size whatever it holds.  Random attention's output is nearly the same for
    all tokens (a mean over hundreds of values), so three layers in the
    stream's larger part was common to all tokens, every token preferred the
    same few experts, a share's pairs a layer ranged 0.4-2.7 times their mean
    with the seed and the layer, a fifth of the layer-steps overflowed the
    compact pairs buffer (``moe_compact_share`` 0.8125) and the rate spread
    1.4 % over six seeds (my chip runs, PR 48: PERF.md section 6).
    Depth-scaled, a token's own vector leads the stream as it does in a
    trained model and a share's pairs stay within 0.85-1.15 of their mean.

    The router is seeded balanced over the shares, as a trained one is, the
    way ``reference/nemotron_h.py`` balances its eight.  The selection bias
    is at the scale of the scores' spread (0.1 against a deviation of 0.2),
    so that the selection differs from the plain top k of the scores: every
    share of ``share`` experts carries the same values, 0.1 x the normal
    quantiles, in an order of its own from the seed.  And a share's ``gate``
    columns are ``share / 2`` random directions and their NEGATIVES, the two
    experts of such a pair carrying one bias value: a component of the normed
    stream that all tokens share favours one expert of a pair as it
    disfavours the other, so the pairs a share receives depend on the seed in
    second order only (drawn independently they swing by a quarter with the
    seed and the rate follows them: PERF.md section 6, PR 45)."""
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
    n = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("_g"):
        gain = 1.0 + np.float32(0.05) * n
        return gain * np.float32(out_gain) if name.endswith("o_g") else gain
    return n / np.float32(np.sqrt(shape[-2] if len(shape) > 1 else shape[0]))


@functools.lru_cache(maxsize=None)
def _makers(dims_key: str):
    import jax
    import jax.numpy as jnp

    dm = _dims_of(dims_key)
    d = dm["d"]

    def layer(key, li):
        k = jax.random.fold_in(key, li + 1)
        return {name: _make_leaf(k, name, shape, dm["held"], dm["out_gain"])
                for name, shape in _layer_shapes(dm, li).items()}

    def emb(key):
        # the stream entering layer 0 has deviation 1 behind the multiplier:
        # a token's own vector leads it, so the routers' inputs differ token
        # by token
        return jax.random.normal(jax.random.fold_in(key, 0x0E),
                                 (dm["vocab"], d), jnp.float32) / \
            np.float32(dm["emb_mult"])

    def small(name, tag, shape):
        return lambda key: _make_leaf(jax.random.fold_in(key, tag), name,
                                      shape)

    mk = {"layer": layer, "emb": emb,
          "norm_g": small("norm_g", 0x4E, (d,))}
    if not dm["tied"]:
        mk["head"] = small("head", 0x4D, (d, dm["vocab"]))

    def whole(key):
        out = {g: fn(key) for g, fn in mk.items() if g != "layer"}
        out["blocks"] = [layer(key, li) for li in range(len(dm["windowed"]))]
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


def _rotated(x, theta: float):
    """Rotary embedding of ``x (t, heads, hd)``, rotate-half over the whole
    head, positions from 0."""
    import jax.numpy as jnp

    t, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos + half * sin


def _attention(p, u, dm, windowed: bool, q, out):
    """The attention sub-layer of one row ``u (t, d)``, a block of queries at
    a time against every key (``lax.map`` over blocks, each checkpointed),
    the causal cut and, on a window layer, the band as masks."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads, kv, hd = dm["heads"], dm["kv_heads"], dm["hd"]
    qh = _rms(out(q(u) @ q(p["wq"])).reshape(t, heads, hd), p["q_g"],
              dm["eps"])
    kh = _rms(out(q(u) @ q(p["wk"])).reshape(t, kv, hd), p["k_g"], dm["eps"])
    vh = out(q(u) @ q(p["wv"])).reshape(t, kv, hd)
    if windowed:
        qh, kh = _rotated(qh, dm["theta"]), _rotated(kh, dm["theta"])
    if kv != heads:
        kh, vh = (jnp.repeat(a, heads // kv, axis=1) for a in (kh, vh))
    block = min(_Q_BLOCK, t)
    fill = -t % block
    qp = jnp.pad(qh, ((0, fill), (0, 0), (0, 0))).reshape(-1, block, heads, hd)
    pos = jnp.arange(t + fill).reshape(-1, block)
    keys = jnp.arange(t)
    reach = dm["window"] if windowed else t

    @jax.checkpoint
    def one_block(args):
        qb, at = args
        s = out(jnp.einsum("qhd,khd->hqk", q(qb), q(kh))) / \
            np.float32(np.sqrt(hd))
        apart = at[None, :, None] - keys[None, None, :]
        a = jax.nn.softmax(jnp.where((apart >= 0) & (apart < reach), s,
                                     -jnp.inf), axis=-1)
        return out(jnp.einsum("hqk,khd->qhd", q(a), q(vh)))

    o = jax.lax.map(one_block, (qp, pos)).reshape(-1, heads * hd)[:t]
    o = o * jax.nn.sigmoid(out(q(u) @ q(p["wg"])))
    return out(q(o) @ q(p["wo"]))


def _swiglu(v, w1, w3, w2, q, out):
    """The family's feed-forward unit, dense, routed or shared."""
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


def _layer(p, x, dm, windowed: bool, q, out):
    """One layer on one row ``x (t, d)``: both sub-layers, four norms."""
    eps = dm["eps"]
    h = x + _rms(_attention(p, _rms(x, p["ln1_g"], eps), dm, windowed, q,
                            out), p["ln1o_g"], eps)
    m = _rms(h, p["ln2_g"], eps)
    f = _experts(p, m, dm, q, out) if "gate" in p else \
        _swiglu(m, p["w1"], p["w3"], p["w2"], q, out)
    return h + _rms(f, p["ln2o_g"], eps)


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

    def layer(p, x, windowed):
        return _layer(p, x, dm, windowed, q, out)

    def layer_vjp(p, x, ct, windowed):
        _, vjp = jax.vjp(lambda p_, x_: layer(p_, x_, windowed), p, x)
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
    windowed = dm["windowed"]
    layers = len(windowed)
    lr = float(cfg["hyper"]["lr"])
    batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    n_tokens = batch * t
    prog = _programs(_key_of(cfg), precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    mult = np.float32(dm["emb_mult"])
    take = jax.jit(lambda e, i: e[i] * mult)
    scatter = jax.jit(lambda d, i, ct: d.at[i].add(ct * mult))
    result = {"loss": [], "grad_norm": {}, "delta_norm": {},
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
            for r in range(batch):
                h, kept = take(params["emb"], jnp.asarray(tokens[r])), []
                for li in range(layers):
                    kept.append(h)
                    h = prog["layer"](blocks[li], h, windowed[li])
                part, (g_tp, ct) = prog["close_grad"](
                    tp, h, jnp.asarray(labels[r]), n_tokens)
                loss += float(part)
                d_tp = accumulate(d_tp, g_tp)
                ins.append(kept)
                cts.append(ct)
                del g_tp, h, ct
            result["loss"].append(loss)
            # backward, a layer at a time over the rows, and the layer's
            # update as soon as its gradient is whole: no other layer reads
            # its weights any more in this step, so one layer's gradients
            # are all that is held beside the weights
            for li in reversed(range(layers)):
                d_layer = None
                for r in range(batch):
                    dp, cts[r] = prog["layer_vjp"](
                        blocks[li], ins[r].pop(), cts[r], windowed[li])
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
