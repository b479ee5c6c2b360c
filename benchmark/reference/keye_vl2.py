"""Plain reference of the ``keye_vl_2_0_30b_a3b`` configuration, cut as its
file says: the language model of Keye-VL-2.0-30B-A3B (``model_type``
``KeyeVL2``: a Qwen3-MoE-shaped decoder whose every attention layer carries
a DeepSeek-Sparse-Attention indexer) in straightforward ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``: the loss ``CE +
L_I`` over the vocabulary slice, every gradient and plain SGD.  No kernels,
no threshold by counting, no sort of pairs, no grouped products, nothing of
the program imported.

With ``d`` the hidden size, ``x`` a layer's input, ``RMSNorm(a; g) = a /
sqrt(mean(a^2) + eps) * g`` and ``LayerNorm(a; g, b) = (a - mean(a)) /
sqrt(var(a) + eps) * g + b``:

    h = RMSNorm(x; ln1_g);  a = x + attention(h);  m = RMSNorm(a; ln2_g)
    y = a + routed(m)

1. Attention operands: ``q = h wq`` (32 heads of 128), ``k = h wk``, ``v =
   h wv`` (4 heads of 128), no bias; RMSNorm with its own gain on each head
   of q (``q_g``) and of k (``k_g``); M-RoPE (:func:`mrope`): the 64 rotary
   frequencies are split by ``rope_scaling.mrope_section`` [16, 24, 24] over
   three position streams and the head is turned rotate-half (pairs ``(i, i
   + 64)``, angle ``pos_stream(i) * theta^(-2i / 128)``).  The step takes
   text tokens, whose three streams are all ``0 .. t-1``: exactly plain
   rotate-half RoPE (a test says so).
2. Indexer, on ``hd = stop_gradient(h)``: ``qI = hd wiq`` (16 heads of 64),
   ``kI = LayerNorm(hd wik; ik_g, ik_b)`` (one head of 64), both turned
   rotate-half over the whole index head with the layer's theta; ``w = hd
   wiw * 16^-0.5 * 64^-0.5``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
   kI[s])`` for ``s <= t``.
3. Selection: ``S_t`` = the ``topk`` largest ``I[t, s]`` over ``s <= t``
   (``lax.top_k`` of the row; every ``s <= t`` while ``t < topk``), one set
   a token for all heads, no gradient through it.
4. Attention: a head's ``softmax over s in S_t of (q_t . k_s / sqrt(128))``
   times ``v`` (query head ``j`` reads key/value head ``j // 8``); ``wo``.
5. Alignment term: ``p[t, s]`` = the mean over the 32 heads of step 4's
   probabilities, detached; ``L_I = mean over tokens of KL(p[t, .] ||
   softmax over S_t of I[t, .])``, summed over the layers, weight 1.  It is
   the only path to ``wiq, wik, wiw, ik_g, ik_b`` and reaches nothing else.
6. Routed layer: ``s = softmax(m gate)`` over ALL 128 experts; the top 8;
   ``w_e = s_e / (sum over the selected of s + 1e-6)`` (``norm_topk_prob``);
   the sum over the selected experts *this chip holds* of ``w_e E_e(m)``,
   ``E_e(m) = ew2 (silu(m ew1) * (m ew3))``: a loop over the held experts
   with masks.  No bias, no scaling, no shared expert.  What the absent
   experts would add is left out, here as in the program.
7. ``z = RMSNorm(x_last; norm_g)``; logits ``z head`` (untied) over the
   slice; ``CE`` the mean next-token cross-entropy.  Loss ``= CE + sum over
   layers of L_I``.

Steps 2-5 run a block of queries at a time (``lax.map`` over blocks, each
checkpointed), so that 16,384 positions fit: a block's scores are ``(32,
block, t)``.

Departures from the published description, all under ``assumed`` in the
configuration file: the QK-norm (Qwen3-MoE's), the indexer's form where the
config gives only its sizes (RoPE over the whole index head, the LayerNorm
on ``kI``, the weights' scale, no Hadamard rotation), the chunk sizes read
as tiles, the alignment objective and its weight, no balance term, plain
SGD.  Not run: the vision tower, multi-axis positions.

It also owns the seeded weights and token rows (one jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, group by group) and ``first_steps``: the first three steps'
loss, each leaf's first gradient as plain SGD applied it (``(w0 - w1) /
lr``), the small leaves' first gradients themselves (``grad_first``) and
each leaf's change after three steps; a row and a layer at a time.
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own
#: size (benchmark/limits.py and the cell's runs; my chip runs, PR 39;
#: PERF.md section 2 has the table): the bfloat16 program over 15 seeds
#: against the fp8 control over 2; every limit lies between its two
#: readings.  ``grad_diff_gap`` (the worst small leaf's first gradient, norm
#: of the difference; the last layer's router ``gate`` on every seed, as in
#: the other routed cells: a few per cent of the (token, expert) choices
#: differ between a bfloat16 stream and the float32 one, and here the
#: (query, key) choices of four indexers before it) 0.106-0.116 against
#: 0.256-0.257: 1.47 times over the sound runs' largest, 1.5 under the
#: control's least, the narrowest room of the four because the selections
#: already cost the sound runs a tenth.  ``grad_norm_gap`` up to 0.0029
#: against 0.0100-0.0187 and ``delta_norm_gap`` up to 0.0022 against
#: 0.0085-0.0137 (the worst leaf a router's ``gate`` or an indexer's
#: ``wik`` / ``wiw``), each limit about twice the sound runs' largest and
#: half the control's least.  ``loss_gap`` separates (up to 4.0e-5 against
#: 2.1e-4-2.5e-4, five times apart), so it takes no other cell's number:
#: 2.3 times over the one, 2.3 under the other.  A step that returns its
#: state unchanged reads a change of 1.
LIMITS = {
    "loss_gap": 9e-5,
    "grad_norm_gap": 0.0055,
    "delta_norm_gap": 0.0043,
    "grad_diff_gap": 0.17,
}

#: leaves small enough to keep whole for ``grad_diff_gap``: the gains, the
#: router, the indexer's three matrices and its key norm
KEEP = ("ln1_g", "ln2_g", "q_g", "k_g", "norm_g", "gate", "wiq", "wik",
        "wiw", "ik_g", "ik_b")

#: query rows a block of steps 2-5 holds
Q_BLOCK = 256


@functools.lru_cache(maxsize=None)
def _dims_of(key: str):
    import json
    return json.loads(key)


def dims(cfg: dict) -> dict:
    """The sizes the reference runs, from the configuration as run."""
    held, sa = cfg["experts_held"], cfg["sa_config"]
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "kv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "hi": int(sa["indexer_num_heads"]), "di": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"]), "moe_ff": int(cfg["moe_intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "router": int(cfg["router_width"]), "first": int(held["first"]),
        "held": int(held["count"]), "top_k": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "sections": [int(n) for n in
                     cfg["rope_scaling"]["mrope_section"]],
    }


def leaf_groups(cfg: dict) -> dict:
    """``{group: path in the step's parameter pytree}``, in the order the
    readings walk them; a group is one array or a dict of them."""
    out = {"emb": ("emb",), "head": ("head",), "norm_g": ("norm_g",)}
    out.update({f"B{li}": ("blocks", li)
                for li in range(dims(cfg)["layers"])})
    return out


def selected_pairs(seq_len: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)``: the (query, key) pairs a row selects."""
    full = max(seq_len - topk, 0)
    head = min(seq_len, topk)
    return head * (head + 1) // 2 + full * topk


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens NEEDS on
    this chip, forward and backward, two operations a multiply-accumulate:

    - three passes of the four attention projections, the router, the
      routed experts' passes this chip does (``top_k * held / router`` a
      token a layer in expectation; the counter reports a step's) and the
      head against the vocabulary slice;
    - two passes of the indexer's three projections (their input is
      detached: no gradient to it);
    - attention over the SELECTED pairs only (``sum_t min(t + 1, topk)`` a
      row): QK^T and PV forward and their four backward products;
    - the index scores over the causal pairs forward, and their two
      backward products (to ``qI`` and ``kI``) over the selected pairs, the
      only ones the alignment term's gradient reaches.

    So ``train_mfu`` reads low for as long as the kernels compute the
    masked part of every causal tile and the alignment pass makes the
    heads' scores a second time: that work is the implementation's, not
    the model's.  The embedding lookup is no product."""
    dm = dims(cfg)
    d, heads, kv, hd = dm["d"], dm["heads"], dm["kv"], dm["hd"]
    hi, di = dm["hi"], dm["di"]
    proj = d * heads * hd * 2 + d * kv * hd * 2
    experts = 3 * d * dm["moe_ff"] * dm["top_k"] * dm["held"] / dm["router"]
    per_token = dm["layers"] * (proj + d * dm["router"] + experts) + \
        d * dm["vocab"]
    index_proj = dm["layers"] * d * (hi * di + di + hi)
    sel = selected_pairs(seq_len, dm["topk"])
    causal = seq_len * (seq_len + 1) // 2
    attention = dm["layers"] * 3.0 * (2.0 * sel * heads * 2 * hd)
    index = dm["layers"] * (2.0 * causal * hi * di + 2 * 2.0 * sel * hi * di)
    return seq_len * 2.0 * (3.0 * per_token + 2.0 * index_proj) + \
        attention + index


# -- seeded weights and tokens ------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _layer_shapes(dm: dict) -> dict:
    d, hd, e, f = dm["d"], dm["hd"], dm["held"], dm["moe_ff"]
    return {"ln1_g": (d,), "ln2_g": (d,),
            "wq": (d, dm["heads"] * hd), "wk": (d, dm["kv"] * hd),
            "wv": (d, dm["kv"] * hd), "wo": (dm["heads"] * hd, d),
            "q_g": (hd,), "k_g": (hd,),
            "wiq": (d, dm["hi"] * dm["di"]), "wik": (d, dm["di"]),
            "wiw": (d, dm["hi"]), "ik_g": (dm["di"],), "ik_b": (dm["di"],),
            "gate": (d, dm["router"]),
            "ew1": (e, d, f), "ew3": (e, d, f), "ew2": (e, f, d)}


def _make_leaf(key, name: str, shape):
    """Projections normal ``1/sqrt(fan_in)``, gains near one (so that no
    gain's gradient hides behind another's), the index key norm's bias
    normal 0.05."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, sum(ord(c) * (i + 1)
                                    for i, c in enumerate(name)))
    n = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + np.float32(0.05) * n
    if name.endswith("_b"):
        return np.float32(0.05) * n
    return n / np.float32(np.sqrt(shape[-2] if len(shape) > 1 else shape[0]))


#: deviation of the embedding's entries: a token's own vector leads the
#: residual stream (``glm4_moe_lite.py`` has why: at 0.02 the routers'
#: inputs share a component and the shares' loads swing with the seed)
_EMB_STD = 1.0


@functools.lru_cache(maxsize=None)
def _makers(dims_key: str):
    import jax
    import jax.numpy as jnp

    dm = _dims_of(dims_key)
    d = dm["d"]

    def layer(key, li):
        k = jax.random.fold_in(key, li + 1)
        return {name: _make_leaf(k, name, shape)
                for name, shape in _layer_shapes(dm).items()}

    def emb(key):
        return jax.random.normal(jax.random.fold_in(key, 0x0E),
                                 (dm["vocab"], d),
                                 jnp.float32) * np.float32(_EMB_STD)

    def head(key):
        return _make_leaf(jax.random.fold_in(key, 0x4D), "head",
                          (d, dm["vocab"]))

    def norm_g(key):
        return _make_leaf(jax.random.fold_in(key, 0x4E), "norm_g", (d,))

    def whole(key):
        return {"emb": emb(key), "head": head(key), "norm_g": norm_g(key),
                "blocks": [layer(key, li) for li in range(dm["layers"])]}

    return {"layer": jax.jit(layer, static_argnums=1), "emb": jax.jit(emb),
            "head": jax.jit(head), "norm_g": jax.jit(norm_g),
            "whole": jax.jit(whole)}


def _key_of(cfg: dict) -> str:
    import json
    return json.dumps(dims(cfg), sort_keys=True)


def init_params(seed: int, cfg: dict):
    """The whole float32 pytree (``emb``, ``head``, ``norm_g``, ``blocks``)
    on the default device, in one jitted call."""
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
    each, uniform over the vocabulary slice this chip holds; every row has
    a generator of its own.  Inputs are ``row[:-1]``, labels ``row[1:]``."""
    vocab = int(cfg["vocab_size"])
    rows = [np.random.default_rng([int(seed), 0x1F2, r]).integers(
        0, vocab, seq_len + 1).astype(np.int32) for r in range(start, stop)]
    return np.stack(rows)


# -- the layer ------------------------------

def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def mrope(x, positions, theta: float, sections=None):
    """Multi-axis rotate-half RoPE of ``x (t, heads, width)``: frequency
    ``i`` of ``width / 2`` (``theta^(-2i / width)``) takes its position
    from the stream its section gives (``sections``: how many frequencies
    each of the streams takes, in order; None: one stream), ``positions``
    ``(streams, t)``; the pairs are ``(i, i + width / 2)``."""
    import jax.numpy as jnp

    width = x.shape[-1]
    half = width // 2
    sections = [half] if sections is None else list(sections)
    if sum(sections) != half:
        raise ValueError(f"sections {sections} against {half} frequencies")
    stream = np.repeat(np.arange(len(sections)), sections)       # (half,)
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    pos = jnp.asarray(positions, jnp.float32)[stream]            # (half, t)
    ang = (pos.T * inv[None, :])[:, None, :]                     # (t, 1, half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _text_positions(t: int, streams: int):
    """A text token's position is the same in every stream."""
    return np.broadcast_to(np.arange(t), (streams, t))


def _attention(p, h, dm, q, out, block: int | None = None):
    """Steps 1-5 on one row ``h (t, d)`` -> ``(attention's output (t, d),
    the row's sum over tokens of the KL)``."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, kv, hd, hi, di = dm["heads"], dm["kv"], dm["hd"], dm["hi"], dm["di"]
    eps, theta = dm["eps"], dm["theta"]
    pos3 = _text_positions(t, len(dm["sections"]))
    qh = _rms(out(q(h) @ q(p["wq"])).reshape(t, heads, hd), p["q_g"], eps)
    kh = _rms(out(q(h) @ q(p["wk"])).reshape(t, kv, hd), p["k_g"], eps)
    vh = out(q(h) @ q(p["wv"])).reshape(t, kv, hd)
    qh = mrope(qh, pos3, theta, dm["sections"])
    kh = mrope(kh, pos3, theta, dm["sections"])
    hd_ = jax.lax.stop_gradient(h)
    pos1 = _text_positions(t, 1)
    qi = mrope(out(q(hd_) @ q(p["wiq"])).reshape(t, hi, di), pos1, theta)
    ki = mrope(_layer_norm(out(q(hd_) @ q(p["wik"])), p["ik_g"], p["ik_b"],
                           eps)[:, None, :], pos1, theta)[:, 0]
    w = out(q(hd_) @ q(p["wiw"])) * np.float32(1.0 / np.sqrt(hi * di))
    block = min(block or Q_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions in blocks of {block}")
    topk = min(dm["topk"], t)
    keys = jnp.arange(t)

    @jax.checkpoint
    def one_block(args):
        qb, qib, wb, pos = args      # (bq, heads, hd) (bq, hi, di) (bq, hi)
        bq = qb.shape[0]
        s = out(q(qib.reshape(bq * hi, di)) @ q(ki).T).reshape(bq, hi, t)
        index = (jax.nn.relu(s) * wb[:, :, None]).sum(1)         # (bq, t)
        causal = keys[None, :] <= pos[:, None]
        _, top = jax.lax.top_k(jax.lax.stop_gradient(
            jnp.where(causal, index, -jnp.inf)), topk)
        sel = jnp.zeros((bq, t), bool).at[
            jnp.arange(bq)[:, None], top].set(True) & causal
        a = out(jnp.einsum("qgjd,kgd->gjqk",
                           q(qb.reshape(bq, kv, heads // kv, hd)), q(kh))
                ) / np.float32(np.sqrt(hd))
        prob = jax.nn.softmax(jnp.where(sel[None, None], a, -jnp.inf), -1)
        o = out(jnp.einsum("gjqk,kgd->qgjd", q(prob), q(vh)))
        target = jax.lax.stop_gradient(prob.mean((0, 1)))        # (bq, t)
        logq = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), -1)
        live = sel & (target > 0)
        kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                       - jnp.where(live, logq, 0.0)), 0.0)
        return o.reshape(bq, heads * hd), kl.sum()

    cut = lambda a: a.reshape(t // block, block, *a.shape[1:])  # noqa: E731
    o, kl = jax.lax.map(one_block, (cut(qh), cut(qi), cut(w), cut(keys)))
    return out(q(o.reshape(t, heads * hd)) @ q(p["wo"])), kl.sum()


def _glu(v, w1, w3, w2, q, out):
    import jax

    return out(q(jax.nn.silu(out(q(v) @ q(w1))) * out(q(v) @ q(w3))) @ q(w2))


def routed(p, v, dm, q, out):
    """Step 6 on ``v (t, d)``: this share's part of the routed layer
    (experts ``first .. first + held`` of ``router``)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(out(q(v) @ q(p["gate"])), axis=-1)    # (t, router)
    _, choice = jax.lax.top_k(jax.lax.stop_gradient(s), dm["top_k"])
    w = jnp.take_along_axis(s, choice, axis=1)
    if dm["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def one(y, args):              # one held expert, masked
        e, w1, w3, w2 = args
        we = (w * (choice == e)).sum(-1)                     # (t,)
        return y + we[:, None] * _glu(v, w1, w3, w2, q, out), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(v), (
        dm["first"] + jnp.arange(dm["held"]), p["ew1"], p["ew3"], p["ew2"]))
    return y


def _layer(p, x, dm, q, out):
    """One layer on one row ``x (t, d)`` -> ``(y, the row's KL sum)``."""
    o, kl = _attention(p, _rms(x, p["ln1_g"], dm["eps"]), dm, q, out)
    a = x + o
    return a + routed(p, _rms(a, p["ln2_g"], dm["eps"]), dm, q, out), kl


def _nll(logits, labels):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


@functools.lru_cache(maxsize=None)
def _programs(dims_key: str, precision: str):
    import jax

    from reference.precision import operand, product

    dm = _dims_of(dims_key)
    q, out = operand(precision), product(precision)

    def layer(p, x):
        return _layer(p, x, dm, q, out)

    def layer_vjp(p, x, ct, ct_kl):
        _, vjp = jax.vjp(layer, p, x)
        return vjp((ct, ct_kl))                         # (dp, dx)

    def tail(tp, x, labels, n_tokens):
        """This row's part of the cross-entropy, and its sum."""
        z = _rms(x, tp["norm_g"], dm["eps"])
        total = _nll(out(q(z) @ q(tp["head"])), labels).sum()
        return total / n_tokens, total

    return {"layer": jax.jit(layer), "layer_vjp": jax.jit(layer_vjp),
            "tail_grad": jax.jit(jax.value_and_grad(tail, (0, 1),
                                                    has_aux=True),
                                 static_argnums=3)}


def loss_and_grads(params, tokens, labels, cfg: dict,
                   precision: str = "f32"):
    """``(loss, {"ce", "index"}, grads)`` of one step on ``tokens`` /
    ``labels`` ``(batch, t)``, the whole pytree's gradients at once: what
    the small tests compare a program with (``first_steps`` walks the same
    programs a layer at a time)."""
    import jax
    import jax.numpy as jnp

    from reference.precision import operand, product

    dm = dims(cfg)
    q, out = operand(precision), product(precision)
    n = tokens.shape[0] * tokens.shape[1]

    def loss_fn(ps):
        ce = kl = 0.0
        for r in range(tokens.shape[0]):
            x = ps["emb"][tokens[r]]
            for blk in ps["blocks"]:
                x, kl_r = _layer(blk, x, dm, q, out)
                kl = kl + kl_r
            z = _rms(x, ps["norm_g"], dm["eps"])
            ce = ce + _nll(out(q(z) @ q(ps["head"])),
                           jnp.asarray(labels[r])).sum()
        return (ce + kl) / n, {"ce": ce / n, "index": kl / n}

    with jax.default_matmul_precision("highest"):
        (loss, terms), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
    return loss, terms, grads


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
    configuration's learning rate; a row and a layer at a time."""
    import jax
    import jax.numpy as jnp

    if chips != 1:
        raise ValueError("the reference follows a one-chip step")
    dm = dims(cfg)
    layers = dm["layers"]
    lr = float(cfg["hyper"]["lr"])
    batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    n_tokens = batch * t
    prog = _programs(_key_of(cfg), precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    result = {"loss": [], "loss_ce": [], "loss_index": [], "grad_norm": {},
              "delta_norm": {}, "grad_first": {}}

    with jax.default_matmul_precision("highest"):
        params = init_params(seed, cfg)
        blocks = params["blocks"]
        for s in range(steps):
            rows = make_tokens(seed, cfg, t, s * batch, (s + 1) * batch)
            tokens, labels = rows[:, :-1], rows[:, 1:]
            # forward: acts[li][r] is row r's input to layer li
            acts = [[params["emb"][jnp.asarray(tokens[r])]
                     for r in range(batch)]]
            kl = 0.0
            for li in range(layers):
                outs = [prog["layer"](blocks[li], x) for x in acts[-1]]
                acts.append([o[0] for o in outs])
                kl += sum(float(o[1]) for o in outs)
                del outs
            tp = {g: params[g] for g in ("head", "norm_g")}
            ce, d_tail, cts = 0.0, None, []
            for r, x in enumerate(acts.pop()):
                (_, row_sum), (g_tp, gx) = prog["tail_grad"](
                    tp, x, jnp.asarray(labels[r]), n_tokens)
                ce += float(row_sum)
                d_tail = g_tp if d_tail is None else add(d_tail, g_tp)
                cts.append(gx)
                del g_tp
            result["loss_ce"].append(ce / n_tokens)
            result["loss_index"].append(kl / n_tokens)
            result["loss"].append((ce + kl) / n_tokens)

            def step_leaf(name, w, g):
                new = sgd(w, g)
                if s == 0:
                    # the gradient as SGD applied it: (w0 - w1) / lr
                    result["grad_norm"][name] = float(norm(w, new)) / lr
                    if name.rsplit(".", 1)[-1] in KEEP:
                        result["grad_first"][name] = np.asarray(
                            (w - new) / np.float32(lr))
                return new

            def step_group(name, tree, grads):
                if not isinstance(tree, dict):
                    return step_leaf(name, tree, grads)
                return {k: step_group(f"{name}.{k}", w, grads[k])
                        for k, w in tree.items()}

            for g in ("head", "norm_g"):
                params[g] = step_group(g, params[g], d_tail[g])
            ct_kl = np.float32(1.0 / n_tokens)
            for li in reversed(range(layers)):
                xs = acts.pop()
                dp = None
                for r in range(batch):
                    dpr, cts[r] = prog["layer_vjp"](blocks[li], xs[r],
                                                    cts[r], ct_kl)
                    dp = dpr if dp is None else add(dp, dpr)
                    del dpr
                blocks[li] = step_group(f"B{li}", blocks[li], dp)
                del dp, xs
            d_emb = jnp.zeros_like(params["emb"])
            for r in range(batch):
                d_emb = d_emb.at[jnp.asarray(tokens[r])].add(cts[r])
            params["emb"] = step_leaf("emb", params["emb"], d_emb)
            del d_emb, d_tail, cts, tp
        for group, path in leaf_groups(cfg).items():
            new = params[path[0]] if len(path) == 1 else blocks[path[1]]
            old = _flat(init_leaf_group(seed, cfg, group), group)
            for name, w in _flat(new, group).items():
                result["delta_norm"][name] = float(norm(w, old[name]))
            del old
    return result
