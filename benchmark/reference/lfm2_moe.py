"""Plain reference of the ``lfm2_24b_a2b`` configuration, cut as its file
says: an ``lfm2_moe`` decoder (transformers' modelling code of that name) in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: its mean next-token
cross-entropy over the vocabulary slice, gradients and plain SGD.  No
kernels, no sort, no grouped products, nothing of the program imported.

With ``d`` the hidden size and ``x`` a layer's input:

    u = RMSNorm(x; ln1_g);  h = x + mixer(u);  v = RMSNorm(h; ln2_g)
    y = h + ffn(v)

- ``conv`` mixer: ``[B, C, X] = split3(u w_in)``; ``z = B * X``; ``c_t =
  sum_j conv_k[j] * z_{t-taps+1+j}`` (depthwise, causal, zeros before the
  sequence starts); ``(C * c) w_out``.
- ``full_attention`` mixer: ``q = u wq``, ``k = u wk``, ``v = u wv``; q and k
  RMSNorm over the head with gains ``q_g`` / ``k_g``, then rotate-half RoPE
  over the whole head; causal ``softmax(q k^T / sqrt(head)) v``, each
  key/value head serving ``heads / kv_heads`` query heads; ``o wo``.
- dense ffn: ``w2 (silu(v w1) * (v w3))``.
- sparse ffn: ``s = sigmoid(v gate)`` over ALL experts; the selected set is
  the top k of ``s + ebias`` (the bias takes no gradient and no update);
  ``w_e = scale * s_e / (sum over the selected of s + 1e-6)``; the sum over
  the selected experts *this chip holds* of ``w_e E_e(v)``, ``E_e`` a dense
  ffn of the expert width: a loop over the held experts with masks.  What
  the absent experts would add is left out, here as in the program.
- after the last layer RMSNorm (``norm_g``), logits against ``emb``.

It also owns the seeded weights and token rows.  One jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, layer by layer, and runs a row and a layer at a time, so it
stays well under what the program holds.

``first_steps``: the first three steps' mean loss, each leaf's first
gradient as plain SGD applied it (``(w0 - w1) / lr``), the small leaves'
first gradients themselves (``grad_first``) and each leaf's change after
three steps.  The backward pass runs layer by layer and updates each layer
in place.
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own
#: size (benchmark/limits.py and the cell's runs; my chip runs, PR 28;
#: PERF.md section 2 has the table): the bfloat16 program over 23 seeds
#: against the fp8 control over 3.  ``grad_diff_gap`` (the worst small
#: leaf's first gradient, norm of the difference; a router's ``gate`` on
#: every seed) 0.202-0.276 against 0.611-0.718: the number the control
#: fails, the limit between the two with room on both sides.  The norms
#: move less with the precision (``grad_norm_gap`` up to 0.0036 against
#: 0.0101-0.0193, ``delta_norm_gap`` up to 0.0027 against 0.0071-0.0108,
#: ``loss_gap`` up to 1.4e-4 against 2.3e-4-5.4e-4) and are held, as the
#: accepted cells', at 2.4 to 3 times the sound runs' largest against a part
#: of the batch left out, a step that returns its state unchanged (a gap
#: of 1) and a router that is not the model's; the loss at the accepted
#: cells' 0.0015, ten times the largest reading.
LIMITS = {
    "loss_gap": 0.0015,
    "grad_norm_gap": 0.0085,
    "delta_norm_gap": 0.008,
    "grad_diff_gap": 0.40,
}

_KINDS = {"conv": "conv", "full_attention": "attn"}
#: leaves small enough to keep whole for ``grad_diff_gap``
KEEP = ("ln1_g", "ln2_g", "conv_k", "gate", "wq", "wk", "wv", "wo", "q_g",
        "k_g")


@functools.lru_cache(maxsize=None)
def _dims_of(key: str):
    import json
    return json.loads(key)


def dims(cfg: dict) -> dict:
    """The sizes the reference runs, from the configuration as run."""
    types = [_KINDS[t] for t in cfg["layer_types"]]
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    held = cfg["experts_held"]
    return {
        "d": d, "heads": heads, "kv": int(cfg["num_key_value_heads"]),
        "hd": d // heads, "ff": int(cfg["intermediate_size"]),
        "moe_ff": int(cfg["moe_intermediate_size"]),
        "vocab": int(cfg["vocab_size"]), "types": types,
        "n_dense": int(cfg["num_dense_layers"]),
        "router": int(cfg["router_width"]), "first": int(held["first"]),
        "held": int(held["count"]), "top_k": int(cfg["num_experts_per_tok"]),
        "taps": int(cfg["conv_L_cache"]), "eps": float(cfg["norm_eps"]),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "bias": bool(cfg["use_expert_bias"]),
    }


def _sparse(dm: dict, li: int) -> bool:
    return li >= dm["n_dense"]


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens needs on
    this chip, forward and backward (three passes, two operations a
    multiply-accumulate): every projection, the head against the
    vocabulary slice, causal attention at the half it needs, and of the
    experts the passes this chip does: ``top_k * held / router`` a token a
    sparse layer in expectation (the routing decides the count of a step;
    the counter reports it).  The convolution's taps and the embedding
    lookup are no products, and nothing recomputed counts."""
    dm = dims(cfg)
    d = dm["d"]
    per_token = d * dm["vocab"]
    attention = 0.0
    for li, kind in enumerate(dm["types"]):
        if kind == "conv":
            per_token += d * 3 * d + d * d
        else:
            per_token += 2 * d * dm["heads"] * dm["hd"] + \
                2 * d * dm["kv"] * dm["hd"]
            attention += 2.0 * seq_len * seq_len * dm["heads"] * dm["hd"]
        if _sparse(dm, li):
            per_token += d * dm["router"] + 3 * d * dm["moe_ff"] * \
                dm["top_k"] * dm["held"] / dm["router"]
        else:
            per_token += 3 * d * dm["ff"]
    return 3.0 * (seq_len * 2.0 * per_token + attention)


# -- seeded weights and tokens ------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _layer_shapes(dm: dict, li: int) -> dict:
    d, hd = dm["d"], dm["hd"]
    out = {"ln1_g": (d,), "ln2_g": (d,)}
    if dm["types"][li] == "conv":
        out.update({"w_in": (d, 3 * d), "conv_k": (dm["taps"], d),
                    "w_out": (d, d)})
    else:
        out.update({"wq": (d, dm["heads"] * hd), "wk": (d, dm["kv"] * hd),
                    "wv": (d, dm["kv"] * hd), "wo": (dm["heads"] * hd, d),
                    "q_g": (hd,), "k_g": (hd,)})
    if _sparse(dm, li):
        e, f = dm["held"], dm["moe_ff"]
        out.update({"gate": (d, dm["router"]), "ew1": (e, d, f),
                    "ew3": (e, d, f), "ew2": (e, f, d)})
        if dm["bias"]:
            out["ebias"] = (dm["router"],)
    else:
        out.update({"w1": (d, dm["ff"]), "w3": (d, dm["ff"]),
                    "w2": (dm["ff"], d)})
    return out


def _make_leaf(key, name: str, shape, share: int = 0):
    """Projections normal ``1/sqrt(fan_in)``, taps ``1/sqrt(taps)``, gains
    near one (so that no gain's gradient hides behind another's).  The
    expert bias is at the scale of the scores' spread (0.1 against a
    deviation of 0.2), so that the selection differs from the plain top k
    of the scores, and balanced over the chips as a trained model's is:
    every chip's share of ``share`` experts carries the same ``share``
    values, 0.1 x the normal quantiles, in an order of its own from the
    seed.  (Drawn independently, the held experts' biases decide how many
    pairs this chip gets: 26,383 to 42,307 a step over six seeds, and a
    rate that follows them, 12.05 to 12.62; my chip runs, PR 28.)"""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, sum(ord(c) * (i + 1)
                                    for i, c in enumerate(name)))
    if name == "ebias":
        values = np.float32(0.1) * jax.scipy.special.ndtri(
            (jnp.arange(share, dtype=jnp.float32) + 0.5) / share)
        return jnp.concatenate([
            jax.random.permutation(jax.random.fold_in(k, chip), values)
            for chip in range(shape[0] // share)])
    n = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + np.float32(0.05) * n
    return n / np.float32(np.sqrt(shape[-2] if len(shape) > 1 else shape[0]))


@functools.lru_cache(maxsize=None)
def _makers(dims_key: str):
    import jax
    import jax.numpy as jnp

    dm = _dims_of(dims_key)

    def layer(key, li):
        k = jax.random.fold_in(key, li + 1)
        return {name: _make_leaf(k, name, shape, dm["held"])
                for name, shape in _layer_shapes(dm, li).items()}

    def emb(key):
        return jax.random.normal(jax.random.fold_in(key, 0x0E),
                                 (dm["vocab"], dm["d"]),
                                 jnp.float32) * np.float32(0.02)

    def norm_g(key):
        return _make_leaf(jax.random.fold_in(key, 0x4E), "norm_g",
                          (dm["d"],))

    def whole(key):
        return {"emb": emb(key), "norm_g": norm_g(key),
                "blocks": [layer(key, li)
                           for li in range(len(dm["types"]))]}

    return {"layer": jax.jit(layer, static_argnums=1), "emb": jax.jit(emb),
            "norm_g": jax.jit(norm_g), "whole": jax.jit(whole)}


def _key_of(cfg: dict) -> str:
    import json
    return json.dumps(dims(cfg), sort_keys=True)


def init_params(seed: int, cfg: dict):
    """The whole float32 pytree (``emb``, ``norm_g``, ``blocks``; the head
    is the embedding) on the default device, in one jitted call."""
    return _makers(_key_of(cfg))["whole"](_root_key(seed))


def init_leaf_group(seed: int, cfg: dict, group):
    """``"emb"``, ``"norm_g"`` or a layer index -> that group's leaves,
    bit-identical with :func:`init_params`."""
    mk, key = _makers(_key_of(cfg)), _root_key(seed)
    if group in ("emb", "norm_g"):
        return mk[group](key)
    return mk["layer"](key, int(group))


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


def _rope(x, theta):
    """``x (t, h, hd)``: rotate-half over the whole head, positions 0.."""
    import jax.numpy as jnp

    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


def _conv_mixer(p, u, dm, q, out):
    import jax.numpy as jnp

    t = u.shape[0]
    b, c, x = jnp.split(out(q(u) @ q(p["w_in"])), 3, axis=-1)
    z = b * x
    zp = jnp.concatenate([jnp.zeros((dm["taps"] - 1, z.shape[1]), z.dtype),
                          z], 0)
    conv = sum(p["conv_k"][j] * zp[j:j + t] for j in range(dm["taps"]))
    return out(q(c * conv) @ q(p["w_out"]))


def _attn_mixer(p, u, dm, q, out):
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads, kv, hd = dm["heads"], dm["kv"], dm["hd"]
    qh = out(q(u) @ q(p["wq"])).reshape(t, heads, hd)
    kh = out(q(u) @ q(p["wk"])).reshape(t, kv, hd)
    vh = out(q(u) @ q(p["wv"])).reshape(t, kv, hd)
    qh = _rope(_rms(qh, p["q_g"], dm["eps"]), dm["theta"])
    kh = _rope(_rms(kh, p["k_g"], dm["eps"]), dm["theta"])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def group(args):               # one key/value head and its query heads
        qg, kg, vg = args          # (t, heads/kv, hd), (t, hd), (t, hd)
        s = out(jnp.einsum("qhd,kd->hqk", q(qg), q(kg))) / np.float32(
            np.sqrt(hd))
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return out(jnp.einsum("hqk,kd->qhd", q(a), q(vg)))

    o = jax.lax.map(group, (
        qh.reshape(t, kv, heads // kv, hd).transpose(1, 0, 2, 3),
        kh.transpose(1, 0, 2), vh.transpose(1, 0, 2)))    # (kv, t, g, hd)
    o = o.transpose(1, 0, 2, 3).reshape(t, heads * hd)
    return out(q(o) @ q(p["wo"]))


def _glu(v, w1, w3, w2, q, out):
    import jax

    return out(q(jax.nn.silu(out(q(v) @ q(w1))) * out(q(v) @ q(w3))) @ q(w2))


def _sparse_ffn(p, v, dm, q, out):
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(out(q(v) @ q(p["gate"])))            # (t, router)
    sel = s + jax.lax.stop_gradient(p["ebias"]) if dm["bias"] else s
    _, choice = jax.lax.top_k(jax.lax.stop_gradient(sel), dm["top_k"])
    w = jnp.take_along_axis(s, choice, axis=1)
    if dm["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    w = w * np.float32(dm["scale"])

    @jax.checkpoint
    def one(y, args):              # one held expert, masked
        e, w1, w3, w2 = args
        we = (w * (choice == e)).sum(-1)                    # (t,)
        return y + we[:, None] * _glu(v, w1, w3, w2, q, out), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(v), (
        dm["first"] + jnp.arange(dm["held"]), p["ew1"], p["ew3"], p["ew2"]))
    return y


def _layer(p, x, kind: str, sparse: bool, dm, q, out):
    """One layer on one row ``x (t, d)``: ``kind`` is its mixer
    (``conv`` or ``attn``), ``sparse`` whether its ffn is the experts'."""
    u = _rms(x, p["ln1_g"], dm["eps"])
    mix = _conv_mixer if kind == "conv" else _attn_mixer
    h = x + mix(p, u, dm, q, out)
    v = _rms(h, p["ln2_g"], dm["eps"])
    if sparse:
        return h + _sparse_ffn(p, v, dm, q, out)
    return h + _glu(v, p["w1"], p["w3"], p["w2"], q, out)


@functools.lru_cache(maxsize=None)
def _programs(dims_key: str, precision: str):
    import jax
    import jax.numpy as jnp

    from reference.precision import operand, product

    dm = _dims_of(dims_key)
    q, out = operand(precision), product(precision)

    # one program a kind of layer, not a layer: layers of one kind share it
    def layer(p, x, kind, sparse):
        return _layer(p, x, kind, sparse, dm, q, out)

    def layer_vjp(p, x, ct, kind, sparse):
        _, vjp = jax.vjp(lambda p_, x_: layer(p_, x_, kind, sparse), p, x)
        return vjp(ct)                                  # (dp, dx)

    def head_loss(emb, norm_g, x, labels):              # one row (t, d)
        logits = out(q(_rms(x, norm_g, dm["eps"])) @ q(emb).T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

    return {"layer": jax.jit(layer, static_argnums=(2, 3)),
            "layer_vjp": jax.jit(layer_vjp, static_argnums=(3, 4)),
            "head_grad": jax.jit(jax.value_and_grad(head_loss, (0, 1, 2)))}


# -- training ------------------------------

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
    layers = len(dm["types"])
    kinds = [(dm["types"][li], _sparse(dm, li)) for li in range(layers)]
    lr = float(cfg["hyper"]["lr"])
    batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    prog = _programs(_key_of(cfg), precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    result = {"loss": [], "grad_norm": {}, "delta_norm": {}, "grad_first": {}}

    with jax.default_matmul_precision("highest"):
        params = init_params(seed, cfg)
        blocks = params["blocks"]
        for s in range(steps):
            rows = make_tokens(seed, cfg, t, s * batch, (s + 1) * batch)
            tokens, labels = rows[:, :-1], rows[:, 1:]
            n_tok = batch * t
            # forward: acts[li][r] is row r's input to layer li
            acts = [[params["emb"][jnp.asarray(tokens[r])]
                     for r in range(batch)]]
            for li in range(layers):
                acts.append([prog["layer"](blocks[li], x, *kinds[li])
                             for x in acts[-1]])
            total, d_emb, d_norm, cts = 0.0, None, None, []
            for r, x in enumerate(acts.pop()):
                loss, (ge, gn, gx) = prog["head_grad"](
                    params["emb"], params["norm_g"], x,
                    jnp.asarray(labels[r]))
                total += float(loss)
                d_emb = ge if d_emb is None else d_emb + ge
                d_norm = gn if d_norm is None else d_norm + gn
                cts.append(gx / n_tok)
            result["loss"].append(total / n_tok)

            def step_leaf(name, w, g, leaf=""):
                new = sgd(w, g)
                if s == 0:
                    # the gradient as SGD applied it: (w0 - w1) / lr
                    result["grad_norm"][name] = float(norm(w, new)) / lr
                    if leaf in KEEP or name == "norm_g":
                        result["grad_first"][name] = np.asarray(
                            (w - new) / np.float32(lr))
                return new

            params["norm_g"] = step_leaf("norm_g", params["norm_g"],
                                         d_norm / n_tok)
            for li in reversed(range(layers)):
                xs = acts.pop()
                dp = None
                for r in range(batch):
                    dpr, cts[r] = prog["layer_vjp"](blocks[li], xs[r],
                                                    cts[r], *kinds[li])
                    dp = dpr if dp is None else add(dp, dpr)
                    del dpr
                blocks[li] = {k: step_leaf(f"B{li}.{k}", w, dp[k], k)
                              for k, w in blocks[li].items()}
                del dp, xs
            # the embedding is also the head: both gradients, one leaf
            d_emb = d_emb / n_tok
            for r in range(batch):
                d_emb = d_emb.at[jnp.asarray(tokens[r])].add(cts[r])
            params["emb"] = step_leaf("emb", params["emb"], d_emb)
            del d_emb, cts
        for group in ("emb", "norm_g"):
            result["delta_norm"][group] = float(norm(
                params[group], init_leaf_group(seed, cfg, group)))
        for li in range(layers):
            p0 = init_leaf_group(seed, cfg, li)
            for k, w in blocks[li].items():
                result["delta_norm"][f"B{li}.{k}"] = float(norm(w, p0[k]))
    return result
