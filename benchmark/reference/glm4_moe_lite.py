"""Plain reference of the ``glm_4_7_flash`` configuration, cut as its file
says: a ``glm4_moe_lite`` decoder (DeepSeek-V3's block, as transformers'
modelling code of that name has it) in straightforward ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``: the two-term loss over
the vocabulary slice, gradients and plain SGD.  No kernels, no sort, no
grouped products, nothing of the program imported.

With ``d`` the hidden size, ``x`` a layer's input and ``RMSNorm(a; g) = a /
sqrt(mean(a^2) + eps) * g``:

    u = RMSNorm(x; ln1_g);  h = x + attention(u);  v = RMSNorm(h; ln2_g)
    y = h + ffn(v)

- latent attention (MLA), ``heads`` heads of ``nope + rope`` query/key
  entries and as many value entries: ``c_q = RMSNorm(u wq_a; q_a_g)``;
  ``[q_nope | q_pe] = c_q wq_b`` a head; ``[c_kv | k_pe] = u wkv_a``; ``c_kv
  = RMSNorm(c_kv; kv_a_g)``; ``[k_nope | v] = c_kv wkv_b`` a head.  RoPE
  (``rope_theta``, all ``rope`` entries) turns ``q_pe`` and the ONE ``k_pe``
  every head shares: the pairs are the neighbours ``(2i, 2i + 1)``, angle
  ``pos * theta^(-2i / rope)``, and the result is left in the order (all
  first members, all second members), as the family's code leaves it
  (queries and keys alike, so every product is the in-place rotation's).
  ``q = [q_nope | q_pe]``, ``k = [k_nope | k_pe]``; causal ``softmax(q k^T /
  sqrt(nope + rope)) v``, a head at a time; the ``heads * v`` result through
  ``wo``.
- dense ffn (the leading ``first_k_dense_replace`` layers): ``w2 (silu(v w1)
  * (v w3))``.
- sparse ffn: ``shared(v) + routed(v)``.  ``shared`` is a dense ffn of the
  expert width (``sw1, sw3, sw2``) that every chip computes alike.
  ``routed``: ``s = sigmoid(v gate)`` over ALL experts; the selected set is
  the top k of ``s + ebias`` (``e_score_correction_bias``: no gradient, no
  update); ``w_e = scale * s_e / (sum over the selected of s + 1e-6)``
  (``norm_topk_prob``, ``routed_scaling_factor``); the sum over the selected
  experts *this chip holds* of ``w_e E_e(v)``: a loop over the held experts
  with masks.  What the absent experts would add is left out, here as in
  the program.
- ``z = RMSNorm(x_last; norm_g)``; main logits ``z head`` (an untied head).
- the MTP module (depth 1, DeepSeek-V3's form; the family's checkpoints
  carry it as one layer past the stack): at position ``i``, with ``t_{i+1}``
  the next token, ``h' = [RMSNorm(emb[t_{i+1}]; enorm_g) | RMSNorm(z_i;
  hnorm_g)] proj`` (``2d -> d``), one more sparse layer with weights of its
  own, ``RMSNorm(.; mtp norm_g)``, logits against the SAME ``head``.
- loss ``= mean_i CE(main_i; t_{i+1}) + lambda * mean_{i < T-1} CE(mtp_i;
  t_{i+2})``: the second mean over the positions that have a second-next
  token inside the row, ``lambda = mtp_loss_weight``.

Departures from the published description, all under ``assumed`` in the
configuration file: ``lambda``, the order of the concatenation, that the
main stack's state enters the module through its final norm (as the
family's inference code hands it over), the order RoPE leaves its pairs in.

It also owns the seeded weights and token rows.  One jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, group by group, and runs a row and a layer at a time (the tail
behind the stack, head and MTP module, is one program a row), so it stays
well under what the program holds.

``first_steps``: the first three steps' loss, each leaf's first gradient as
plain SGD applied it (``(w0 - w1) / lr``), the small leaves' first gradients
themselves (``grad_first``) and each leaf's change after three steps.
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own
#: size (benchmark/limits.py and the cell's runs; my chip runs, PR 32;
#: PERF.md section 2 has the table): the bfloat16 program over 14 seeds
#: against the fp8 control over 2.  ``grad_diff_gap`` (the worst small
#: leaf's first gradient, norm of the difference; a router's ``gate`` on
#: every seed) 0.172-0.221 against 0.520-0.559: the limit between the two
#: with room on both sides (1.5 either way).  The norms separate here too:
#: ``grad_norm_gap`` up to 0.0041 against 0.0147-0.0179 and
#: ``delta_norm_gap`` up to 0.0032 against 0.0171-0.0208, each limit
#: between its two readings, with the more room above the sound runs'
#: largest (2.2 and 2.8 times it: fresh seeds read higher), far under a
#: part of the batch left out, a step that returns its state unchanged (a
#: gap of 1) and a router that is not the model's.  ``loss_gap`` hardly
#: moves with the precision (up to 9.0e-5 against 1.7e-4-2.4e-4) and
#: stands at the accepted cells' 0.0015, sixteen times the largest reading.
LIMITS = {
    "loss_gap": 0.0015,
    "grad_norm_gap": 0.009,
    "delta_norm_gap": 0.009,
    "grad_diff_gap": 0.34,
}

#: leaves small enough to keep whole for ``grad_diff_gap``: the gains, the
#: routers, latent attention's four projections, the MTP module's norms
KEEP = ("ln1_g", "ln2_g", "q_a_g", "kv_a_g", "gate", "wq_a", "wq_b", "wkv_a",
        "wkv_b", "enorm_g", "hnorm_g", "norm_g")


@functools.lru_cache(maxsize=None)
def _dims_of(key: str):
    import json
    return json.loads(key)


def dims(cfg: dict) -> dict:
    """The sizes the reference runs, from the configuration as run."""
    held = cfg["experts_held"]
    moe_ff = int(cfg["moe_intermediate_size"])
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "q_lora": int(cfg["q_lora_rank"]), "kv_lora": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "vd": int(cfg["v_head_dim"]),
        "ff": int(cfg["intermediate_size"]), "moe_ff": moe_ff,
        "shared_ff": int(cfg["n_shared_experts"]) * moe_ff,
        "vocab": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "n_dense": int(cfg["first_k_dense_replace"]),
        "router": int(cfg["router_width"]), "first": int(held["first"]),
        "held": int(held["count"]), "top_k": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "mtp": int(cfg["num_nextn_predict_layers"]),
        "mtp_weight": float(cfg["mtp_loss_weight"]),
    }


def _sparse(dm: dict, li: int) -> bool:
    return li >= dm["n_dense"]


def leaf_groups(cfg: dict) -> dict:
    """``{group: path in the step's parameter pytree}``, in the order the
    readings walk them; a group is one array or a (nested) dict of them."""
    dm = dims(cfg)
    out = {"emb": ("emb",), "head": ("head",), "norm_g": ("norm_g",)}
    out.update({f"B{li}": ("blocks", li) for li in range(dm["layers"])})
    if dm["mtp"]:
        out["mtp"] = ("mtp",)
    return out


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens needs on
    this chip, forward and backward (three passes, two operations a
    multiply-accumulate): latent attention's five projections in every
    layer (the MTP module's too), causal attention at the half it needs,
    the dense layer, the shared expert, the router, of the routed experts
    the passes this chip does (``top_k * held / router`` a token a sparse
    layer in expectation; the counter reports a step's), the MTP module's
    projection and BOTH passes of the head against the vocabulary slice.
    The embedding lookups are no products, and nothing recomputed
    counts."""
    dm = dims(cfg)
    d, heads = dm["d"], dm["heads"]
    qk = dm["nope"] + dm["rope"]
    mla = d * dm["q_lora"] + dm["q_lora"] * heads * qk + \
        d * (dm["kv_lora"] + dm["rope"]) + \
        dm["kv_lora"] * heads * (dm["nope"] + dm["vd"]) + heads * dm["vd"] * d
    sparse = d * dm["router"] + 3 * d * dm["shared_ff"] + \
        3 * d * dm["moe_ff"] * dm["top_k"] * dm["held"] / dm["router"]
    per_token = d * dm["vocab"] * (1 + dm["mtp"]) + dm["mtp"] * 2 * d * d
    attention = 0.0
    for li in range(dm["layers"] + dm["mtp"]):
        per_token += mla + (sparse if _sparse(dm, li) else 3 * d * dm["ff"])
        # QK^T and PV, each 2 * t * t * heads * width operations, halved
        attention += seq_len * seq_len * heads * (qk + dm["vd"])
    return 3.0 * (seq_len * 2.0 * per_token + attention)


# -- seeded weights and tokens ------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _layer_shapes(dm: dict, li: int) -> dict:
    d, heads = dm["d"], dm["heads"]
    qk = dm["nope"] + dm["rope"]
    out = {"ln1_g": (d,), "ln2_g": (d,),
           "wq_a": (d, dm["q_lora"]), "q_a_g": (dm["q_lora"],),
           "wq_b": (dm["q_lora"], heads * qk),
           "wkv_a": (d, dm["kv_lora"] + dm["rope"]),
           "kv_a_g": (dm["kv_lora"],),
           "wkv_b": (dm["kv_lora"], heads * (dm["nope"] + dm["vd"])),
           "wo": (heads * dm["vd"], d)}
    if _sparse(dm, li):
        e, f, s = dm["held"], dm["moe_ff"], dm["shared_ff"]
        out.update({"gate": (d, dm["router"]), "ebias": (dm["router"],),
                    "ew1": (e, d, f), "ew3": (e, d, f), "ew2": (e, f, d),
                    "sw1": (d, s), "sw3": (d, s), "sw2": (s, d)})
    else:
        out.update({"w1": (d, dm["ff"]), "w3": (d, dm["ff"]),
                    "w2": (dm["ff"], d)})
    return out


def _make_leaf(key, name: str, shape, share: int = 0):
    """Projections normal ``1/sqrt(fan_in)``, gains near one (so that no
    gain's gradient hides behind another's; the embedding is ``emb`` of
    :func:`_makers`, normal ``_EMB_STD``).  The expert bias is at the
    scale of the scores' spread (0.1 against a deviation of 0.2), so that
    the selection differs from the plain top k of the scores, and balanced
    over the chips as a trained model's is: every chip's share of ``share``
    experts carries the same ``share`` values, 0.1 x the normal quantiles,
    in an order of its own from the seed (drawn independently, the held
    experts' biases decide how many pairs this chip gets, and the rate
    follows them: PERF.md, PR 28)."""
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


#: deviation of the embedding's entries: a token's own vector (norm 45)
#: leads the residual stream.  At 0.02, as ``lfm2_moe.py`` has it, the
#: stream's first addend is attention's running mean of the values, all
#: but common to the late positions of a row; the routers' inputs then
#: share a component that favours some experts for every token, and the
#: pairs one chip's share receives range from 1,800 to 5,800 a layer with
#: the seed (mean 4,096), some layer-steps pass the compact pairs buffer's
#: 6,144 rows and the rate follows (8.27 against 8.33 samples/s; PERF.md,
#: PR 32).  At 1.0 the eight shares of a layer draw 3,550-4,650.
_EMB_STD = 1.0


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
        return jax.random.normal(jax.random.fold_in(key, 0x0E),
                                 (dm["vocab"], d),
                                 jnp.float32) * np.float32(_EMB_STD)

    def head(key):
        return _make_leaf(jax.random.fold_in(key, 0x4D), "head",
                          (d, dm["vocab"]))

    def norm_g(key):
        return _make_leaf(jax.random.fold_in(key, 0x4E), "norm_g", (d,))

    def mtp(key):
        k = jax.random.fold_in(key, 0x717)
        small = {"enorm_g": (d,), "hnorm_g": (d,), "proj": (2 * d, d),
                 "norm_g": (d,)}
        return {**{name: _make_leaf(k, name, shape)
                   for name, shape in small.items()},
                "block": layer(k, dm["layers"])}

    def whole(key):
        out = {"emb": emb(key), "head": head(key), "norm_g": norm_g(key),
               "blocks": [layer(key, li) for li in range(dm["layers"])]}
        if dm["mtp"]:
            out["mtp"] = mtp(key)
        return out

    return {"layer": jax.jit(layer, static_argnums=1), "emb": jax.jit(emb),
            "head": jax.jit(head), "norm_g": jax.jit(norm_g),
            "mtp": jax.jit(mtp), "whole": jax.jit(whole)}


def _key_of(cfg: dict) -> str:
    import json
    return json.dumps(dims(cfg), sort_keys=True)


def init_params(seed: int, cfg: dict):
    """The whole float32 pytree (``emb``, ``head``, ``norm_g``, ``blocks``,
    ``mtp``) on the default device, in one jitted call."""
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


def _rope(x, theta):
    """``x (t, h, rope)``: the pairs ``(2i, 2i + 1)`` turned by ``pos *
    theta^(-2i / rope)``; first members, then second members."""
    import jax.numpy as jnp

    t, _, rope = x.shape
    inv = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :])[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _latent_attention(p, u, dm, q, out):
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads, nope, rope, vd = dm["heads"], dm["nope"], dm["rope"], dm["vd"]
    c_q = _rms(out(q(u) @ q(p["wq_a"])), p["q_a_g"], dm["eps"])
    qh = out(q(c_q) @ q(p["wq_b"])).reshape(t, heads, nope + rope)
    kv_a = out(q(u) @ q(p["wkv_a"]))
    c_kv = _rms(kv_a[:, :dm["kv_lora"]], p["kv_a_g"], dm["eps"])
    kv = out(q(c_kv) @ q(p["wkv_b"])).reshape(t, heads, nope + vd)
    k_pe = _rope(kv_a[:, dm["kv_lora"]:].reshape(t, 1, rope), dm["theta"])
    qh = jnp.concatenate([qh[..., :nope], _rope(qh[..., nope:], dm["theta"])],
                         -1)
    kh = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_pe, (t, heads, rope))], -1)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def one_head(args):            # a head at a time: (t, t) scores
        qg, kg, vg = args
        s = out(q(qg) @ q(kg).T) / np.float32(np.sqrt(nope + rope))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return out(q(a) @ q(vg))

    o = jax.lax.map(one_head, (qh.transpose(1, 0, 2), kh.transpose(1, 0, 2),
                               kv[..., nope:].transpose(1, 0, 2)))
    return out(q(o.transpose(1, 0, 2).reshape(t, heads * vd)) @ q(p["wo"]))


def _glu(v, w1, w3, w2, q, out):
    import jax

    return out(q(jax.nn.silu(out(q(v) @ q(w1))) * out(q(v) @ q(w3))) @ q(w2))


def _routed(p, v, dm, q, out):
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(out(q(v) @ q(p["gate"])))            # (t, router)
    sel = s + jax.lax.stop_gradient(p["ebias"])
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


def _layer(p, x, sparse: bool, dm, q, out):
    """One layer on one row ``x (t, d)``."""
    h = x + _latent_attention(p, _rms(x, p["ln1_g"], dm["eps"]), dm, q, out)
    v = _rms(h, p["ln2_g"], dm["eps"])
    if sparse:
        return h + _glu(v, p["sw1"], p["sw3"], p["sw2"], q, out) + \
            _routed(p, v, dm, q, out)
    return h + _glu(v, p["w1"], p["w3"], p["w2"], q, out)


def _nll(logits, labels):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def _tail(tp, x, labels, dm, q, out):
    """Behind the stack, one row: ``(sum of the main loss over the row's
    positions, sum of the MTP loss over those that have a second-next
    token)``.  ``tp`` holds ``emb``, ``head``, ``norm_g`` and ``mtp``."""
    import jax.numpy as jnp

    z = _rms(x, tp["norm_g"], dm["eps"])
    main = _nll(out(q(z) @ q(tp["head"])), labels).sum()
    if not dm["mtp"]:
        return main, jnp.zeros(())
    m = tp["mtp"]
    both = jnp.concatenate([_rms(tp["emb"][labels], m["enorm_g"], dm["eps"]),
                            _rms(z, m["hnorm_g"], dm["eps"])], -1)
    y = _layer(m["block"], out(q(both) @ q(m["proj"])), True, dm, q, out)
    logits = out(q(_rms(y, m["norm_g"], dm["eps"])) @ q(tp["head"]))
    return main, _nll(logits[:-1], labels[1:]).sum()


@functools.lru_cache(maxsize=None)
def _programs(dims_key: str, precision: str):
    import jax

    from reference.precision import operand, product

    dm = _dims_of(dims_key)
    q, out = operand(precision), product(precision)

    def layer(p, x, sparse):
        return _layer(p, x, sparse, dm, q, out)

    def layer_vjp(p, x, ct, sparse):
        _, vjp = jax.vjp(lambda p_, x_: layer(p_, x_, sparse), p, x)
        return vjp(ct)                                  # (dp, dx)

    def tail(tp, x, labels, n_main, n_mtp):
        """This row's part of the loss, and its two sums."""
        main, mtp = _tail(tp, x, labels, dm, q, out)
        return main / n_main + np.float32(dm["mtp_weight"]) * mtp / n_mtp, \
            (main, mtp)

    return {"layer": jax.jit(layer, static_argnums=2),
            "layer_vjp": jax.jit(layer_vjp, static_argnums=3),
            "tail_grad": jax.jit(jax.value_and_grad(tail, (0, 1),
                                                    has_aux=True),
                                 static_argnums=(3, 4))}


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
    n_main, n_mtp = batch * t, max(batch * (t - 1), 1)
    prog = _programs(_key_of(cfg), precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    result = {"loss": [], "loss_main": [], "loss_mtp": [], "grad_norm": {},
              "delta_norm": {}, "grad_first": {}}
    tail_groups = ("emb", "head", "norm_g") + (("mtp",) if dm["mtp"] else ())

    with jax.default_matmul_precision("highest"):
        params = init_params(seed, cfg)
        blocks = params["blocks"]
        for s in range(steps):
            rows = make_tokens(seed, cfg, t, s * batch, (s + 1) * batch)
            tokens, labels = rows[:, :-1], rows[:, 1:]
            # forward: acts[li][r] is row r's input to layer li
            acts = [[params["emb"][jnp.asarray(tokens[r])]
                     for r in range(batch)]]
            for li in range(layers):
                acts.append([prog["layer"](blocks[li], x, _sparse(dm, li))
                             for x in acts[-1]])
            tp = {g: params[g] for g in tail_groups}
            sums, d_tail, cts = np.zeros(2), None, []
            for r, x in enumerate(acts.pop()):
                (_, row_sums), (g_tp, gx) = prog["tail_grad"](
                    tp, x, jnp.asarray(labels[r]), n_main, n_mtp)
                sums += [float(v) for v in row_sums]
                d_tail = g_tp if d_tail is None else add(d_tail, g_tp)
                cts.append(gx)
                del g_tp
            main, mtp = sums[0] / n_main, sums[1] / n_mtp
            result["loss_main"].append(main)
            result["loss_mtp"].append(mtp)
            result["loss"].append(main + dm["mtp_weight"] * mtp)

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

            for g in tail_groups[1:]:
                params[g] = step_group(g, params[g], d_tail[g])
            for li in reversed(range(layers)):
                xs = acts.pop()
                dp = None
                for r in range(batch):
                    dpr, cts[r] = prog["layer_vjp"](blocks[li], xs[r],
                                                    cts[r], _sparse(dm, li))
                    dp = dpr if dp is None else add(dp, dpr)
                    del dpr
                blocks[li] = step_group(f"B{li}", blocks[li], dp)
                del dp, xs
            # the embedding: the stack's lookup, and the MTP module's
            d_emb = d_tail["emb"]
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
