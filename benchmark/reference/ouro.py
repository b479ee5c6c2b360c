"""Plain reference of the ``ouro_2_6b`` configuration, cut as its file says:
an ``ouro`` decoder (Ouro's LoopLM, arXiv:2510.25741; the family's published
modelling code gives the layer) in straightforward ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``: the exit-weighted loss of
a stack run several times over the same weights, every gradient and plain
SGD.  No kernel, no scan, nothing recomputed, no cache, nothing of the
program imported: the stack is a Python loop over loop steps and layers.

With ``d`` the hidden size and ``RMSNorm(a; g) = a / sqrt(mean(a^2) + eps) *
g``:

- *A layer* (index i of N, the same weights in every loop step), sandwich
  norm, four gains a layer (the published code's ``input_layernorm``,
  ``input_layernorm_2``, ``post_attention_layernorm``,
  ``post_attention_layernorm_2``; here ``ln1_g``, ``ln1o_g``, ``ln2_g``,
  ``ln2o_g``):

      a = x + RMSNorm(Attn(RMSNorm(x; ln1_g)); ln1o_g)
      y = a + RMSNorm(SwiGLU(RMSNorm(a; ln2_g)); ln2o_g)

  ``Attn``: ``q, k, v = h wq, h wk, h wv`` as ``heads`` heads of
  ``head_dim`` (as many key/value heads), no bias, no QK-norm; rotate-half
  RoPE over the whole head (pairs ``(i, i + head_dim / 2)``, angle ``pos *
  theta^(-2i / head_dim)``) on q and k; causal ``softmax(q k^T /
  sqrt(head_dim)) v``, in blocks of queries so that 4,096 fits; ``wo``.
  ``SwiGLU``: ``(silu(m w1) * (m w3)) w2``, no bias.
- *The loop.*  ``h_0 = emb[tokens]``; for r = 1..R (``total_ut_steps``):
  ``h_r = RMSNorm(Stack(h_{r-1}); norm_g)``: the ONE final norm closes every
  loop step and its result is fed into layer 0 again; ``g_r = h_r exit_w +
  exit_b`` (one logit a token); ``logits_r = h_r head`` (untied), taken in
  chunks of tokens.
- *The exit distribution and the loss*, token by token: ``lam_r =
  sigmoid(g_r)``; ``S_0 = 1``; for r < R: ``p_r = lam_r S_{r-1}``, ``S_r =
  S_{r-1} (1 - lam_r)``; ``p_R = S_{R-1}`` (the four sum to one; the last
  gate is read by nothing).  ``L = mean over tokens of [sum_r p_r nll_r -
  beta H(p)]``, ``nll_r`` the next-token cross-entropy of ``logits_r``,
  ``H(p) = -sum_r p_r log p_r``; gradients flow through ``p`` into the gate
  and the stack.  With R = 1 the distribution is the constant 1, the entropy
  0, the loss the plain cross-entropy, and the pytree has no gate.

Departures from the published description, all under ``assumed`` in the
configuration file: ``beta`` 0.1 (the paper's first-stage value; the config
gives none), the gate's input (the loop step's state through the final
norm), that the final norm lies inside the loop, plain SGD for the model's
own optimizer.  ``early_exit_threshold`` is an inference key and is read by
nothing.

It also owns the seeded weights and token rows.  One jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, group by group, and runs a row and a layer application at a
time, keeping every application's input (``R x N`` arrays of ``(t, d)`` a
row) for the backward walk.

``first_steps``: the first three steps' loss, each leaf's first gradient as
plain SGD applied it (``(w0 - w1) / lr``: every weight's gradient is the sum
over its R uses), the small leaves' first gradients themselves
(``grad_first``), each leaf's change after three steps, and a step's loop
readings (``loop``: the mean exit step, the mean entropy, each loop step's
own cross-entropy).
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own
#: size (benchmark/limits.py and the cell's runs; my chip runs, PR 34;
#: PERF.md section 2 has the table): the bfloat16 program over 28 seeds
#: against the fp8 control over 6; every limit lies between its two
#: readings.  ``grad_diff_gap`` (the worst small leaf's first gradient,
#: norm of the difference; layer 0's second SwiGLU gain ``ln2o_g`` on most
#: seeds, where the routers were the worst elsewhere: this stack makes no
#: discrete choice, so its worst leaf is a gain behind the widest product)
#: 0.026-0.042 against 0.253-0.341: 2.6 times over the sound runs' largest,
#: 2.3 under the control's least.  The norms separate too, less widely:
#: ``grad_norm_gap`` up to 0.0068 against 0.024-0.075 and
#: ``delta_norm_gap`` up to 0.0052 against 0.019-0.042 (the worst leaf most
#: often the gate's weight or bias, whose gradient is a small difference of
#: large per-token terms, so it moves most with the seed), each limit with
#: the more room above the sound runs' largest (2.4 and 2.5 times it: fresh
#: seeds read higher; 1.5 times under the control's least).  ``loss_gap``
#: separates here as it did not in the accepted cells (up to 4.7e-5 against
#: 1.5e-4-3.4e-4, 3.2 times apart), so it takes no other cell's number:
#: 1.8 times over the one, 1.7 under the other.  Faults planted in the
#: program at the cell's size, two seeds each (PERF.md section 6), fail all
#: four: a loop step left out reads 0.0021-0.0024 / 0.24-0.35 / 0.35-0.39 /
#: 0.40-0.53 (loss, gradient norm, change, difference), row 1 of every
#: step masked 0.0009-0.0015 / 0.56-0.71 / 0.39-0.61 / 1.0, the last gate
#: read (``p_R = lam_R S_{R-1}``) 0.93-0.97 / 51-77 / 112-147 / 54-77; a
#: step that returns its state unchanged reads a change of 1.
LIMITS = {
    "loss_gap": 8.5e-5,
    "grad_norm_gap": 0.016,
    "delta_norm_gap": 0.013,
    "grad_diff_gap": 0.11,
}

#: leaves small enough to keep whole for ``grad_diff_gap``: the four gains a
#: layer, the final gain, the exit gate's weight and bias
KEEP = ("ln1_g", "ln1o_g", "ln2_g", "ln2o_g", "norm_g", "exit_w", "exit_b")

#: queries a block of the reference's attention, tokens a chunk of its head
_Q_BLOCK, _HEAD_CHUNK = 512, 1024


@functools.lru_cache(maxsize=None)
def _dims_of(key: str):
    import json
    return json.loads(key)


def dims(cfg: dict) -> dict:
    """The sizes the reference runs, from the configuration as run."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "d": d, "heads": heads,
        "kv_heads": int(cfg.get("num_key_value_heads", heads)),
        "hd": int(cfg.get("head_dim") or d // heads),
        "ff": int(cfg["intermediate_size"]), "vocab": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "steps": int(cfg.get("total_ut_steps", 1)),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "beta": float(cfg.get("exit_entropy_weight", 0.1)),
    }


def leaf_groups(cfg: dict) -> dict:
    """``{group: path in the step's parameter pytree}``, in the order the
    readings walk them; a group is one array or a dict of them."""
    dm = dims(cfg)
    out = {"emb": ("emb",), "head": ("head",), "norm_g": ("norm_g",)}
    if dm["steps"] > 1:
        out.update({"exit_w": ("exit_w",), "exit_b": ("exit_b",)})
    out.update({f"B{li}": ("blocks", li) for li in range(dm["layers"])})
    return out


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens needs,
    forward and backward (three passes, two operations a
    multiply-accumulate): ``total_ut_steps x num_hidden_layers`` layer
    applications (four projections, the SwiGLU's three products, causal
    attention at the half it needs), and a head pass and a gate product for
    every loop step.  The embedding lookup is no product, and nothing that
    is recomputed counts."""
    dm = dims(cfg)
    d, hd = dm["d"], dm["hd"]
    layer = d * hd * (2 * dm["heads"] + 2 * dm["kv_heads"]) + 3 * d * dm["ff"]
    # QK^T and PV, each 2 * t * t * heads * head_dim operations, halved
    attention = seq_len * seq_len * dm["heads"] * 2 * hd
    per_token = dm["layers"] * layer + d * dm["vocab"] + d
    return 3.0 * dm["steps"] * (seq_len * 2.0 * per_token +
                                dm["layers"] * attention)


# -- seeded weights and tokens ------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _layer_shapes(dm: dict) -> dict:
    d, hd = dm["d"], dm["hd"]
    return {"ln1_g": (d,), "ln1o_g": (d,), "ln2_g": (d,), "ln2o_g": (d,),
            "wq": (d, dm["heads"] * hd), "wk": (d, dm["kv_heads"] * hd),
            "wv": (d, dm["kv_heads"] * hd), "wo": (dm["heads"] * hd, d),
            "w1": (d, dm["ff"]), "w3": (d, dm["ff"]), "w2": (dm["ff"], d)}


def _make_leaf(key, name: str, shape):
    """Projections (the gate's weight among them) normal ``1/sqrt(fan_in)``,
    gains ``1 + normal 0.05`` (so that no gain's gradient hides behind
    another's), the gate's bias 0: a token's gate logit is about standard
    normal, so the exit distribution is neither uniform nor collapsed at
    the first step."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, sum(ord(c) * (i + 1)
                                    for i, c in enumerate(name)))
    if name == "exit_b":
        return jnp.zeros(shape, jnp.float32)
    n = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + np.float32(0.05) * n
    return n / np.float32(np.sqrt(shape[-2] if len(shape) > 1 else shape[0]))


#: deviation of the embedding's entries: a token's own vector leads the
#: residual stream (``glm4_moe_lite.py`` has why)
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

    def small(name, tag, shape):
        return lambda key: _make_leaf(jax.random.fold_in(key, tag), name,
                                      shape)

    mk = {"layer": layer, "emb": emb,
          "head": small("head", 0x4D, (d, dm["vocab"])),
          "norm_g": small("norm_g", 0x4E, (d,))}
    if dm["steps"] > 1:
        mk["exit_w"] = small("exit_w", 0x4F, (d, 1))
        mk["exit_b"] = small("exit_b", 0x50, (1,))

    def whole(key):
        out = {g: fn(key) for g, fn in mk.items() if g != "layer"}
        out["blocks"] = [layer(key, li) for li in range(dm["layers"])]
        return out

    return {**{g: jax.jit(fn) for g, fn in mk.items() if g != "layer"},
            "layer": jax.jit(layer, static_argnums=1),
            "whole": jax.jit(whole)}


def _key_of(cfg: dict) -> str:
    import json
    return json.dumps(dims(cfg), sort_keys=True)


def init_params(seed: int, cfg: dict):
    """The whole float32 pytree (``emb``, ``head``, ``norm_g``, ``exit_w``,
    ``exit_b``, ``blocks``) on the default device, in one jitted call."""
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


def _rope(x, theta):
    """``x (t, h, hd)``: rotate-half over the whole head, positions from
    0: the pairs ``(i, i + hd / 2)`` turned by ``pos * theta^(-2i / hd)``."""
    import jax.numpy as jnp

    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :])[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _attention(p, u, dm, q, out):
    """Causal attention of one row ``u (t, d)``, a block of queries at a
    time against the keys up to the block's end."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    heads, kv, hd = dm["heads"], dm["kv_heads"], dm["hd"]
    qh = _rope(out(q(u) @ q(p["wq"])).reshape(t, heads, hd), dm["theta"])
    kh = _rope(out(q(u) @ q(p["wk"])).reshape(t, kv, hd), dm["theta"])
    vh = out(q(u) @ q(p["wv"])).reshape(t, kv, hd)
    if kv != heads:
        kh, vh = (jnp.repeat(a, heads // kv, axis=1) for a in (kh, vh))
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (qh, kh, vh))   # (h, t, hd)
    blocks = []
    for lo in range(0, t, _Q_BLOCK):
        hi = min(lo + _Q_BLOCK, t)
        s = out(jnp.einsum("hqd,hkd->hqk", q(qh[:, lo:hi]), q(kh[:, :hi]))) \
            / np.float32(np.sqrt(hd))
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        blocks.append(out(jnp.einsum("hqk,hkd->hqd", q(a), q(vh[:, :hi]))))
    o = jnp.concatenate(blocks, axis=1).transpose(1, 0, 2)
    return out(q(o.reshape(t, heads * hd)) @ q(p["wo"]))


def _glu(v, w1, w3, w2, q, out):
    import jax

    return out(q(jax.nn.silu(out(q(v) @ q(w1))) * out(q(v) @ q(w3))) @ q(w2))


def _layer(p, x, dm, q, out):
    """One layer application on one row ``x (t, d)``."""
    eps = dm["eps"]
    a = x + _rms(_attention(p, _rms(x, p["ln1_g"], eps), dm, q, out),
                 p["ln1o_g"], eps)
    m = _rms(a, p["ln2_g"], eps)
    return a + _rms(_glu(m, p["w1"], p["w3"], p["w2"], q, out),
                    p["ln2o_g"], eps)


def _close(tp, x, labels, dm, q, out):
    """What closes a loop step, one row: the final norm, the gate's logit
    and the head pass -> ``(h_r (t, d), g_r (t,), nll_r (t,))``; ``tp``
    holds ``norm_g``, ``head`` and the gate."""
    import jax
    import jax.numpy as jnp

    h = _rms(x, tp["norm_g"], dm["eps"])
    g = jnp.zeros(x.shape[0], jnp.float32)
    if dm["steps"] > 1:
        g = out(q(h) @ q(tp["exit_w"]))[:, 0] + tp["exit_b"][0]
    nll = []
    for lo in range(0, x.shape[0], _HEAD_CHUNK):
        hi = lo + _HEAD_CHUNK
        logp = jax.nn.log_softmax(out(q(h[lo:hi]) @ q(tp["head"])), axis=-1)
        nll.append(-jnp.take_along_axis(logp, labels[lo:hi, None],
                                        axis=-1)[:, 0])
    return h, g, jnp.concatenate(nll)


def exit_distribution(g):
    """``g (R, ...)`` gate logits -> ``p (R, ...)``: ``p_r = sigmoid(g_r)
    prod_{s<r} (1 - sigmoid(g_s))`` for r < R, the rest for r = R."""
    import jax
    import jax.numpy as jnp

    p, alive = [], jnp.ones_like(g[0])
    for r in range(g.shape[0] - 1):
        lam = jax.nn.sigmoid(g[r])
        p.append(lam * alive)
        alive = alive * (1.0 - lam)
    return jnp.stack(p + [alive])


def _exit_loss(g, nll, beta):
    """One row's sum over tokens of ``sum_r p_r nll_r - beta H(p)``, with
    the sums the loop readings are made of: of each loop step's ``nll``, of
    ``sum_r r p_r`` and of ``H(p)``."""
    import jax.numpy as jnp

    p = exit_distribution(g)
    plogp = p * jnp.log(jnp.maximum(p, 1e-30))
    rank = jnp.arange(1, g.shape[0] + 1, dtype=jnp.float32)[:, None]
    return (p * nll + beta * plogp).sum(), \
        (nll.sum(-1), (rank * p).sum(), -plogp.sum())


@functools.lru_cache(maxsize=None)
def _programs(dims_key: str, precision: str):
    import jax

    from reference.precision import operand, product

    dm = _dims_of(dims_key)
    q, out = operand(precision), product(precision)

    def layer(p, x):
        return _layer(p, x, dm, q, out)

    def layer_vjp(p, x, ct):
        _, vjp = jax.vjp(layer, p, x)
        return vjp(ct)                                   # (dp, dx)

    def close(tp, x, labels):
        return _close(tp, x, labels, dm, q, out)

    def close_vjp(tp, x, labels, cts):
        _, vjp = jax.vjp(lambda tp_, x_: close(tp_, x_, labels), tp, x)
        return vjp(cts)                                  # (d_tp, dx)

    def exit_part(g, nll, n_tokens):
        """This row's part of the loss, and its sums."""
        total, sums = _exit_loss(g, nll, np.float32(dm["beta"]))
        return total / n_tokens, sums

    return {"layer": jax.jit(layer), "layer_vjp": jax.jit(layer_vjp),
            "close": jax.jit(close), "close_vjp": jax.jit(close_vjp),
            "exit_grad": jax.jit(jax.value_and_grad(exit_part, (0, 1),
                                                    has_aux=True),
                                 static_argnums=2)}


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
    configuration's learning rate; a row and a layer application at a
    time, forward through the loop steps and back through them."""
    import jax
    import jax.numpy as jnp

    if chips != 1:
        raise ValueError("the reference follows a one-chip step")
    dm = dims(cfg)
    layers, loops = dm["layers"], dm["steps"]
    lr = float(cfg["hyper"]["lr"])
    batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    n_tokens = batch * t
    prog = _programs(_key_of(cfg), precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    result = {"loss": [], "loop": [], "grad_norm": {}, "delta_norm": {},
              "grad_first": {}}
    tail_groups = tuple(g for g in leaf_groups(cfg)
                        if g != "emb" and not g.startswith("B"))

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
            loss, sums = 0.0, np.zeros(loops + 2)
            for r in range(batch):
                lab = jnp.asarray(labels[r])
                # forward: ins[k][li] is this row's input to layer li in
                # loop step k, pre[k] what the final norm closes it from
                h, ins, pre, gs, nlls = params["emb"][jnp.asarray(
                    tokens[r])], [], [], [], []
                for _ in range(loops):
                    ins.append([])
                    for li in range(layers):
                        ins[-1].append(h)
                        h = prog["layer"](blocks[li], h)
                    pre.append(h)
                    h, g, nll = prog["close"](tp, h, lab)
                    gs.append(g)
                    nlls.append(nll)
                (part, row_sums), (dg, dnll) = prog["exit_grad"](
                    jnp.stack(gs), jnp.stack(nlls), n_tokens)
                loss += float(part)
                sums += np.concatenate([np.asarray(row_sums[0]),
                                        [float(row_sums[1]),
                                         float(row_sums[2])]])
                # backward: the last loop step's state feeds nothing on
                ct = jnp.zeros_like(h)
                for k in reversed(range(loops)):
                    g_tp, ct = prog["close_vjp"](tp, pre[k], lab,
                                                 (ct, dg[k], dnll[k]))
                    d_tp = accumulate(d_tp, g_tp)
                    for li in reversed(range(layers)):
                        dp, ct = prog["layer_vjp"](blocks[li], ins[k][li],
                                                   ct)
                        d_blocks[li] = accumulate(d_blocks[li], dp)
                    del g_tp, dp
                d_emb = d_emb.at[jnp.asarray(tokens[r])].add(ct)
                del ins, pre, ct
            result["loss"].append(loss)
            result["loop"].append({
                "loss_step": [float(v) / n_tokens for v in sums[:loops]],
                "exit_step_mean": float(sums[loops]) / n_tokens,
                "exit_entropy": float(sums[loops + 1]) / n_tokens})

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
            params["emb"] = step_leaf("emb", params["emb"], d_emb)
            del d_emb, d_tp, d_blocks, tp
        for group, path in leaf_groups(cfg).items():
            new = params[path[0]] if len(path) == 1 else blocks[path[1]]
            old = _flat(init_leaf_group(seed, cfg, group), group)
            for name, w in _flat(new, group).items():
                result["delta_norm"][name] = float(norm(w, old[name]))
            del old
    return result
