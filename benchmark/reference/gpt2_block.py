"""Plain reference of the ``cerebras_gpt_1.3b`` configuration: a GPT-2
shaped decoder (pre-LayerNorm blocks, causal multi-head attention scaled
by ``1/sqrt(head)``, a biased tanh-GELU MLP), its mean next-token
cross-entropy, gradients and plain SGD, in straightforward ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``.  No kernels, no
cache, no batching, nothing of the program imported.

Departures from the published model, the same as the configuration file
states under ``assumed`` and as the program's block has them: no position
embedding, no biases on the attention projections, no final LayerNorm, and
an output head that is not tied to the embedding.

It also owns the seeded weights and token rows.  One jitted call makes the
whole pytree on the device for the program; the reference makes the same
leaves again, block by block, so it never holds more than the program
does and takes nothing the program has made.

Training (``first_steps``): the first three steps' mean loss, each leaf's
first gradient as plain SGD applied it (``(w0 - w1) / lr``) and each leaf's
change after three steps.  The backward pass runs block by block and
updates each block in place, so the peak is the parameters, one activation
a block and one block's gradients.

Serving (``served_logits``): one full forward over prompt plus served
tokens, a block at a time over all sampled sequences, returning the logits
that predicted each served token.
"""

from __future__ import annotations

import functools

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cells' own
#: size (benchmark/limits.py; my chip runs, PR 23).  Training, bfloat16
#: program over 8 seeds against the fp8 control over 3: ``grad_norm_gap``
#: 0.0055-0.0130 against 0.080-0.101 (the number the control fails);
#: ``delta_norm_gap`` up to 0.0081 against 0.041-0.152 and ``loss_gap`` up to
#: 6.0e-4 against 3.1e-3-5.2e-3, both held at three times the sound runs'
#: largest against a step that returns its state unchanged and a part of
#: the batch left out.  Serving, over 7 seeds against the control over 3:
#: ``served_logit_gap`` (a widest gap over some 300 served tokens, in logit
#: deviations) 0-0.036 against 0.161-0.302; as a widest gap swings by its
#: nature the limit sits nearer the control, 2.8 x the sound runs' largest.
LIMITS = {
    "loss_gap": 0.0018,
    "grad_norm_gap": 0.035,
    "delta_norm_gap": 0.025,
    "served_logit_gap": 0.10,
}

_BLOCK_LEAVES = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b",
                 "w1", "b1", "w2", "b2")


def _dims(cfg: dict):
    return (int(cfg["n_embd"]), int(cfg["n_head"]), int(cfg["n_inner"]),
            int(cfg["vocab_size"]), int(cfg["n_layer"]))


def train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Matrix-unit operations one sequence of ``seq_len`` tokens needs,
    forward and backward (three passes, two operations a
    multiply-accumulate), causal attention counted at the half it needs;
    the embedding lookup is no product, and nothing recomputed counts."""
    d, _, ff, vocab, layers = _dims(cfg)
    per_token = 2.0 * (layers * (4 * d * d + 2 * d * ff) + d * vocab)
    attention = layers * 2.0 * seq_len * seq_len * d     # QK^T + PV, causal
    return 3.0 * (seq_len * per_token + attention)


# -- seeded weights and tokens -------------------------------------------------

def _root_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _block_leaves(key, d: int, ff: int):
    import jax
    import jax.numpy as jnp

    def w(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32) / np.float32(np.sqrt(shape[0]))

    return {"ln1_g": jnp.ones(d, jnp.float32),
            "ln1_b": jnp.zeros(d, jnp.float32),
            "wq": w(0, (d, d)), "wk": w(1, (d, d)), "wv": w(2, (d, d)),
            "wo": w(3, (d, d)),
            "ln2_g": jnp.ones(d, jnp.float32),
            "ln2_b": jnp.zeros(d, jnp.float32),
            "w1": w(4, (d, ff)), "b1": jnp.zeros(ff, jnp.float32),
            "w2": w(5, (ff, d)), "b2": jnp.zeros(d, jnp.float32)}


@functools.lru_cache(maxsize=None)
def _makers(d: int, ff: int, vocab: int, layers: int):
    import jax
    import jax.numpy as jnp

    def block(key, li):
        return _block_leaves(jax.random.fold_in(key, li + 1), d, ff)

    def emb(key):
        return jax.random.normal(jax.random.fold_in(key, 0x0E), (vocab, d),
                                 jnp.float32) * np.float32(0.02)

    def head(key):
        return jax.random.normal(jax.random.fold_in(key, 0x4D), (d, vocab),
                                 jnp.float32) / np.float32(np.sqrt(d))

    def whole(key):
        return {"emb": emb(key), "head": head(key),
                "blocks": [block(key, li) for li in range(layers)]}

    return {"block": jax.jit(block, static_argnums=1), "emb": jax.jit(emb),
            "head": jax.jit(head), "whole": jax.jit(whole)}


def _maker(cfg: dict):
    d, _, ff, vocab, layers = _dims(cfg)
    return _makers(d, ff, vocab, layers)


def init_params(seed: int, cfg: dict):
    """The whole float32 pytree (``emb``, ``head``, ``blocks``) on the
    default device, in one jitted call."""
    return _maker(cfg)["whole"](_root_key(seed))


def init_leaf_group(seed: int, cfg: dict, group):
    """``"emb"``, ``"head"`` or a block index -> that group's leaves,
    bit-identical with :func:`init_params`."""
    mk, key = _maker(cfg), _root_key(seed)
    if group in ("emb", "head"):
        return mk[group](key)
    return mk["block"](key, int(group))


def make_tokens(seed: int, cfg: dict, seq_len: int, start: int, stop: int):
    """Rows ``[start, stop)`` of the seeded token set, ``seq_len + 1`` ids
    each, uniform over the vocabulary; every row has a generator of its
    own.  Inputs are ``row[:-1]`` and labels ``row[1:]``."""
    vocab = int(cfg["vocab_size"])
    rows = [np.random.default_rng([int(seed), 0x70C, r]).integers(
        0, vocab, seq_len + 1).astype(np.int32) for r in range(start, stop)]
    return np.stack(rows)


# -- the block -----------------------------------------------------------------

def _layer_norm(x, g, b):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * g + b


def _block(p, x, heads: int, q, out):
    """One block on a batch of sequences ``x (b, t, d)``."""
    import jax
    import jax.numpy as jnp

    b, t, d = x.shape
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
    hq = q(h)
    qh = out(hq @ q(p["wq"])).reshape(b, t, heads, -1)
    kh = out(hq @ q(p["wk"])).reshape(b, t, heads, -1)
    vh = out(hq @ q(p["wv"])).reshape(b, t, heads, -1)
    s = out(jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh))) / np.float32(
        np.sqrt(d // heads))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = out(jnp.einsum("bhqk,bkhd->bqhd", q(a), q(vh))).reshape(b, t, d)
    x = x + out(q(o) @ q(p["wo"]))
    m = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    u = jax.nn.gelu(out(q(m) @ q(p["w1"])) + p["b1"], approximate=True)
    return x + out(q(u) @ q(p["w2"])) + p["b2"]


@functools.lru_cache(maxsize=None)
def _programs(heads: int, precision: str):
    import jax
    import jax.numpy as jnp

    from reference.precision import operand, product

    q, out = operand(precision), product(precision)

    def block(p, x):                                  # x (b, t, d)
        return _block(p, x, heads, q, out)

    def block_vjp(p, x, ct):
        _, vjp = jax.vjp(block, p, x)
        return vjp(ct)                                # (dp, dx)

    def head_loss(head, x, labels):                   # one row (t, d)
        logits = out(q(x) @ q(head))
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

    def head_logits(head, x):
        return q(x) @ q(head)

    return {"block": jax.jit(block), "block_vjp": jax.jit(block_vjp),
            "head_grad": jax.jit(jax.value_and_grad(head_loss, (0, 1))),
            "head_logits": jax.jit(head_logits)}


# -- training ------------------------------------------------------------------

def first_steps(seed: int, cfg: dict, traffic: dict, chips: int,
                precision: str = "f32", steps: int = 3) -> dict:
    """Follow the program's first ``steps`` steps on rows in storage
    order: ``minibatch_size`` sequences a step, plain SGD at the
    configuration's learning rate."""
    import jax
    import jax.numpy as jnp

    if chips != 1:
        raise ValueError("the reference follows a one-chip step")
    d, heads, _, _, layers = _dims(cfg)
    lr = float(cfg["hyper"]["lr"])
    batch, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    prog = _programs(heads, precision)
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    sgd = jax.jit(lambda w, g: w - np.float32(lr) * g)
    out = {"loss": [], "grad_norm": {}, "delta_norm": {}}

    with jax.default_matmul_precision("highest"):
        params = init_params(seed, cfg)
        blocks = params["blocks"]
        for s in range(steps):
            rows = make_tokens(seed, cfg, t, s * batch, (s + 1) * batch)
            tokens, labels = rows[:, :-1], rows[:, 1:]
            acts = [params["emb"][jnp.asarray(tokens)]]
            for li in range(layers):
                acts.append(prog["block"](blocks[li], acts[-1]))
            x = acts.pop()
            n_tok = batch * t
            total, d_head, d_x = 0.0, None, []
            for r in range(batch):
                loss, (gh, gx) = prog["head_grad"](
                    params["head"], x[r], jnp.asarray(labels[r]))
                total += float(loss)
                d_head = gh if d_head is None else d_head + gh
                d_x.append(gx)
            out["loss"].append(total / n_tok)
            ct = jnp.stack(d_x) / n_tok
            d_head = d_head / n_tok
            del x, d_x

            def step_leaf(name, w, g):
                new = sgd(w, g)
                if s == 0:
                    # the gradient as SGD applied it: (w0 - w1) / lr
                    out["grad_norm"][name] = float(norm(w, new)) / lr
                return new

            params["head"] = step_leaf("head", params["head"], d_head)
            del d_head
            for li in reversed(range(layers)):
                dp, ct = prog["block_vjp"](blocks[li], acts.pop(), ct)
                blocks[li] = {k: step_leaf(f"B{li}.{k}", blocks[li][k], dp[k])
                              for k in _BLOCK_LEAVES}
                del dp
            d_emb = jnp.zeros_like(params["emb"]).at[
                jnp.asarray(tokens)].add(ct)
            params["emb"] = step_leaf("emb", params["emb"], d_emb)
            del d_emb, ct
        for group in ("emb", "head"):
            out["delta_norm"][group] = float(norm(
                params[group], init_leaf_group(seed, cfg, group)))
        for li in range(layers):
            p0 = init_leaf_group(seed, cfg, li)
            for k in _BLOCK_LEAVES:
                out["delta_norm"][f"B{li}.{k}"] = float(
                    norm(blocks[li][k], p0[k]))
    return out


# -- serving -------------------------------------------------------------------

_PAD_TO = 128


def served_logits(seed: int, cfg: dict, sequences: list, precision: str = "f32"):
    """``sequences``: ``[(prompt ids, served ids)]``.  One forward over
    each ``prompt + served[:-1]``; returns, per sequence, the float32
    logits ``(len(served), vocab)`` that predicted each served token.
    A block's weights are made, used on every sequence and dropped, so
    the peak is one block and the activations."""
    import jax
    import jax.numpy as jnp

    _, heads, _, _, layers = _dims(cfg)
    prog = _programs(heads, precision)
    with jax.default_matmul_precision("highest"):
        emb = init_leaf_group(seed, cfg, "emb")
        xs = []
        for prompt, served in sequences:
            ids = np.concatenate([np.asarray(prompt, np.int32),
                                  np.asarray(served[:-1], np.int32)])
            padded = np.zeros(-(-ids.size // _PAD_TO) * _PAD_TO, np.int32)
            padded[:ids.size] = ids                   # causal: padding is inert
            xs.append(emb[jnp.asarray(padded)][None])
        del emb
        for li in range(layers):
            p = init_leaf_group(seed, cfg, li)
            xs = [prog["block"](p, x) for x in xs]
            del p
        head = init_leaf_group(seed, cfg, "head")
        out = []
        for (prompt, served), x in zip(sequences, xs):
            lo = len(prompt) - 1
            rows = x[0, lo:lo + len(served)]
            out.append(np.asarray(prog["head_logits"](head, rows)))
    return out


def logit_gaps(ref_logits: list, tokens: list) -> np.ndarray:
    """By how much each token's reference logit lies below the
    reference's best at its position, over all sequences, in units of
    that position's logit deviation (so the number means the same at any
    width or weight scale: 0 is the reference's own choice, and a token
    drawn blindly lies some four deviations down)."""
    gaps = []
    for logits, toks in zip(ref_logits, tokens):
        toks = np.asarray(toks, np.int64)
        gap = logits.max(axis=-1) - logits[np.arange(toks.size), toks]
        gaps.append(gap / logits.std(axis=-1))
    return np.concatenate(gaps)
