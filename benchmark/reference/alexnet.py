"""Plain reference of the ``alexnet`` configuration: the single-tower
AlexNet/CaffeNet forward pass, its summed cross-entropy loss, gradients by
``jax.grad`` and the reference framework's SGD rule, in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.
No kernels, no mesh, nothing of the program imported.

It also owns what both sides are made from: the seeded weights, the
seeded images and the dropout key.  The builder hands those to the
program; the reference makes them again from the seed, so it takes nothing
the program has made.

What is compared (see ``benchlib.compare_train_readings``): the mean loss
of the first three steps, the norm of the first gradient as the optimizer
got it, leaf by leaf, and the norm of each leaf's change after three steps.

Dropout: the program splits its step key on the device
(``key, sub = split(key)``), folds the data-shard index into ``sub`` and
then the dropout unit's index in the layer list, and keeps ``u >= ratio``
scaled by ``1 / (1 - ratio)``.  The reference draws the same masks from the
same key, one shard of the batch at a time.
"""

from __future__ import annotations

import numpy as np

#: limit of each number compared.  Readings on the v5e at the cell's own
#: size (benchmark/limits.py; my chip runs, PR 23): the bfloat16 program
#: against this reference over 35 seeds, and the fp8 control over 3.
#: ``grad_diff_gap`` is the number the lower precision fails: sound
#: 0.246-0.260, control 0.760-0.766.  The three norm gaps do not separate
#: the two here (rounding errors cancel in a norm: sound up to 0.033 and
#: 0.047, control 0.024-0.051): they are held against the faults they can
#: catch (a part of the batch or the exchange between chips left out, a
#: step that returns its state unchanged: gaps of 0.5 to 1) at about
#: three times the sound runs' largest (4.9e-4, 0.033, 0.047).
LIMITS = {
    "loss_gap": 0.0015,
    "grad_norm_gap": 0.12,
    "delta_norm_gap": 0.15,
    "grad_diff_gap": 0.45,
}

#: index of every layer in the workflow's layer list that has parameters
#: (conv 0 3 6 7 8, fully connected 11 13, softmax 14) and of the two
#: dropout layers (10, 12): the program folds these indices into its keys
PARAM_LAYERS = (0, 3, 6, 7, 8, 11, 13, 14)
DROPOUT_LAYERS = (10, 12)
_CONVS = ((96, 11, 4, 0), (256, 5, 1, 2), (384, 3, 1, 1), (384, 3, 1, 1),
          (256, 3, 1, 1))        # kernels, size, stride, padding
_LRN = dict(alpha=1e-4, beta=0.75, k=2.0, n=5)


def _geometry(cfg: dict):
    """[(kind, w shape, fan_in)] of the eight parameter layers and the
    spatial size after each convolution (for the FLOP count)."""
    size, c_in = int(cfg["input_size"]), 3
    shapes, positions = [], []
    for i, (n, k, s, p) in enumerate(_CONVS):
        size = (size + 2 * p - k) // s + 1
        shapes.append(("conv", (k, k, c_in, n), k * k * c_in))
        positions.append(size * size)
        c_in = n
        if i in (0, 1, 4):
            size = (size - 3) // 2 + 1           # max pool 3x3 stride 2
    flat = size * size * c_in
    for n_out in (4096, 4096, int(cfg["n_classes"])):
        shapes.append(("fc", (flat, n_out), flat))
        flat = n_out
    return shapes, positions


def train_flops_per_sample(cfg: dict) -> float:
    """Matrix-unit operations one image needs, forward and backward: two
    per multiply-accumulate, three passes (forward, input gradient, weight
    gradient).  Elementwise work is left out, and nothing is recomputed."""
    shapes, positions = _geometry(cfg)
    fwd = 0.0
    for i, (kind, shape, fan_in) in enumerate(shapes):
        n_out = shape[-1]
        fwd += 2.0 * fan_in * n_out * (positions[i] if kind == "conv" else 1)
    return 3.0 * fwd


def make_weights(seed: int, cfg: dict) -> list[dict]:
    """The eight layers' ``{"w", "b"}`` as float32 numpy: weights normal
    with deviation ``1/sqrt(fan_in)``, biases normal with 0.01, which are
    the workflow's own defaults."""
    rng = np.random.default_rng([int(seed), 0xA1E])
    out = []
    for _, shape, fan_in in _geometry(cfg)[0]:
        w = rng.standard_normal(shape, dtype=np.float32) / np.float32(
            np.sqrt(fan_in))
        b = rng.standard_normal(shape[-1], dtype=np.float32) * \
            np.float32(0.01)
        out.append({"w": w, "b": b})
    return out


_ROW_CHUNK = 256


def make_rows(seed: int, cfg: dict, start: int, stop: int):
    """Images ``[start, stop)`` of the seeded set and their labels: a
    coarse pattern per label class (a quarter of the resolution, blown
    up), plus noise of deviation 0.5.  Every chunk of 256 rows has a
    generator of its own, so any slice can be made without the rest."""
    size, n_lab = int(cfg["input_size"]), int(cfg["n_label_classes"])
    rng = np.random.default_rng([int(seed), 0x1A6E])
    coarse_n = -(-size // 4)
    coarse = rng.standard_normal((n_lab, coarse_n, coarse_n, 3),
                                 dtype=np.float32)
    means = np.repeat(np.repeat(coarse, 4, axis=1), 4, axis=2)[
        :, :size, :size, :]
    images, labels = [], []
    for chunk in range(start // _ROW_CHUNK, -(-stop // _ROW_CHUNK)):
        crng = np.random.default_rng([int(seed), 0xDA7A, chunk])
        lab = crng.integers(0, n_lab, _ROW_CHUNK).astype(np.int32)
        img = crng.standard_normal((_ROW_CHUNK, size, size, 3),
                                   dtype=np.float32)
        img *= np.float32(0.5)
        img += means[lab]
        lo = max(start - chunk * _ROW_CHUNK, 0)
        hi = min(stop - chunk * _ROW_CHUNK, _ROW_CHUNK)
        images.append(img[lo:hi])
        labels.append(lab[lo:hi])
    return np.concatenate(images), np.concatenate(labels)


def dropout_key(seed: int):
    """The step key both sides start from (old-style uint32[2])."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def _forward_loss(params, x, labels, rng, cfg, q, out):
    """Summed cross-entropy of one shard of the batch."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ratio = float(cfg["dropout_ratio"])

    def lrn(v):
        half = _LRN["n"] // 2
        sq = jnp.pad(v * v, ((0, 0),) * 3 + ((half, half),))
        c = v.shape[-1]
        acc = sum(sq[..., i:i + c] for i in range(_LRN["n"]))
        return v * (_LRN["k"] + _LRN["alpha"] * acc) ** (-_LRN["beta"])

    def pool(v):
        return lax.reduce_window(v, -jnp.inf, lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "VALID")

    def drop(v, unit_index):
        if ratio == 0.0:
            return v
        u = jax.random.uniform(jax.random.fold_in(rng, unit_index), v.shape)
        return v * ((u >= ratio).astype(v.dtype) / (1.0 - ratio))

    for i, (_, _, stride, pad) in enumerate(_CONVS):
        p = params[i]
        x = out(lax.conv_general_dilated(
            q(x), q(p["w"]), (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))) + p["b"]
        x = jnp.maximum(x, 0.0)
        if i in (0, 1):
            x = lrn(x)
        if i in (0, 1, 4):
            x = pool(x)
    x = x.reshape(x.shape[0], -1)
    for j, unit_index in enumerate(DROPOUT_LAYERS):
        p = params[5 + j]
        x = jnp.maximum(out(q(drop(x, unit_index)) @ q(p["w"])) + p["b"],
                        0.0)
    logits = out(q(x) @ q(params[7]["w"])) + params[7]["b"]
    logp = jax.nn.log_softmax(logits, axis=1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).sum()


def first_steps(seed: int, cfg: dict, traffic: dict, chips: int,
                precision: str = "f32", steps: int = 3) -> dict:
    """Follow the program's first ``steps`` steps: rows in storage order
    (the traffic file turns the loader's shuffle off), ``chips`` shards of
    ``minibatch_size`` rows a step, momentum SGD with weight decay."""
    import jax
    import jax.numpy as jnp

    from reference.precision import operand, product

    q, out = operand(precision), product(precision)
    hyper = cfg["hyper"]
    lr, mom = float(hyper["lr"]), float(hyper["momentum"])
    wd = {"w": float(hyper["weights_decay"]),
          "b": float(hyper["weights_decay_bias"])}
    per_chip = int(cfg["minibatch_size"])
    batch = per_chip * chips

    grad_fn = jax.jit(jax.value_and_grad(
        lambda ps, x, y, rng: _forward_loss(ps, x, y, rng, cfg, q, out)))

    @jax.jit
    def apply(params, vel, grads):
        new_p, new_v = [], []
        for p, v, g in zip(params, vel, grads):
            nv = {k: mom * v[k] + lr * (g[k] / batch + wd[k] * p[k])
                  for k in p}
            new_v.append(nv)
            new_p.append({k: p[k] - nv[k] for k in p})
        return new_p, new_v

    def norms(tree_fn):
        return {f"L{i}.{k}": float(jnp.sqrt(jnp.sum(jnp.square(
            tree_fn(j, k))))) for j, i in enumerate(PARAM_LAYERS)
            for k in ("w", "b")}

    with jax.default_matmul_precision("highest"):
        params0 = jax.tree.map(jnp.asarray, make_weights(seed, cfg))
        params = params0
        vel = jax.tree.map(jnp.zeros_like, params)
        key = dropout_key(seed)
        images, labels = make_rows(seed, cfg, 0, steps * batch)
        readings = {"loss": []}
        for s in range(steps):
            key, sub = jax.random.split(key)
            total, grads = 0.0, None
            for shard in range(chips):
                lo = s * batch + shard * per_chip
                loss, g = grad_fn(params, jnp.asarray(images[lo:lo + per_chip]),
                                  jnp.asarray(labels[lo:lo + per_chip]),
                                  jax.random.fold_in(sub, shard))
                total += float(loss)
                grads = g if grads is None else jax.tree.map(jnp.add,
                                                             grads, g)
            readings["loss"].append(total / batch)
            params, vel = apply(params, vel, grads)
            if s == 0:
                # the gradient as the optimizer got it, from its state:
                # v1 = lr * (g / batch + wd * w0)
                def first(j, k):
                    return vel[j][k] / lr - wd[k] * params0[j][k]

                readings["grad_norm"] = norms(first)
                # the gradient itself, on the host: 244 MB at full size
                readings["grad_first"] = {
                    f"L{i}.{k}": np.asarray(first(j, k))
                    for j, i in enumerate(PARAM_LAYERS) for k in ("w", "b")}
        readings["delta_norm"] = norms(lambda j, k: params[j][k] - params0[j][k])
    return readings
