"""Token-dispatch (all_to_all) MoE tests: parallel/moe.py::
moe_ffn_dispatch — the token-sharded expert-parallel regime where each
token travels to its expert's device and back — against a dense
single-device oracle, values AND grads, plus the capacity-overflow drop
semantics.  Main-stack MoE (tokens replicated over model) is covered in
test_transformer_spmd.py."""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from znicz_tpu.parallel.mesh import make_mesh
from znicz_tpu.parallel.moe import moe_ffn_dispatch
from znicz_tpu.parallel.transformer import shard_map


def _setup(rng, n_dev, e_local, d, ff, t_total):
    E = n_dev * e_local
    return (jnp.asarray(rng.normal(size=(t_total, d)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(d, E)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(E, d, ff)).astype(np.float32)
                        * 0.3),
            jnp.asarray(rng.normal(size=(E, ff)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(E, ff, d)).astype(np.float32)
                        * 0.3),
            jnp.asarray(rng.normal(size=(E, d)).astype(np.float32)))


def _dense_oracle(x, gate, w1, b1, w2, b2):
    """Single-device top-1 MoE (jnp, differentiable): every token by its
    argmax expert, scaled by that expert's softmax prob."""
    s = x @ gate
    probs = jax.nn.softmax(s, axis=-1)
    choice = s.argmax(-1)
    gate_val = jnp.take_along_axis(probs, choice[:, None], 1)[:, 0]
    h = jax.nn.gelu(jnp.einsum("td,edf->etf", x, w1) + b1[:, None, :])
    y_e = jnp.einsum("etf,efd->etd", h, w2) + b2[:, None, :]
    sel = jax.nn.one_hot(choice, w1.shape[0], dtype=x.dtype).T
    return (y_e * sel[:, :, None]).sum(0) * gate_val[:, None]


def _sharded(mesh, capacity_factor):
    def local(x, gate, w1, b1, w2, b2):
        y, _ = moe_ffn_dispatch(x, gate, w1, b1, w2, b2, jax.nn.gelu,
                                axis_name="expert",
                                capacity_factor=capacity_factor)
        return y
    return shard_map(local, mesh=mesh,
                     in_specs=(P("expert"), P(), P("expert"),
                               P("expert"), P("expert"), P("expert")),
                     out_specs=P("expert"))


def test_dispatch_matches_dense_oracle_values_and_grads(cpu_devices):
    mesh = make_mesh({"expert": 4})
    n_dev, e_local, d, ff, t_total = 4, 2, 8, 16, 32
    rng = np.random.default_rng(3)
    x, gate, w1, b1, w2, b2 = _setup(rng, n_dev, e_local, d, ff, t_total)
    # capacity_factor = E: provably lossless (even if every local token
    # picks the same expert, the bucket holds them all)
    fn = _sharded(mesh, float(n_dev * e_local))

    y = fn(x, gate, w1, b1, w2, b2)
    y_ref = _dense_oracle(x, gate, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)

    wsum = jnp.asarray(rng.normal(size=y.shape).astype(np.float32))
    args = (x, gate, w1, b1, w2, b2)
    g = jax.grad(lambda *a: (fn(*a) * wsum).sum(),
                 argnums=tuple(range(6)))(*args)
    g_ref = jax.grad(lambda *a: (_dense_oracle(*a) * wsum).sum(),
                     argnums=tuple(range(6)))(*args)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_dispatch_capacity_drops_overflow_tokens(cpu_devices):
    """With capacity 1 slot per (expert, source), a second local token
    routed to the same expert contributes ZERO output (switch
    semantics), while first-arrival tokens match the oracle."""
    mesh = make_mesh({"expert": 2})
    n_dev, e_local, d, ff = 2, 1, 4, 8
    t_total = 8                                  # 4 per device
    rng = np.random.default_rng(5)
    x, gate, w1, b1, w2, b2 = _setup(rng, n_dev, e_local, d, ff, t_total)
    # capacity = ceil(0.5 * 4 / 2) = 1
    fn = _sharded(mesh, 0.5)
    y = np.asarray(fn(x, gate, w1, b1, w2, b2))
    y_ref = np.asarray(_dense_oracle(x, gate, w1, b1, w2, b2))

    choice = np.asarray(jnp.argmax(x @ gate, -1))
    seen = set()
    n_dropped = 0
    for t in range(t_total):
        dev, e = t // 4, int(choice[t])
        key = (dev, e)
        if key in seen:
            np.testing.assert_allclose(y[t], 0.0, atol=1e-6)
            n_dropped += 1
        else:
            np.testing.assert_allclose(y[t], y_ref[t], rtol=2e-5,
                                       atol=2e-5)
            seen.add(key)
    assert n_dropped > 0, "test vector never overflowed — regenerate"


def _dense_top2_oracle(x, gate, w1, b1, w2, b2):
    """Single-device GShard top-2 oracle (renormalized combine) shared
    by the dense-masked and dispatch top-2 parity tests."""
    E = w1.shape[0]
    s = x @ gate
    probs = jax.nn.softmax(s, axis=-1)
    _, idx = jax.lax.top_k(s, 2)                      # (t, 2)
    g2 = jnp.take_along_axis(probs, idx, 1)
    g2 = g2 / g2.sum(-1, keepdims=True)
    h = jax.nn.gelu(jnp.einsum("td,edf->etf", x, w1) + b1[:, None, :])
    y_e = jnp.einsum("etf,efd->etd", h, w2) + b2[:, None, :]
    out = 0.0
    for k in range(2):
        sel = jax.nn.one_hot(idx[:, k], E, dtype=x.dtype).T
        out = out + (y_e * sel[:, :, None]).sum(0) * g2[:, k:k + 1]
    return out


def test_dense_masked_top2_matches_oracle(cpu_devices):
    """moe_ffn top_k=2 (GShard renormalized combine) on the replicated-
    token regime matches a single-device oracle, values and grads, and
    is expert-shard invariant (same result with E experts on one device
    vs split over 4)."""
    d, ff, E, t_total = 8, 16, 4, 16
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(t_total, d)).astype(np.float32))
    gate = jnp.asarray(rng.normal(size=(d, E)).astype(np.float32))
    w1 = jnp.asarray(rng.normal(size=(E, d, ff)).astype(np.float32) * 0.3)
    b1 = jnp.asarray(rng.normal(size=(E, ff)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(E, ff, d)).astype(np.float32) * 0.3)
    b2 = jnp.asarray(rng.normal(size=(E, d)).astype(np.float32))
    oracle = _dense_top2_oracle

    from znicz_tpu.parallel.moe import moe_ffn

    outs = {}
    for name, n_dev in (("ep1", 1), ("ep4", 4)):
        mesh = make_mesh({"expert": n_dev})
        fn = shard_map(
            lambda x, gate, w1, b1, w2, b2: moe_ffn(
                x, gate, w1, b1, w2, b2, jax.nn.gelu,
                axis_name="expert", top_k=2)[0],
            mesh=mesh,
            in_specs=(P(), P(), P("expert"), P("expert"), P("expert"),
                      P("expert")),
            out_specs=P())
        outs[name] = fn(x, gate, w1, b1, w2, b2)
        g = jax.grad(lambda *a: (fn(*a) ** 2).sum(),
                     argnums=(0, 2))(x, gate, w1, b1, w2, b2)
        g_ref = jax.grad(lambda *a: (oracle(*a) ** 2).sum(),
                         argnums=(0, 2))(x, gate, w1, b1, w2, b2)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)
    y_ref = oracle(x, gate, w1, b1, w2, b2)
    for name in outs:
        np.testing.assert_allclose(np.asarray(outs[name]),
                                   np.asarray(y_ref), rtol=2e-5,
                                   atol=2e-5)


def test_dispatch_top2_matches_dense_top2_oracle(cpu_devices):
    """top_k=2 dispatch: each token occupies two bucket slots and the
    combine is GShard-renormalized — matches the dense top-2 oracle
    (values + grads) at lossless capacity."""
    mesh = make_mesh({"expert": 4})
    n_dev, e_local, d, ff, t_total = 4, 1, 8, 16, 32
    E = n_dev * e_local
    rng = np.random.default_rng(11)
    x, gate, w1, b1, w2, b2 = _setup(rng, n_dev, e_local, d, ff, t_total)

    def local(x, gate, w1, b1, w2, b2):
        y, _ = moe_ffn_dispatch(x, gate, w1, b1, w2, b2, jax.nn.gelu,
                                axis_name="expert",
                                capacity_factor=float(E), top_k=2)
        return y
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P("expert"), P(), P("expert"), P("expert"),
                             P("expert"), P("expert")),
                   out_specs=P("expert"))

    oracle = _dense_top2_oracle

    y = fn(x, gate, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(oracle(x, gate, w1, b1, w2,
                                                 b2)),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda *a: (fn(*a) ** 2).sum(),
                 argnums=(0, 1, 2))(x, gate, w1, b1, w2, b2)
    g_ref = jax.grad(lambda *a: (oracle(*a) ** 2).sum(),
                     argnums=(0, 1, 2))(x, gate, w1, b1, w2, b2)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_router_z_loss_value_and_presence(cpu_devices):
    """router_z_loss pins to a hand-computed mean(logsumexp^2), and a
    z-loss-ONLY training config (aux weight 0) changes the transformer
    loss — so the regularizer cannot silently become a no-op while the
    balance aux masks it."""
    from znicz_tpu.parallel.moe import router_z_loss
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.core import prng

    rng = np.random.default_rng(3)
    s = rng.normal(size=(5, 7)).astype(np.float32)
    want = float(np.mean(
        np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) ** 2
        + 2 * s.max(-1) * np.log(np.exp(s - s.max(-1, keepdims=True))
                                 .sum(-1))
        + s.max(-1) ** 2))
    got = float(router_z_loss(jnp.asarray(s)))
    np.testing.assert_allclose(got, want, rtol=1e-5)

    tokens = rng.integers(0, 16, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % 16).astype(np.int32)
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    losses = {}
    for name, zw in (("off", 0.0), ("on", 0.01)):
        prng.seed_all(21)
        params = init_params(prng.get(), 2, 32, 4, 64, 16,
                             n_experts=4)
        step, _ = tfm.make_train_step(mesh, 2, 32, 4, 64, 16, lr=0.2,
                                      n_experts=4, moe_zloss_weight=zw)
        _, loss = step(params, tokens, labels)
        losses[name] = float(loss)
    assert abs(losses["on"] - losses["off"]) > 1e-4, losses
