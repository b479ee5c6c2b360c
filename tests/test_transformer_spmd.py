"""Flagship sharded-transformer tests on the 8-device CPU mesh: the
dp x sp x tp train step runs and learns; the dp x pipe x expert step runs
and learns; both exercise every mesh axis the framework supports."""

import pytest

# full SPMD training runs on the virtual 8-device CPU mesh take
# minutes per file; tier-1 (-m 'not slow') must fit its 870 s
# budget, so these ride the registered slow lane
pytestmark = pytest.mark.slow

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.parallel.mesh import make_mesh
from znicz_tpu.parallel import transformer as tfm
from znicz_tpu.parallel.params import init_params
from znicz_tpu.parallel.pipeline import (
    init_moe_pipeline_params, make_pipeline_step)


def test_dp_sp_tp_train_step_learns(cpu_devices):
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    prng.seed_all(5)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 2, 32, 4, 64, 17
    params = init_params(gen, n_layers, d, heads, ff, vocab)
    step, _ = tfm.make_train_step(mesh, n_layers, d, heads, ff, vocab,
                                  lr=0.2)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, vocab, (4, 16)).astype(np.int32)
    # learnable synthetic rule: label = (token + 1) mod vocab
    labels = ((tokens + 1) % vocab).astype(np.int32)
    losses = []
    for _ in range(30):
        params, loss = step(params, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_dp_sp_tp_matches_tp1(cpu_devices):
    """The sharded step computes the same loss as a 1x1x1 mesh (same math,
    different partitioning)."""
    prng.seed_all(7)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 1, 16, 2, 32, 11
    params = init_params(gen, n_layers, d, heads, ff, vocab)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, vocab, (4, 8)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)

    losses = {}
    for name, axes in (("sharded", {"data": 2, "seq": 2, "model": 2}),
                       ("single", {"data": 1, "seq": 1, "model": 1})):
        step, _ = tfm.make_train_step(
            make_mesh(axes), n_layers, d, heads, ff, vocab, lr=0.1)
        p = {k: (v if not isinstance(v, list) else
                 [dict(b) for b in v]) for k, v in params.items()}
        _, loss = step(p, tokens, labels)
        losses[name] = float(loss)
    np.testing.assert_allclose(losses["sharded"], losses["single"],
                               rtol=2e-4)


def test_bf16_step_tracks_f32(cpu_devices):
    """Mixed precision (bf16 compute, f32 masters) trains the same
    function: per-step losses track the f32 oracle within bf16's ~3
    decimal digits, and params stay f32 throughout."""
    import jax
    import jax.numpy as jnp

    prng.seed_all(11)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 1, 16, 2, 32, 11
    params = init_params(gen, n_layers, d, heads, ff, vocab)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, vocab, (4, 8)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})

    losses = {}
    for name, cdt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        step, _ = tfm.make_train_step(mesh, n_layers, d, heads, ff, vocab,
                                      lr=0.1, compute_dtype=cdt)
        p = {k: (v if not isinstance(v, list) else
                 [dict(b) for b in v]) for k, v in params.items()}
        run = []
        for _ in range(5):
            p, loss = step(p, tokens, labels)
            run.append(float(loss))
        losses[name] = run
        assert all(leaf.dtype == jnp.float32
                   for leaf in jax.tree.leaves(p)), name
    np.testing.assert_allclose(losses["bf16"], losses["f32"], rtol=2e-2)


def test_dp_pp_ep_pipeline_step_learns(cpu_devices):
    mesh = make_mesh({"data": 2, "pipe": 2, "expert": 2})
    prng.seed_all(9)
    gen = prng.get()
    d, ff, n_experts = 16, 32, 4
    params = init_moe_pipeline_params(gen, n_stages=2, d=d, ff=ff,
                                      n_experts=n_experts)
    step, _ = make_pipeline_step(mesh, n_experts, lr=0.05)
    rng = np.random.default_rng(2)
    xs = rng.normal(size=(4, 8, d)).astype(np.float32)
    w_true = rng.normal(0, 0.3, (d, d)).astype(np.float32)
    ys = xs @ w_true + 0.5 * xs
    losses = []
    for _ in range(40):
        params, loss = step(params, xs, ys)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


def test_flash_step_matches_ring_composition(cpu_devices):
    """The full composition — Pallas flash attention inside the
    shard_map'd train step, through jit and AD — executes (interpret
    mode) and trains identically to the ring/XLA attention path.

    Runs on a SINGLETON mesh on purpose: the interpret path needs
    ``check_vma=False`` (a Pallas HLO-interpreter limitation), under
    which psum transposition gains extra reductions — harmless only when
    every axis has size 1.  Multi-device semantics of the step itself are
    covered by the ring-path tests; the flash kernel is per-shard-local
    math.  t=128 exercises exactly one q block; dh=128 passes the flash
    gate (guarded below against geometry drift silently degrading this
    to ring-vs-ring)."""
    from znicz_tpu.core.config import root

    from znicz_tpu.ops.pallas.attention import supported

    prng.seed_all(13)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 1, 256, 2, 64, 11
    assert supported(128, d // heads)
    params = init_params(gen, n_layers, d, heads, ff, vocab)
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, vocab, (4, 128)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1})

    losses = {}
    for name, flags in (("ring", {"flash_attention": False}),
                        ("flash", {"flash_attention": True,
                                   "pallas_interpret": True})):
        for key, val in flags.items():
            setattr(root.common.engine, key, val)
        try:
            step, _ = tfm.make_train_step(mesh, n_layers, d, heads, ff,
                                          vocab, lr=0.1)
            p = {k: (v if not isinstance(v, list) else
                     [dict(b) for b in v]) for k, v in params.items()}
            run = []
            for _ in range(3):
                p, loss = step(p, tokens, labels)
                run.append(float(loss))
            losses[name] = run
        finally:
            root.common.engine.flash_attention = True
            root.common.engine.pallas_interpret = False
    np.testing.assert_allclose(losses["flash"], losses["ring"],
                               rtol=1e-4, atol=1e-5)


def test_shard_update_transformer_matches_replicated(cpu_devices):
    """ZeRO-style update splitting on the transformer's replicated
    leaves trains identically to the plain update on a dp x sp x tp
    mesh."""
    prng.seed_all(19)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 2, 32, 4, 64, 17
    params = init_params(gen, n_layers, d, heads, ff, vocab)
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, vocab, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})

    losses = {}
    for mode in (False, True):
        step, _ = tfm.make_train_step(mesh, n_layers, d, heads, ff,
                                      vocab, lr=0.2, shard_update=mode)
        p = {k: (v if not isinstance(v, list) else
                 [dict(b) for b in v]) for k, v in params.items()}
        run = []
        for _ in range(6):
            p, loss = step(p, tokens, labels)
            run.append(float(loss))
        losses[mode] = run
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=1e-5, atol=1e-7)


def test_bf16_pipeline_step_tracks_f32(cpu_devices):
    """Mixed precision on the MoE pipeline step: bf16 losses track the
    f32 oracle, params stay f32."""
    import jax
    import jax.numpy as jnp

    prng.seed_all(25)
    gen = prng.get()
    d, ff, n_experts = 16, 32, 4
    params = init_moe_pipeline_params(gen, n_stages=2, d=d, ff=ff,
                                      n_experts=n_experts)
    mesh = make_mesh({"data": 2, "pipe": 2, "expert": 2})
    rng = np.random.default_rng(6)
    xs = rng.normal(size=(4, 8, d)).astype(np.float32)
    ys = xs * 0.5

    losses = {}
    for name, cdt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        step, _ = make_pipeline_step(mesh, n_experts, lr=0.05,
                                     compute_dtype=cdt)
        p = dict(params)
        run = []
        for _ in range(5):
            p, loss = step(p, xs, ys)
            run.append(float(loss))
        losses[name] = run
        assert all(leaf.dtype == jnp.float32
                   for leaf in jax.tree.leaves(p)), name
    np.testing.assert_allclose(losses["bf16"], losses["f32"], rtol=5e-2)


def _place_like(params, mesh, specs):
    """Sharded restore template: params' arrays device_put onto ``mesh``
    with ``specs``'s per-leaf PartitionSpecs (the shape both orbax
    roundtrip tests hand to load_pytree as ``like=``)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    flat_t, treedef = jax.tree.flatten(jax.tree.map(np.asarray, params))
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    return jax.tree.unflatten(treedef, [
        jax.device_put(leaf, NamedSharding(mesh, spec))
        for leaf, spec in zip(flat_t, flat_s)])


def test_orbax_checkpoint_roundtrip_across_meshes(tmp_path, cpu_devices):
    """Transformer params checkpoint via orbax and restore with sharding
    taken from the target tree: the template carries MESH_B shardings,
    so the restored leaves land distributed for the new mesh (not merely
    resharded by jit), and training continues with the same loss as on
    the original mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.parallel.checkpoint import load_pytree, save_pytree

    prng.seed_all(29)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 1, 32, 4, 64, 13
    p = init_params(gen, n_layers, d, heads, ff, vocab)
    rng = np.random.default_rng(8)
    tokens = rng.integers(0, vocab, (4, 8)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)

    mesh_a = make_mesh({"data": 2, "seq": 2, "model": 2})
    step_a, _ = tfm.make_train_step(mesh_a, n_layers, d, heads, ff, vocab,
                                    lr=0.1)
    for _ in range(3):
        p, _loss = step_a(p, tokens, labels)
    path = save_pytree(str(tmp_path / "ckpt"), p)

    # template placed on MESH_B with its param shardings — restore must
    # adopt them (the cross-mesh feature under test)
    mesh_b = make_mesh({"data": 4, "seq": 1, "model": 2})
    like = _place_like(p, mesh_b, tfm.param_specs(n_layers))
    restored = load_pytree(path, like=like)
    for a, b, want in zip(jax.tree.leaves(p), jax.tree.leaves(restored),
                          jax.tree.leaves(like)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert b.sharding == want.sharding    # mesh_b layout adopted

    # continue on mesh_b from the restored params; the loss must equal
    # continuing on the ORIGINAL mesh (same math, different layout)
    step_b, _ = tfm.make_train_step(mesh_b, n_layers, d, heads, ff, vocab,
                                    lr=0.1)
    _p2, loss_b = step_b(restored, tokens, labels)
    _p1, loss_ref = step_a(p, tokens, labels)
    np.testing.assert_allclose(float(loss_b), float(loss_ref), rtol=2e-4)


def test_donate_matches_baseline(cpu_devices):
    """donate=True (params buffers donated to the step) is a pure
    execution-strategy switch: losses and updated params must match the
    plain step bit-for-bit.

    NOTE: the CPU backend ignores donate_argnums, so the donate leg
    here pins only API/rebind safety; actual donation runs on the chip
    via bench_transformer (donate=True)."""
    import jax

    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    n_layers, d, heads, ff, vocab = 2, 32, 4, 64, 13
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, vocab, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)

    outs = {}
    for name, kw in (("plain", {}), ("donate", {"donate": True})):
        prng.seed_all(9)
        params = init_params(prng.get(), n_layers, d, heads, ff,
                             vocab)
        step, _ = tfm.make_train_step(mesh, n_layers, d, heads, ff,
                                      vocab, lr=0.2, **kw)
        for _ in range(3):
            params, loss = step(params, tokens, labels)  # rebinds: donation-safe
        outs[name] = (float(loss),
                      np.asarray(jax.device_get(
                          jax.tree.leaves(params)[0])))
    assert outs["donate"][0] == outs["plain"][0], outs["donate"][0]
    np.testing.assert_array_equal(outs["donate"][1], outs["plain"][1])


def test_chunked_ce_matches_dense(cpu_devices):
    """loss_chunks=k computes the same loss/updated params as the dense
    CE path up to summation order (the (tokens, vocab) logits are never
    materialized — docs/TUNING.md); covers unmasked AND masked variants,
    including a token count that does not divide the chunk count (the
    zero-weight padding tail), on the full dp x sp x tp mesh."""
    import jax

    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    n_layers, d, heads, ff, vocab = 2, 32, 4, 64, 13
    rng = np.random.default_rng(6)
    tokens = rng.integers(0, vocab, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)
    mask = np.array([True, True, True, False])

    for masked in (False, True):
        outs = {}
        for name, chunks in (("dense", None), ("chunk4", 4),
                             ("chunk3", 3)):   # 3 does not divide 16·2
            prng.seed_all(11)
            params = init_params(prng.get(), n_layers, d, heads, ff,
                                 vocab)
            step, _ = tfm.make_train_step(
                mesh, n_layers, d, heads, ff, vocab, lr=0.2,
                masked=masked, loss_chunks=chunks)
            args = (tokens, labels, mask) if masked else (tokens, labels)
            for _ in range(3):
                params, loss = step(params, *args)
            outs[name] = (float(loss), jax.device_get(
                jax.tree.leaves(params)))
        for name in ("chunk4", "chunk3"):
            np.testing.assert_allclose(outs[name][0], outs["dense"][0],
                                       rtol=1e-6, atol=1e-7)
            for a, b in zip(outs[name][1], outs["dense"][1]):
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)

    # eval path shares the implementation
    prng.seed_all(11)
    params = init_params(prng.get(), n_layers, d, heads, ff, vocab)
    ev_d = tfm.make_eval_loss(mesh, n_layers, d, heads, ff, vocab)
    ev_c = tfm.make_eval_loss(mesh, n_layers, d, heads, ff, vocab,
                              loss_chunks=4)
    np.testing.assert_allclose(float(ev_c(params, tokens, labels)),
                               float(ev_d(params, tokens, labels)),
                               rtol=1e-6, atol=1e-7)


def test_head_sharded_matches_replicated(cpu_devices):
    """Megatron parallel cross-entropy (vocab-sharded head,
    head_sharded=True) trains identically to the replicated-head step
    on the full dp2 x sp2 x tp2 mesh — the full-vocab logits row never
    exists on any device; composes with loss_chunks; masked and
    unmasked; eval path shares the implementation."""
    import jax

    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    n_layers, d, heads, ff, vocab = 2, 32, 4, 64, 16   # vocab % tp == 0
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, vocab, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)
    mask = np.array([True, True, False, False])

    for masked in (False, True):
        outs = {}
        for name, kw in (("repl", {}),
                         ("vshard", {"head_sharded": True}),
                         ("vshard_chunk", {"head_sharded": True,
                                           "loss_chunks": 4})):
            prng.seed_all(21)
            params = init_params(prng.get(), n_layers, d, heads, ff,
                                 vocab)
            step, _ = tfm.make_train_step(mesh, n_layers, d, heads, ff,
                                          vocab, lr=0.2, masked=masked,
                                          **kw)
            args = (tokens, labels, mask) if masked else (tokens, labels)
            for _ in range(3):
                params, loss = step(params, *args)
            outs[name] = (float(loss), jax.device_get(
                jax.tree.leaves(params)))
        for name in ("vshard", "vshard_chunk"):
            np.testing.assert_allclose(outs[name][0], outs["repl"][0],
                                       rtol=1e-5, atol=1e-6)
            for a, b in zip(outs[name][1], outs["repl"][1]):
                np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)

    prng.seed_all(21)
    params = init_params(prng.get(), n_layers, d, heads, ff, vocab)
    ev_r = tfm.make_eval_loss(mesh, n_layers, d, heads, ff, vocab)
    ev_v = tfm.make_eval_loss(mesh, n_layers, d, heads, ff, vocab,
                              head_sharded=True)
    np.testing.assert_allclose(float(ev_v(params, tokens, labels)),
                               float(ev_r(params, tokens, labels)),
                               rtol=1e-5, atol=1e-6)

    # indivisible vocab is refused loudly
    import pytest
    with pytest.raises(ValueError, match="divisible"):
        tfm.make_train_step(mesh, n_layers, d, heads, ff, 17,
                            head_sharded=True)


def test_orbax_roundtrip_head_sharded_to_replicated(tmp_path,
                                                    cpu_devices):
    """A checkpoint written from a VOCAB-SHARDED-head run restores into
    a replicated-head layout (and trains on, loss-equal): the elastic
    contract must hold across head layouts, not just mesh shapes —
    a tp-trained model must load on a single chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.parallel.checkpoint import load_pytree, save_pytree

    prng.seed_all(31)
    n_layers, d, heads, ff, vocab = 1, 32, 4, 64, 16
    p = init_params(prng.get(), n_layers, d, heads, ff, vocab)
    rng = np.random.default_rng(9)
    tokens = rng.integers(0, vocab, (4, 8)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)

    mesh_a = make_mesh({"data": 2, "seq": 2, "model": 2})
    step_a, _ = tfm.make_train_step(mesh_a, n_layers, d, heads, ff,
                                    vocab, lr=0.1, head_sharded=True)
    for _ in range(3):
        p, _loss = step_a(p, tokens, labels)
    path = save_pytree(str(tmp_path / "ckpt_vs"), p)

    mesh_b = make_mesh({"data": 2, "seq": 1, "model": 1})
    like = _place_like(p, mesh_b,
                       tfm.param_specs(n_layers, head_sharded=False))
    restored = load_pytree(path, like=like)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    step_b, _ = tfm.make_train_step(mesh_b, n_layers, d, heads, ff,
                                    vocab, lr=0.1, head_sharded=False)
    _p2, loss_b = step_b(restored, tokens, labels)
    _p1, loss_ref = step_a(p, tokens, labels)
    np.testing.assert_allclose(float(loss_b), float(loss_ref), rtol=2e-4)


def test_moe_ffn_transformer_tp_invariant_and_learns(cpu_devices):
    """n_experts swaps every block's dense FFN for the expert-parallel
    top-1 MoE FFN (experts sharded over the model axis).  The step must
    be tp-INVARIANT — identical losses with the 4 experts on one device
    vs split across model=2 — and must still learn the shift rule."""
    import jax

    n_layers, d, heads, ff, vocab, n_experts = 2, 32, 4, 64, 17, 4
    rng = np.random.default_rng(12)
    tokens = rng.integers(0, vocab, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)

    losses = {}
    for name, shape, aux_w in (
            ("tp1", {"data": 2, "seq": 2, "model": 1}, 0.0),
            ("tp2", {"data": 2, "seq": 2, "model": 2}, 0.0),
            ("tp1_aux", {"data": 2, "seq": 2, "model": 1}, 0.01),
            ("tp2_aux", {"data": 2, "seq": 2, "model": 2}, 0.01)):
        # the aux legs also carry the router z-loss so BOTH MoE
        # regularizers ride the tp-invariance pin
        mesh = make_mesh(shape)
        prng.seed_all(33)
        params = init_params(prng.get(), n_layers, d, heads, ff,
                             vocab, n_experts=n_experts)
        step, _ = tfm.make_train_step(mesh, n_layers, d, heads, ff,
                                      vocab, lr=0.2,
                                      n_experts=n_experts,
                                      moe_aux_weight=aux_w,
                                      moe_zloss_weight=aux_w / 10)
        run = []
        for _ in range(15):
            params, loss = step(params, tokens, labels)
            run.append(float(loss))
        losses[name] = run
    np.testing.assert_allclose(losses["tp2"], losses["tp1"],
                               rtol=2e-4, atol=2e-5)
    # the load-balance aux is tp-invariant too, and actually present
    np.testing.assert_allclose(losses["tp2_aux"], losses["tp1_aux"],
                               rtol=2e-4, atol=2e-5)
    assert abs(losses["tp1_aux"][0] - losses["tp1"][0]) > 1e-4
    assert losses["tp1"][-1] < losses["tp1"][0] * 0.6, losses["tp1"]
    assert losses["tp1_aux"][-1] < losses["tp1_aux"][0] * 0.6

    # indivisible expert count is refused loudly
    import pytest
    with pytest.raises(ValueError, match="n_experts"):
        tfm.make_train_step(make_mesh({"data": 2, "seq": 2, "model": 2}),
                            n_layers, d, heads, ff, vocab, n_experts=3)
