"""Tier-3 tests: the fused/sharded training step (SURVEY.md §5 rebuild
translation — multi-device SPMD on the virtual 8-device CPU mesh).

- fused-vs-eager parity: one fused step produces the same weight update as
  the per-unit eager chain (autograd-composed backward == hand-written
  unit backward, through the full segment);
- mesh invariance: training on an 8-device mesh matches 1-device within
  float tolerance (psum math), and converges;
- determinism on the mesh.
"""

import numpy as np
import pytest

import jax

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.models.mnist_fc import build_eager, build_fused
from znicz_tpu.parallel.mesh import data_parallel_mesh, make_mesh


def test_fused_step_matches_eager_units():
    """Same seed => same data, same init; run exactly one TRAIN minibatch
    through both shapes and compare the updated weights."""
    # eager: skip valid passes by using a train-only loader
    prng.seed_all(77)
    we = build_eager(max_epochs=1, n_valid=0, n_train=200, minibatch_size=50)
    we.initialize(device=NumpyDevice())
    we.loader.run()
    for f in we.forwards:
        f.run()
    we.evaluator.run()
    for gd in reversed(we.gds):
        gd.run()

    prng.seed_all(77)
    wf = build_fused(max_epochs=1, n_valid=0, n_train=200, minibatch_size=50)
    wf.initialize(device=XLADevice())
    wf.loader.run()
    wf.step.run()
    wf.step.sync_to_units()

    for i, (fe, ff) in enumerate(zip(we.forwards, wf.forwards)):
        np.testing.assert_allclose(
            ff.weights.map_read(), fe.weights.map_read(),
            rtol=1e-4, atol=1e-5, err_msg=f"layer {i} weights")
        np.testing.assert_allclose(
            ff.bias.map_read(), fe.bias.map_read(),
            rtol=1e-4, atol=1e-5, err_msg=f"layer {i} bias")
    # velocity buffers too (momentum state)
    for i, (ge, gf) in enumerate(zip(we.gds, wf.gds)):
        np.testing.assert_allclose(
            gf.gradient_weights.map_read(), ge.gradient_weights.map_read(),
            rtol=1e-4, atol=1e-5, err_msg=f"layer {i} velocity")


def run_fused(seed, mesh, max_epochs=3):
    prng.seed_all(seed)
    w = build_fused(max_epochs=max_epochs, mesh=mesh)
    w.initialize(device=XLADevice())
    w.run()
    w.step.sync_to_units()
    return w


def test_fused_training_converges_on_8dev_mesh(cpu_devices):
    mesh = data_parallel_mesh(8)
    w = run_fused(31, mesh)
    hist = w.decision.metrics_history
    assert len(hist) == 3
    assert hist[-1]["metric_validation"] < hist[0]["metric_validation"]
    assert w.decision.epoch_n_err_pt[1] < 15.0, hist


def test_mesh_size_invariance(cpu_devices):
    """DP over 8 devices is the same math as 1 device (sync SPMD: batch
    split + psum == full-batch gradient), modulo float reduction order."""
    w1 = run_fused(13, data_parallel_mesh(1), max_epochs=2)
    w8 = run_fused(13, data_parallel_mesh(8), max_epochs=2)
    np.testing.assert_allclose(
        w8.forwards[0].weights.map_read(), w1.forwards[0].weights.map_read(),
        rtol=1e-3, atol=1e-4)
    assert [h["metric_validation"] for h in w1.decision.metrics_history] == \
        [h["metric_validation"] for h in w8.decision.metrics_history]


def test_fused_deterministic_on_mesh(cpu_devices):
    w_a = run_fused(17, data_parallel_mesh(8), max_epochs=2)
    w_b = run_fused(17, data_parallel_mesh(8), max_epochs=2)
    np.testing.assert_array_equal(w_a.forwards[0].weights.map_read(),
                                  w_b.forwards[0].weights.map_read())
    assert w_a.decision.metrics_history == w_b.decision.metrics_history


def test_make_mesh_axes(cpu_devices):
    mesh = make_mesh({"data": 4, "model": 2})
    assert mesh.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        make_mesh({"data": 16})


def test_train_steps_scan_matches_sequential(cpu_devices):
    """The K-step scan (the bench's measurement path) is the SAME program
    as K sequential per-minibatch steps: identical final params and summed
    metrics; and the device hyper cache invalidates on an LR change."""
    import jax.numpy as jnp

    mesh = data_parallel_mesh(4)

    def fresh():
        prng.seed_all(23)
        w = build_fused(max_epochs=1, n_valid=0, n_train=240,
                        minibatch_size=40, mesh=mesh)
        w.initialize(device=XLADevice())
        return w

    rng = np.random.default_rng(3)
    K = 5
    xs = rng.normal(size=(K, 40, 28, 28)).astype(np.float32)
    ys = rng.integers(0, 10, (K, 40)).astype(np.int32)
    ms = np.ones((K, 40), bool)

    w_seq = fresh()
    seq_sums = None
    for k in range(K):
        w_seq.step._params, w_seq.step._key, metrics = w_seq.step._train_fn(
            w_seq.step._params, w_seq.step._key,
            w_seq.step._hyper_device(), xs[k], ys[k], ms[k])
        host = jax.device_get(metrics)
        seq_sums = host if seq_sums is None else \
            jax.tree.map(np.add, seq_sums, host)

    w_scan = fresh()
    scan_sums = jax.device_get(w_scan.step.train_steps(
        jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(ms)))

    for leaf_seq, leaf_scan in zip(jax.tree.leaves(w_seq.step._params),
                                   jax.tree.leaves(w_scan.step._params)):
        np.testing.assert_allclose(np.asarray(leaf_seq),
                                   np.asarray(leaf_scan),
                                   rtol=1e-5, atol=1e-6)
    assert int(seq_sums["n_err"]) == int(scan_sums["n_err"])
    np.testing.assert_allclose(float(seq_sums["loss"]),
                               float(scan_sums["loss"]), rtol=1e-5)
    assert int(seq_sums["bs"]) == int(scan_sums["bs"]) == K * 40

    # hyper cache: an LR change must produce a DIFFERENT device pytree
    h0 = w_scan.step._hyper_device()
    for gd in w_scan.gds:
        gd.learning_rate *= 0.5
    h1 = w_scan.step._hyper_device()
    assert float(jax.device_get(h1[0]["lr"])) == \
        0.5 * float(jax.device_get(h0[0]["lr"]))


def test_scan_epoch_mode_matches_per_minibatch(cpu_devices):
    """root.common.engine.scan_epoch dispatches one compiled scan per
    class pass; Decision history and final weights must match the
    per-minibatch path (same key chain, same math, one dispatch)."""
    from znicz_tpu.core.config import root

    def run(scan):
        root.common.engine.scan_epoch = scan
        try:
            w = run_fused(41, data_parallel_mesh(4), max_epochs=3)
        finally:
            root.common.engine.scan_epoch = False
        return w

    base = run(False)
    scan = run(True)
    assert scan.step.scan_epoch and scan.step._scan_idx_fns
    assert [h["metric_validation"] for h in base.decision.metrics_history] \
        == [h["metric_validation"] for h in scan.decision.metrics_history]
    assert [h["metric_train"] for h in base.decision.metrics_history] \
        == [h["metric_train"] for h in scan.decision.metrics_history]
    np.testing.assert_allclose(scan.forwards[0].weights.map_read(),
                               base.forwards[0].weights.map_read(),
                               rtol=1e-4, atol=1e-5)


def test_scan_epoch_refuses_per_minibatch_lr_schedule(cpu_devices):
    """VERDICT r5 item 6: scan_epoch reads hyperparams once per class
    pass, so a linked per-minibatch (by_epoch=False) LearningRateAdjust
    would silently coarsen to a per-pass schedule — initialize must
    refuse with a diagnostic naming the offending unit.  The per-epoch
    variant stays allowed."""
    from znicz_tpu.core.config import root
    from znicz_tpu.units.lr_adjust import ExpPolicy, LearningRateAdjust

    def build(by_epoch):
        prng.seed_all(11)
        w = build_fused(max_epochs=1, mesh=data_parallel_mesh(2))
        adj = LearningRateAdjust(w, lr_policy=ExpPolicy(0.9),
                                 by_epoch=by_epoch)
        for gd in w.gds:
            adj.add_gd_unit(gd)
        adj.link_from(w.decision)
        if by_epoch:
            adj.decision = w.decision
        return w

    root.common.engine.scan_epoch = True
    try:
        w = build(by_epoch=False)
        with pytest.raises(ValueError, match="by_epoch=False.*coarsen"):
            w.initialize(device=XLADevice())
        # by_epoch=True is pass-granular already: must initialize fine
        w_ok = build(by_epoch=True)
        w_ok.initialize(device=XLADevice())
        assert w_ok.step._scan_idx_fns
    finally:
        root.common.engine.scan_epoch = False


def test_scan_epoch_single_minibatch_classes(cpu_devices):
    """Regression: when a class pass fits in ONE minibatch, the loader
    has already advanced to the next class (and possibly reshuffled) by
    the time the step dispatches — the plan must be the one captured at
    class start, not the next class's indices."""
    from znicz_tpu.core.config import root

    def run(scan):
        prng.seed_all(19)
        root.common.engine.scan_epoch = scan
        try:
            # valid (80) and train (160) each fit in one 160-row minibatch
            w = build_fused(max_epochs=3, n_train=160, n_valid=80,
                            minibatch_size=160,
                            mesh=data_parallel_mesh(4))
            w.initialize(device=XLADevice())
            w.run()
            w.step.sync_to_units()
        finally:
            root.common.engine.scan_epoch = False
        return w

    base = run(False)
    scan = run(True)
    assert [h["metric_validation"] for h in base.decision.metrics_history] \
        == [h["metric_validation"] for h in scan.decision.metrics_history]
    np.testing.assert_allclose(scan.forwards[0].weights.map_read(),
                               base.forwards[0].weights.map_read(),
                               rtol=1e-4, atol=1e-5)


def test_scan_epoch_midpass_entry_falls_back(cpu_devices):
    """A class pass entered mid-way (restored loader state) must fall
    back to the per-minibatch path for the remainder instead of skipping
    the pass and publishing a None accumulator."""
    from znicz_tpu.core.config import root

    prng.seed_all(27)
    root.common.engine.scan_epoch = True
    try:
        w = build_fused(max_epochs=1, n_train=200, n_valid=0,
                        minibatch_size=40, mesh=data_parallel_mesh(4))
        w.initialize(device=XLADevice())
    finally:
        root.common.engine.scan_epoch = False
    loader, step = w.loader, w.step
    # simulate a mid-pass restore: advance the loader two minibatches
    # without the step seeing them, then clear any device accumulator
    loader.run()
    loader.run()
    loader.run()
    assert int(loader.minibatch_offset) > 0
    step._acc = None
    before = np.asarray(jax.tree.leaves(step._params)[0])
    while True:                            # remaining minibatches of pass
        step.run()
        if loader.last_minibatch:
            break
        loader.run()
    # the WHOLE remainder trained (3 of 5 minibatches = 120 samples),
    # not just the first fallback minibatch (regression: _acc was
    # misused as the scan-in-flight marker and re-routed minibatch 2+
    # back into the no-op scan path)
    after = np.asarray(jax.tree.leaves(step._params)[0])
    assert not np.array_equal(before, after)
    assert step.minibatch_size == 120, step.minibatch_size
    assert step.loss > 0.0


def test_scan_epoch_mse_workflow(cpu_devices):
    """Epoch-scan parity for the MSE/regression path (targets pinned on
    device instead of labels)."""
    from znicz_tpu.core.config import root
    from znicz_tpu.models import autoencoder

    def run(scan):
        prng.seed_all(9)
        root.common.engine.scan_epoch = scan
        try:
            w = autoencoder.build(max_epochs=3, n_train=200, n_valid=64,
                                  minibatch_size=40, sample_shape=(12, 12, 1),
                                  mesh=data_parallel_mesh(4))
            w.initialize(device=XLADevice())
            w.run()
        finally:
            root.common.engine.scan_epoch = False
        return [h["metric_validation"] for h in w.decision.metrics_history]

    base = run(False)
    scan = run(True)
    np.testing.assert_allclose(scan, base, rtol=1e-5)


def test_lr_schedule_no_recompile(cpu_devices):
    """Hyperparams are traced scalars: mutating gd.learning_rate between
    steps must not retrigger compilation."""
    prng.seed_all(5)
    w = build_fused(max_epochs=1, mesh=data_parallel_mesh(8))
    w.initialize(device=XLADevice())
    w.loader.run()
    while int(w.loader.minibatch_class) != 2:
        w.loader.run()
    w.step.run()
    compiled = w.step._train_fn._cache_size()
    for gd in w.gds:
        gd.learning_rate *= 0.5
    w.loader.run()
    w.step.run()
    assert w.step._train_fn._cache_size() == compiled


def test_fused_step_bf16_compute_tracks_f32():
    """Force the bf16 compute path (dead on CPU by default) through a
    whole training run: losses track the f32 run loosely, params stay
    f32, and every unit's xla_apply survives bf16 inputs.  Uses a conv
    stack so conv/pool/LRN/dropout all see bf16."""
    import jax.numpy as jnp
    from znicz_tpu.models.mnist_conv import build

    losses = {}
    for name, cdt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        prng.seed_all(123)
        w = build(max_epochs=2, minibatch_size=50, n_train=200, n_valid=50,
                  loader_name="synthetic_image")
        w.step.compute_dtype = cdt
        w.initialize(device=XLADevice())
        w.run()
        losses[name] = [h["metric_train"] for h in
                        w.decision.metrics_history]
        for leaf in jax.tree.leaves(w.step._params):
            assert leaf.dtype == jnp.float32, (name, leaf.dtype)
    assert len(losses["bf16"]) == len(losses["f32"])
    # bf16 rounding makes trajectories diverge step by step; the run must
    # still LEARN the same problem: final-epoch train errors in the same
    # ballpark as the f32 oracle (identical data + init)
    f32_final = losses["f32"][-1]
    bf16_final = losses["bf16"][-1]
    assert bf16_final <= max(1.5 * f32_final, f32_final + 10), losses


def test_hybrid_mesh_single_slice_fallback(cpu_devices):
    """make_hybrid_mesh: same axis names/sizes as the plain mesh on a
    single-slice platform (identical sharded program, only physical
    routing differs on real pods), with the dcn validation enforced."""
    import pytest

    from znicz_tpu.parallel.mesh import make_hybrid_mesh

    mesh = make_hybrid_mesh({"data": 2, "model": 4}, {"data": 2})
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (2, 4)

    # a collective over both axes executes on the hybrid-constructed mesh
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map

    def f(x):
        return jax.lax.psum(x, ("data", "model"))

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data", "model"),
                            out_specs=P()))(jnp.ones((2, 4)))
    assert float(out.ravel()[0]) == 8.0   # (1,1) replicated block

    with pytest.raises(ValueError, match="must divide"):
        make_hybrid_mesh({"data": 3}, {"data": 2})
    with pytest.raises(ValueError, match="not in axis_sizes"):
        make_hybrid_mesh({"data": 8}, {"pipe": 2})


def test_hybrid_mesh_multi_slice_assignment(cpu_devices):
    """Simulated multi-slice runtime (fake slice_index wrappers): the
    dcn axis spans slices outermost, surplus slices/devices are trimmed
    like the single-slice path, and dcn=1 stays inside one slice."""
    import pytest

    from znicz_tpu.parallel.mesh import make_hybrid_mesh

    class Dev:
        def __init__(self, d, sid):
            self._d = d
            self.slice_index = sid

        def __getattr__(self, name):
            return getattr(self._d, name)

        def __repr__(self):
            return f"<s{self.slice_index}:{self._d.id}>"

    devs = [Dev(d, i // 4) for i, d in enumerate(cpu_devices)]  # 2 slices

    mesh = make_hybrid_mesh({"data": 2, "model": 2}, {"data": 2},
                            devices=devs)
    assert mesh.devices.shape == (2, 2)
    # data (the dcn axis) is outermost: row 0 from slice 0, row 1 from 1
    rows = [[d.slice_index for d in row] for row in mesh.devices]
    assert rows == [[0, 0], [1, 1]], rows

    # dcn=1 on a multi-slice runtime: stays within one slice
    mesh1 = make_hybrid_mesh({"data": 4}, devices=devs)
    assert {d.slice_index for d in mesh1.devices.ravel()} == {0}
    # ...and refuses when no slice is big enough
    with pytest.raises(ValueError, match="no single slice"):
        make_hybrid_mesh({"data": 8}, devices=devs)
    # more dcn than slices: clear error
    with pytest.raises(ValueError, match="only"):
        make_hybrid_mesh({"data": 4}, {"data": 4}, devices=devs)
