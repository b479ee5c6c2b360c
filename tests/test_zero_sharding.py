"""ZeRO-grade persistent parameter sharding (ISSUE 15): shard_params
parity with the replicated and shard_update paths, the cross-layout
snapshot matrix, per-chip memory accounting, the zero-retrace pin, and
the zero.py gather primitives."""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.config import root
from znicz_tpu.models.mnist_fc import build_fused
from znicz_tpu.observe import registry
from znicz_tpu.parallel.mesh import data_parallel_mesh
from znicz_tpu.snapshotter import (collect_state, restore_state,
                                   write_snapshot)

LAYOUTS = {
    "replicated": {},
    "shard_update": {"shard_update": True},
    "shard_params": {"shard_params": True},
}


def _build(n_epochs, n_dev, layout, optimizer="adam", seed=7, **kw):
    prng.seed_all(seed)
    return build_fused(max_epochs=n_epochs, layers=(16,),
                       minibatch_size=16, n_train=64, n_valid=0,
                       mesh=data_parallel_mesh(n_dev),
                       optimizer=optimizer, **LAYOUTS[layout], **kw)


def _weights(w):
    w.step.sync_to_units()
    return [np.asarray(f.weights.map_read()).copy() for f in w.forwards]


def _gauge(name):
    return registry.REGISTRY.get(name).labels(unit="FusedStep").get()


def test_shard_params_matches_replicated(cpu_devices):
    """shard_params trains within the repo's established
    sharded-vs-replicated pins for both optimizers (seeded metric
    history EXACTLY equal; weights/momenta at the existing
    test_shard_update_matches_replicated tolerances) — and matches the
    shard_update path BIT-FOR-BIT: the on-demand gather is exact data
    movement and the shard update is the same elementwise math on the
    same slices."""
    for opt in ("sgd", "adam"):
        runs = {}
        for layout in LAYOUTS:
            prng.seed_all(31)
            w = build_fused(max_epochs=3, layers=(23,),
                            minibatch_size=32, n_train=160, n_valid=64,
                            mesh=data_parallel_mesh(8), optimizer=opt,
                            **LAYOUTS[layout])
            w.initialize(device=XLADevice())
            w.run()
            w.step.sync_to_units()
            runs[layout] = {
                "w": [np.asarray(f.weights.map_read()).copy()
                      for f in w.forwards],
                "v": [np.asarray(g.gradient_weights.map_read()).copy()
                      for g in w.gds],
                "hist": [h["metric_validation"]
                         for h in w.decision.metrics_history],
            }
        base = runs["replicated"]
        for layout in ("shard_update", "shard_params"):
            assert runs[layout]["hist"] == base["hist"], (opt, layout)
            for key, rtol, atol in (("w", 2e-5, 1e-6), ("v", 2e-5, 1e-6)):
                for a, b in zip(runs[layout][key], base[key]):
                    np.testing.assert_allclose(
                        a, b, rtol=rtol, atol=atol,
                        err_msg=f"{opt}/{layout}/{key}")
        # the new mode vs the existing sharded path: bit-identical
        for key in ("w", "v"):
            for a, b in zip(runs["shard_params"][key],
                            runs["shard_update"][key]):
                np.testing.assert_array_equal(a, b, err_msg=f"{opt}/{key}")


def test_cross_layout_snapshot_matrix(tmp_path, cpu_devices):
    """Satellite 3: snapshots are layout-independent — a run interrupted
    in ANY layout resumes in ANY OTHER layout on the same mesh with
    BIT-IDENTICAL final weights and the same seeded history (snapshots
    store param-shaped host arrays; gather_params re-places them in
    whatever layout the resuming step uses)."""
    # one oracle serves every same-mesh cell: the three layouts are
    # bit-identical (pinned above)
    w_o = _build(4, 8, "replicated")
    w_o.initialize(device=XLADevice())
    w_o.run()
    want = _weights(w_o)
    want_hist = [h["metric_train"] for h in w_o.decision.metrics_history]

    matrix = [("shard_params", "replicated"),
              ("shard_params", "shard_update"),
              ("replicated", "shard_params"),
              ("shard_update", "shard_params"),
              ("shard_params", "shard_params")]
    for src, dst in matrix:
        w_a = _build(2, 8, src)
        w_a.initialize(device=XLADevice())
        w_a.run()
        arrays, meta = collect_state(w_a)
        # state arrays always carry the PARAM shape, never the layout
        assert arrays["step.opt.0.sw"].shape == \
            w_a.forwards[0].weights.shape, src
        snap = str(tmp_path / f"{src}_{dst}.npz")
        write_snapshot(snap, arrays, meta)

        w_b = _build(4, 8, dst)
        w_b.initialize(device=XLADevice())
        restore_state(w_b, snap)
        w_b.decision.max_epochs = 4
        w_b.decision.complete.set(False)
        w_b.run()
        got = _weights(w_b)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b, err_msg=f"{src}->{dst}")
        hist = [h["metric_train"]
                for h in w_b.decision.metrics_history]
        assert hist[-2:] == want_hist[-2:], (src, dst)


def test_cross_layout_elastic_resume_other_world_size(tmp_path,
                                                      cpu_devices):
    """The elastic leg of the matrix (PR 9 drill pattern): a
    shard_params run interrupted on an 8-wide mesh resumes REPLICATED on
    a 2-wide mesh — and vice versa — and continues within the repo's
    established cross-world-size pins (gradient psums group differently
    across mesh sizes, so the continuation is allclose, not bit-equal;
    same strength as test_shard_update_snapshot_restores_across_layouts)."""
    for src, n_src, dst, n_dst in (("shard_params", 8, "replicated", 2),
                                   ("replicated", 2, "shard_params", 8)):
        w_a = _build(2, n_src, src)
        w_a.initialize(device=XLADevice())
        w_a.run()
        arrays, meta = collect_state(w_a)
        snap = str(tmp_path / f"ws_{src}_{dst}.npz")
        write_snapshot(snap, arrays, meta)

        # oracle: continue at the SOURCE world size and layout
        w_o = _build(4, n_src, src)
        w_o.initialize(device=XLADevice())
        w_o.run()
        want = _weights(w_o)

        w_b = _build(4, n_dst, dst)
        w_b.initialize(device=XLADevice())
        restore_state(w_b, snap)
        w_b.decision.max_epochs = 4
        w_b.decision.complete.set(False)
        w_b.run()
        for a, b in zip(_weights(w_b), want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                       err_msg=f"{src}@{n_src}->"
                                               f"{dst}@{n_dst}")


def test_shard_params_memory_gauges(cpu_devices):
    """Acceptance: per-chip znicz_zero_param_bytes +
    znicz_zero_opt_state_bytes under shard_params reads <= 1/n of the
    replicated figure plus the padding epsilon, and the gathered-bytes
    counter advances by exactly the static per-dispatch figure."""
    n = 8
    totals = {}
    for layout in ("replicated", "shard_params"):
        w = _build(1, n, layout)
        w.initialize(device=XLADevice())
        totals[layout] = (_gauge("znicz_zero_param_bytes") +
                          _gauge("znicz_zero_opt_state_bytes"))
        if layout != "shard_params":
            continue
        # padding epsilon: at most (n - 1) f32 elements per sharded leaf
        n_sharded = sum(1 for leaf in w.step._params
                        for k in leaf if w.step._leaf_sharded(k))
        eps = 4 * (n - 1) * n_sharded
        assert totals["shard_params"] <= \
            totals["replicated"] / n + eps, totals
        before = _gauge("znicz_zero_gathered_bytes_total")
        w.loader.run()
        w.step.run()
        after = _gauge("znicz_zero_gathered_bytes_total")
        assert after - before == w.step._zero_gather_nbytes > 0
    # replicated steps report full bytes per chip and gather nothing
    assert totals["replicated"] > 0


def test_shard_params_zero_retrace(cpu_devices):
    """Acceptance: the gather chain compiles into the ONE train/eval
    program — steady-state compile delta 0 (no per-step retrace)."""
    prng.seed_all(11)
    w = build_fused(max_epochs=3, layers=(16,), minibatch_size=16,
                    n_train=64, n_valid=32, mesh=data_parallel_mesh(8),
                    optimizer="adam", shard_params=True)
    w.initialize(device=XLADevice())
    w.run()
    # the small synthetic dataset rides the HBM-pinned index-fed path
    train_fn = w.step._train_fn_idx or w.step._train_fn
    eval_fn = w.step._eval_fn_idx or w.step._eval_fn
    assert train_fn._cache_size() == 1
    assert eval_fn._cache_size() == 1


def test_shard_params_composes_with_accumulation_and_ema(cpu_devices):
    """accumulate_steps and the EMA mirror ride shard_params unchanged:
    seeded histories match the replicated run exactly, EMA weights at
    the standard sharded-vs-replicated tolerance, and the shard_update
    run bit-for-bit (the EMA mirrors live sharded too)."""
    runs = {}
    for layout in LAYOUTS:
        prng.seed_all(17)
        w = build_fused(max_epochs=2, layers=(12,), minibatch_size=16,
                        n_train=96, n_valid=32,
                        mesh=data_parallel_mesh(4), optimizer="sgd",
                        accumulate_steps=2, ema_decay=0.9,
                        **LAYOUTS[layout])
        w.initialize(device=XLADevice())
        w.run()
        runs[layout] = {
            "hist": [h["metric_validation"]
                     for h in w.decision.metrics_history],
            "ema": w.step.ema_params(),
        }
    assert runs["shard_params"]["hist"] == runs["replicated"]["hist"]
    for a, b in zip(runs["shard_params"]["ema"],
                    runs["replicated"]["ema"]):
        np.testing.assert_allclose(a["w"], b["w"], rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(a["b"], b["b"], rtol=2e-5, atol=1e-6)
    for a, b in zip(runs["shard_params"]["ema"],
                    runs["shard_update"]["ema"]):
        np.testing.assert_array_equal(a["w"], b["w"])
        np.testing.assert_array_equal(a["b"], b["b"])


def test_snapshot_d2h_batched(cpu_devices, monkeypatch):
    """Satellite 1: the snapshot path's D2H traffic is batched — the
    number of jax.device_get calls in collect_state does NOT scale with
    layer count (one batched fetch for sync_to_units' sharded leaves,
    one for the PRNG key, one for extra_state_arrays)."""
    import jax as jax_mod

    def counted_build(layers):
        prng.seed_all(13)
        w = build_fused(max_epochs=1, layers=layers, minibatch_size=16,
                        n_train=32, n_valid=0,
                        mesh=data_parallel_mesh(4), optimizer="adam",
                        shard_params=True, ema_decay=0.9)
        w.initialize(device=XLADevice())
        w.loader.run()
        w.step.run()
        real = jax_mod.device_get
        calls = []
        monkeypatch.setattr(jax_mod, "device_get",
                            lambda *a, **kw: calls.append(1) or
                            real(*a, **kw))
        collect_state(w)
        monkeypatch.setattr(jax_mod, "device_get", real)
        return len(calls)

    shallow = counted_build((8,))
    deep = counted_build((8, 8, 8))
    assert deep == shallow, (shallow, deep)


def test_all_gather_slices_matches_psum_regather(cpu_devices):
    """zero.all_gather_slices reconstructs exactly what psum_regather
    does — including the padded (size % n != 0) case — and the
    via_psum fallback routes through the psum path."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from znicz_tpu.parallel import zero
    from znicz_tpu.parallel.compat import shard_map
    from znicz_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": 4})
    for size in (64, 61):          # aligned + padded
        x = np.arange(size, dtype=np.float32).reshape(-1)
        like = jax.ShapeDtypeStruct((size,), np.float32)
        pad = (-size) % 4
        flat = np.pad(x, (0, pad))

        def body(f):
            rank = lax.axis_index("data")
            a = zero.all_gather_slices(f, rank, 4, "data", like)
            b = zero.all_gather_slices(f, rank, 4, "data", like,
                                       via_psum=True)
            return a, b

        fn = shard_map(body, mesh=mesh, in_specs=(P("data"),),
                       out_specs=(P(), P()))
        a, b = jax.jit(fn)(flat)
        np.testing.assert_array_equal(np.asarray(a), x)
        np.testing.assert_array_equal(np.asarray(b), x)


def test_pad_slice_skips_noop_pad(cpu_devices):
    """Satellite 2: pad_slice emits NO pad op when the size already
    divides by n (the aligned common case), and still pads otherwise."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.parallel import zero

    aligned = str(jax.make_jaxpr(
        lambda x: zero.pad_slice(x, jnp.int32(0), 4))(
            np.zeros((8, 8), np.float32)))
    ragged = str(jax.make_jaxpr(
        lambda x: zero.pad_slice(x, jnp.int32(0), 4))(
            np.zeros((7, 9), np.float32)))
    assert "pad" not in aligned
    assert "pad" in ragged


def test_shard_params_via_psum_fallback_matches(cpu_devices):
    """engine.zero_gather_via_psum routes the gather chain through the
    vma-safe psum_regather and trains identically."""
    hists = {}
    for via in (False, True):
        prev = root.common.engine.get("zero_gather_via_psum", False)
        root.common.engine.zero_gather_via_psum = via
        try:
            w = _build(2, 4, "shard_params", seed=23)
            w.initialize(device=XLADevice())
            w.run()
            hists[via] = ([h["metric_train"]
                           for h in w.decision.metrics_history],
                          _weights(w))
        finally:
            root.common.engine.zero_gather_via_psum = prev
    assert hists[True][0] == hists[False][0]
    for a, b in zip(hists[True][1], hists[False][1]):
        np.testing.assert_array_equal(a, b)


def test_shard_params_scan_epoch_and_state_dtype(cpu_devices):
    """shard_params composes with scan-epoch dispatch (the gather chain
    re-runs inside each scanned minibatch) and narrow SGD momenta:
    identical weights to the shard_update run bit-for-bit, and the
    gathered-bytes counter advances per SCANNED minibatch, not per
    dispatch."""
    import jax.numpy as jnp

    weights = {}
    for layout in ("shard_update", "shard_params"):
        prng.seed_all(31)
        w = build_fused(max_epochs=2, layers=(23,), minibatch_size=32,
                        n_train=160, n_valid=64,
                        mesh=data_parallel_mesh(8), optimizer="sgd",
                        optimizer_config={"state_dtype": "bfloat16"},
                        **LAYOUTS[layout])
        w.step.scan_epoch = True
        w.initialize(device=XLADevice())
        assert w.step._params[0]["vw"].dtype == jnp.bfloat16
        before = _gauge("znicz_zero_gathered_bytes_total")
        w.run()
        w.step.sync_to_units()
        if layout == "shard_params":
            per_dispatch = w.step._zero_gather_nbytes
            delta = _gauge("znicz_zero_gathered_bytes_total") - before
            assert per_dispatch > 0 and delta > per_dispatch, \
                (delta, per_dispatch)
        weights[layout] = [np.asarray(f.weights.map_read()).copy()
                           for f in w.forwards]
    for a, b in zip(weights["shard_params"], weights["shard_update"]):
        np.testing.assert_array_equal(a, b)


# -- transformer step ---------------------------------------------------------

def test_transformer_shard_params_matches_shard_update(cpu_devices):
    """The transformer step's shard_params mode is bit-identical to its
    shard_update pin (both update per-data-rank slices of the same
    psum-convention gradients; shard_params just PERSISTS the slices
    and regathers on demand instead of after the update)."""
    import jax
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.params import (
        init_params, shard_params_host, unshard_params_host)
    from znicz_tpu.parallel.mesh import make_mesh

    prng.seed_all(19)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 2, 32, 4, 64, 17
    params = init_params(gen, n_layers, d, heads, ff, vocab)
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, vocab, (4, 16)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    specs = tfm.param_specs(n_layers)
    shapes = tfm.param_shapes(n_layers, d, ff, vocab)

    res = {}
    for mode in ("shard_update", "shard_params"):
        step, _ = tfm.make_train_step(
            mesh, n_layers, d, heads, ff, vocab, lr=0.2,
            shard_update=(mode == "shard_update"),
            shard_params=(mode == "shard_params"))
        p = {k: (v if not isinstance(v, list) else [dict(b) for b in v])
             for k, v in params.items()}
        if mode == "shard_params":
            p = shard_params_host(p, specs, 2)
        losses = []
        for _ in range(6):
            p, loss = step(p, tokens, labels)
            losses.append(float(loss))
        host = jax.device_get(p)
        if mode == "shard_params":
            host = unshard_params_host(host, specs, shapes)
        res[mode] = (losses, host)

    assert res["shard_params"][0] == res["shard_update"][0]
    for a, b in zip(jax.tree.leaves(res["shard_params"][1]),
                    jax.tree.leaves(res["shard_update"][1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_transformer_shard_params_host_roundtrip(cpu_devices):
    """shard_params_host -> unshard_params_host is the identity,
    including odd (padded) leaf sizes."""
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.params import (
        init_params, shard_params_host, unshard_params_host)

    prng.seed_all(3)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 1, 16, 2, 32, 11   # 11: pads at n=4
    params = init_params(gen, n_layers, d, heads, ff, vocab)
    specs = tfm.param_specs(n_layers)
    shapes = tfm.param_shapes(n_layers, d, ff, vocab)
    flat = shard_params_host(params, specs, 4)
    back = unshard_params_host(flat, specs, shapes)
    import jax
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_transformer_shard_params_rejects_shard_update(cpu_devices):
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError, match="subsumes"):
        tfm.make_train_step(make_mesh({"data": 2, "seq": 1, "model": 1}),
                            1, 16, 2, 32, 8, shard_update=True,
                            shard_params=True)


# -- ISSUE 18: error-feedback residual snapshot/restore ----------------------

QC = {"mode": "int8", "chunk": 64, "error_feedback": True}


def test_ef_residual_snapshot_resume_bit_exact(tmp_path, cpu_devices):
    """ISSUE 18: error-feedback residuals are real state — a quantized
    int8+EF run interrupted mid-training resumes BIT-IDENTICAL to the
    uninterrupted run on the same mesh, in both the replicated and
    shard_params layouts (the per-rank rw/rb slabs snapshot as-is and
    restore into the same ranks; dropping them instead would fork the
    trajectory at the first post-resume step)."""
    for layout in ("replicated", "shard_params"):
        w_o = _build(4, 8, layout, quantized_collectives=QC)
        w_o.initialize(device=XLADevice())
        w_o.run()
        want = _weights(w_o)
        want_hist = [h["metric_train"]
                     for h in w_o.decision.metrics_history]

        w_a = _build(2, 8, layout, quantized_collectives=QC)
        w_a.initialize(device=XLADevice())
        w_a.run()
        arrays, meta = collect_state(w_a)
        # the residual slabs ride the snapshot, one rank row per device
        assert arrays["step.opt.0.rw"].shape == \
            (8,) + w_a.forwards[0].weights.shape, layout
        assert "step.opt.0.rb" in arrays and "step.opt.1.rw" in arrays
        snap = str(tmp_path / f"ef_{layout}.npz")
        write_snapshot(snap, arrays, meta)

        w_b = _build(4, 8, layout, quantized_collectives=QC)
        w_b.initialize(device=XLADevice())
        restore_state(w_b, snap)
        w_b.decision.max_epochs = 4
        w_b.decision.complete.set(False)
        w_b.run()
        for a, b in zip(_weights(w_b), want):
            np.testing.assert_array_equal(a, b, err_msg=layout)
        hist = [h["metric_train"]
                for h in w_b.decision.metrics_history]
        assert hist[-2:] == want_hist[-2:], layout


def test_ef_cross_mode_restore_matrix(tmp_path, cpu_devices):
    """The quantized <-> exact cells of the restore matrix, with the
    layout flipping at the same time: a quantized shard_params snapshot
    restores into an exact replicated build (the residuals have no home
    there — dropped, the run completes), and an exact replicated
    snapshot restores into a quantized shard_params build (residuals
    start at zero and the EF gauge goes live as training continues)."""
    # quantized shard_params -> exact replicated
    w_a = _build(2, 8, "shard_params", quantized_collectives=QC)
    w_a.initialize(device=XLADevice())
    w_a.run()
    arrays, meta = collect_state(w_a)
    assert "step.opt.0.rw" in arrays
    snap = str(tmp_path / "qc_to_exact.npz")
    write_snapshot(snap, arrays, meta)
    w_b = _build(4, 8, "replicated")
    w_b.initialize(device=XLADevice())
    restore_state(w_b, snap)
    w_b.decision.max_epochs = 4
    w_b.decision.complete.set(False)
    w_b.run()
    assert all("rw" not in leaf for leaf in w_b.step._params)
    assert all(np.isfinite(a).all() for a in _weights(w_b))

    # exact replicated -> quantized shard_params
    w_c = _build(2, 8, "replicated")
    w_c.initialize(device=XLADevice())
    w_c.run()
    arrays, meta = collect_state(w_c)
    assert not any(k.endswith(".rw") for k in arrays)
    snap2 = str(tmp_path / "exact_to_qc.npz")
    write_snapshot(snap2, arrays, meta)
    w_d = _build(4, 8, "shard_params", quantized_collectives=QC)
    w_d.initialize(device=XLADevice())
    restore_state(w_d, snap2)
    w_d.decision.max_epochs = 4
    w_d.decision.complete.set(False)
    w_d.run()
    assert all(np.isfinite(a).all() for a in _weights(w_d))
    assert _gauge("znicz_qcomm_residual_norm") > 0


def test_ef_residual_cross_world_fold(tmp_path, cpu_devices):
    """Restoring EF residuals at a DIFFERENT world size folds the rank
    SUM — the only quantity the deferred-error correction depends on —
    onto rank 0, and training continues finite from there."""
    w_a = _build(2, 8, "shard_params", quantized_collectives=QC)
    w_a.initialize(device=XLADevice())
    w_a.run()
    arrays, meta = collect_state(w_a)
    want_sum = arrays["step.opt.0.rw"].sum(axis=0)
    assert np.abs(want_sum).max() > 0            # EF actually accrued
    snap = str(tmp_path / "ef_fold.npz")
    write_snapshot(snap, arrays, meta)

    w_b = _build(4, 2, "replicated", quantized_collectives=QC)
    w_b.initialize(device=XLADevice())
    restore_state(w_b, snap)
    got = np.asarray(w_b.step._params[0]["rw"])
    assert got.shape[0] == 2
    np.testing.assert_allclose(got[0], want_sum, rtol=1e-6, atol=1e-7)
    assert np.abs(got[1]).max() == 0.0
    w_b.decision.max_epochs = 4
    w_b.decision.complete.set(False)
    w_b.run()
    assert all(np.isfinite(a).all() for a in _weights(w_b))
