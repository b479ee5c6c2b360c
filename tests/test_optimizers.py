"""Optimizer registry for the fused step (AdamW beyond the reference's
SGD+momentum): optax-oracle parity, convergence, snapshot round-trip,
and the fused-only guard."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.standard_workflow import StandardWorkflow


LAYERS = [{"type": "all2all_tanh", "->": {"output_sample_shape": 16}},
          {"type": "softmax", "->": {"output_sample_shape": 4}}]


def build_adam(max_epochs=2, seed=55, lr=0.01, wd=0.001, **kwargs):
    prng.seed_all(seed)
    return StandardWorkflow(
        name="AdamWf", loss_function="softmax", layers=[
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": 16},
             "<-": {"learning_rate": lr, "learning_rate_bias": lr,
                    "weights_decay": wd, "weights_decay_bias": wd}},
            {"type": "softmax",
             "->": {"output_sample_shape": 4},
             "<-": {"learning_rate": lr, "learning_rate_bias": lr,
                    "weights_decay": wd, "weights_decay_bias": wd}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 4, "sample_shape": (6,), "n_train": 40,
                       "n_valid": 0, "minibatch_size": 40},
        decision_config={"max_epochs": max_epochs},
        optimizer="adam", **kwargs)


def test_fused_adam_matches_optax():
    """One-minibatch dataset: the fused adam trajectory equals optax's
    adamw applied to gradients of the same loss (shuffling only permutes
    rows within the single batch; the summed loss/grads are invariant)."""
    import optax

    lr, wd = 0.01, 0.001
    w = build_adam(max_epochs=5, lr=lr, wd=wd)
    w.initialize(device=XLADevice())
    step = w.step
    # capture the (only) minibatch the workflow will train on — via the
    # HBM-pinned dataset + indices (serve_indices_only mode leaves
    # minibatch_data unfilled)
    w.loader.run()
    idx = np.maximum(np.asarray(w.loader.minibatch_indices.mem), 0)
    x0 = np.asarray(w.loader.original_data.mem)[idx].copy()
    y0 = np.asarray(w.loader.original_labels.mem)[idx].copy()
    params0 = [{k: np.asarray(jax.device_get(v)) for k, v in leaf.items()}
               for leaf in step._params]

    w.run()
    step.sync_to_units()
    trained = [{k: np.asarray(jax.device_get(v)) for k, v in leaf.items()}
               for leaf in step._params]
    # the capture above consumed epoch 0's only minibatch, so training
    # covered the remaining epochs; every epoch trains on the same rows
    # (one-minibatch dataset — reshuffling only permutes within it)
    n_steps = int(trained[0]["t"])
    assert n_steps >= 3

    # optax oracle on the identical loss geometry
    trainable = [{k: jnp.asarray(v) for k, v in leaf.items()
                  if k in ("w", "b")} for leaf in params0]

    def loss_fn(ps):
        out, logits_tail = step._forward_chain(ps, jnp.asarray(x0),
                                               train=True)
        loss, _ = step._loss_and_metrics(
            out, logits_tail, jnp.asarray(y0),
            jnp.ones(len(x0), bool))
        return loss / len(x0)

    opt = optax.adamw(lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=wd)
    state = opt.init(trainable)
    ps = trainable
    for _ in range(n_steps):
        grads = jax.grad(loss_fn)(ps)
        updates, state = opt.update(grads, state, ps)
        ps = optax.apply_updates(ps, updates)
    for got, want in zip(trained, ps):
        for k in ("w", "b"):
            np.testing.assert_allclose(got[k], np.asarray(want[k]),
                                       rtol=2e-4, atol=1e-6,
                                       err_msg=k)


def test_adam_learns_faster_than_tiny_sgd():
    """Sanity: adam with its adaptive step actually trains (errors drop
    to ~0 on separable synthetic clusters)."""
    w = build_adam(max_epochs=12, lr=0.02)
    w.initialize(device=XLADevice())
    w.run()
    hist = [h["metric_train"] for h in w.decision.metrics_history]
    assert hist[-1] <= hist[0] * 0.5, hist


def test_adam_snapshot_resume_bit_exact(tmp_path):
    """Interrupt/resume with adam state (second moments + step count)
    reproduces the uninterrupted run bit-exactly."""
    from znicz_tpu.snapshotter import collect_state, restore_state, \
        write_snapshot

    def final_weights(w):
        w.step.sync_to_units()
        return [np.asarray(f.weights.map_read()).copy()
                for f in w.forwards]

    # uninterrupted: 6 epochs
    w_full = build_adam(max_epochs=6, seed=99)
    w_full.initialize(device=XLADevice())
    w_full.run()
    want = final_weights(w_full)

    # interrupted at 3, resumed to 6
    w_a = build_adam(max_epochs=3, seed=99)
    w_a.initialize(device=XLADevice())
    w_a.run()
    arrays, meta = collect_state(w_a)
    snap = str(tmp_path / "adam.npz")
    write_snapshot(snap, arrays, meta)

    # same seed: the synthetic DATASET is generated at build time from
    # the prng (snapshots restore streams + shuffle order, not data)
    w_b = build_adam(max_epochs=6, seed=99)
    w_b.initialize(device=XLADevice())
    restore_state(w_b, snap)
    # the snapshot was taken after w_a COMPLETED (max_epochs reached);
    # extending the run means lifting both the epoch cap and the stored
    # completion gate — exactly what continuing w_a in-process needs too
    w_b.decision.max_epochs = 6
    w_b.decision.complete.set(False)
    w_b.run()
    got = final_weights(w_b)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_adam_requires_fused():
    with pytest.raises(ValueError, match="requires fused"):
        build_adam(fused=False)


def test_unknown_optimizer_rejected():
    from znicz_tpu.parallel.step import FusedTrainStep

    with pytest.raises(ValueError, match="unknown optimizer"):
        FusedTrainStep(optimizer="rmsprop")


def test_cross_optimizer_resume_rejected(tmp_path):
    from znicz_tpu.snapshotter import collect_state, restore_state, \
        write_snapshot

    w_a = build_adam(max_epochs=1, seed=42)
    w_a.initialize(device=XLADevice())
    w_a.run()
    arrays, meta = collect_state(w_a)
    assert meta["optimizer"] == "adam"
    snap = str(tmp_path / "x.npz")
    write_snapshot(snap, arrays, meta)

    prng.seed_all(42)
    # same architecture, default (sgd) optimizer
    w_b = StandardWorkflow(
        name="AdamWf", loss_function="softmax", layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 16}},
            {"type": "softmax", "->": {"output_sample_shape": 4}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 4, "sample_shape": (6,), "n_train": 40,
                       "n_valid": 0, "minibatch_size": 40},
        decision_config={"max_epochs": 1})
    w_b.initialize(device=XLADevice())
    with pytest.raises(ValueError, match="snapshot optimizer"):
        restore_state(w_b, snap)


def test_adam_rejects_l1():
    prng.seed_all(8)
    w = StandardWorkflow(
        name="L1Adam", loss_function="softmax", layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
             "<-": {"l1_vs_l2": 0.5}},
            {"type": "softmax", "->": {"output_sample_shape": 4}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 4, "sample_shape": (6,), "n_train": 40,
                       "n_valid": 0, "minibatch_size": 40},
        decision_config={"max_epochs": 1}, optimizer="adam")
    with pytest.raises(ValueError, match="l1_vs_l2 is SGD-only"):
        w.initialize(device=XLADevice())


def test_shard_update_matches_replicated(cpu_devices):
    """ZeRO-style sharded update (reduce-scatter grads, shard-local
    optimizer state, all-gather params — arXiv:2004.13336) trains
    identically to the replicated update on an 8-device mesh, for both
    optimizers."""
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    for opt in ("sgd", "adam"):
        weights = {}
        for mode in (False, True):
            prng.seed_all(31)
            w = build_fused(max_epochs=3, layers=(23,), minibatch_size=32,
                            n_train=160, n_valid=64,
                            mesh=data_parallel_mesh(8),
                            optimizer=opt, shard_update=mode)
            w.initialize(device=XLADevice())
            w.run()
            w.step.sync_to_units()
            weights[mode] = {
                "w": [np.asarray(f.weights.map_read()).copy()
                      for f in w.forwards],
                "v": [np.asarray(g.gradient_weights.map_read()).copy()
                      for g in w.gds],
                "hist": [h["metric_validation"]
                         for h in w.decision.metrics_history],
            }
        assert weights[True]["hist"] == weights[False]["hist"], opt
        for a, b in zip(weights[True]["w"], weights[False]["w"]):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                       err_msg=opt)
        # momentum buffers reassemble from shards to the same state
        for a, b in zip(weights[True]["v"], weights[False]["v"]):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                       err_msg=opt)


def test_shard_update_adam_snapshot_roundtrip(tmp_path, cpu_devices):
    """Sharded optimizer state snapshots in the param shape and restores
    into a sharded run bit-exactly."""
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh
    from znicz_tpu.snapshotter import collect_state, restore_state, \
        write_snapshot

    def build(n):
        prng.seed_all(13)
        return build_fused(max_epochs=n, layers=(16,), minibatch_size=16,
                           n_train=64, n_valid=0,
                           mesh=data_parallel_mesh(8),
                           optimizer="adam", shard_update=True)

    w_full = build(4)
    w_full.initialize(device=XLADevice())
    w_full.run()
    w_full.step.sync_to_units()
    want = [np.asarray(f.weights.map_read()).copy()
            for f in w_full.forwards]

    w_a = build(2)
    w_a.initialize(device=XLADevice())
    w_a.run()
    arrays, meta = collect_state(w_a)
    # state arrays carry the PARAM shape, not the shard layout
    assert arrays["step.opt.0.sw"].shape == \
        w_a.forwards[0].weights.shape
    snap = str(tmp_path / "z.npz")
    write_snapshot(snap, arrays, meta)

    w_b = build(4)
    w_b.initialize(device=XLADevice())
    restore_state(w_b, snap)
    w_b.decision.max_epochs = 4
    w_b.decision.complete.set(False)
    w_b.run()
    w_b.step.sync_to_units()
    got = [np.asarray(f.weights.map_read()).copy() for f in w_b.forwards]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_shard_update_snapshot_restores_across_layouts(tmp_path,
                                                       cpu_devices):
    """State is stored in param shape, so a sharded-update run restores
    into a replicated one on a different mesh size (the elastic-resume
    story) and continues identically."""
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh
    from znicz_tpu.snapshotter import collect_state, restore_state, \
        write_snapshot

    def build(n_epochs, n_dev, shard):
        prng.seed_all(7)
        return build_fused(max_epochs=n_epochs, layers=(16,),
                           minibatch_size=16, n_train=64, n_valid=0,
                           mesh=data_parallel_mesh(n_dev),
                           optimizer="adam", shard_update=shard)

    # sharded over 8 devices, interrupted at 2 epochs
    w_a = build(2, 8, True)
    w_a.initialize(device=XLADevice())
    w_a.run()
    arrays, meta = collect_state(w_a)
    snap = str(tmp_path / "x.npz")
    write_snapshot(snap, arrays, meta)

    # oracle: continue the SAME layout to 4 epochs
    w_o = build(4, 8, True)
    w_o.initialize(device=XLADevice())
    w_o.run()
    w_o.step.sync_to_units()
    want = [np.asarray(f.weights.map_read()).copy()
            for f in w_o.forwards]

    # resume REPLICATED on a 2-device mesh from the sharded snapshot
    w_b = build(4, 2, False)
    w_b.initialize(device=XLADevice())
    restore_state(w_b, snap)
    w_b.decision.max_epochs = 4
    w_b.decision.complete.set(False)
    w_b.run()
    w_b.step.sync_to_units()
    got = [np.asarray(f.weights.map_read()).copy() for f in w_b.forwards]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_clip_norm_matches_manual_oracle():
    """Global-norm clipping: one fused SGD step (zero momentum) equals
    w - lr * clip(g_mean); a huge threshold is a no-op."""
    import jax
    import jax.numpy as jnp

    def build(clip):
        prng.seed_all(91)
        return StandardWorkflow(
            name="ClipWf", loss_function="softmax", layers=[
                {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
                 "<-": {"learning_rate": 0.1, "learning_rate_bias": 0.1,
                        "gradient_moment": 0.0,
                        "gradient_moment_bias": 0.0}},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.1, "learning_rate_bias": 0.1,
                        "gradient_moment": 0.0,
                        "gradient_moment_bias": 0.0}}],
            loader_name="synthetic_classifier",
            loader_config={"n_classes": 4, "sample_shape": (6,),
                           "n_train": 40, "n_valid": 0,
                           "minibatch_size": 40},
            decision_config={"max_epochs": 1}, clip_norm=clip)

    results = {}
    for clip in (0.5, 1e9):
        w = build(clip)
        w.initialize(device=XLADevice())
        step = w.step
        w.loader.run()
        idx = np.maximum(np.asarray(w.loader.minibatch_indices.mem), 0)
        x0 = np.asarray(w.loader.original_data.mem)[idx]
        y0 = np.asarray(w.loader.original_labels.mem)[idx]
        p0 = [{k: np.asarray(jax.device_get(v))
               for k, v in leaf.items()} for leaf in step._params]
        step.run()
        p1 = [{k: np.asarray(jax.device_get(v))
               for k, v in leaf.items()} for leaf in step._params]
        results[clip] = (p0, p1, x0, y0, step)

    p0, p1, x0, y0, step = results[0.5]
    trainable = [{k: jnp.asarray(l[k]) for k in ("w", "b")} for l in p0]

    def loss_fn(ps):
        out, lt = step._forward_chain(ps, jnp.asarray(x0), train=True)
        loss, _ = step._loss_and_metrics(out, lt, jnp.asarray(y0),
                                         jnp.ones(len(x0), bool))
        return loss / len(x0)

    grads = jax.grad(loss_fn)(trainable)
    gnorm = float(jnp.sqrt(sum(jnp.sum(g * g)
                               for l in grads for g in l.values())))
    assert gnorm > 0.5          # threshold actually binds
    scale = 0.5 / gnorm
    for li, leaf in enumerate(grads):
        for k in ("w", "b"):
            want = p0[li][k] - 0.1 * scale * np.asarray(leaf[k])
            np.testing.assert_allclose(p1[li][k], want, rtol=1e-5,
                                       atol=1e-7, err_msg=f"{li}.{k}")
    # huge threshold: same update as the raw gradient
    p0u, p1u, _, _, _ = results[1e9]
    for li, leaf in enumerate(grads):
        for k in ("w", "b"):
            want = p0u[li][k] - 0.1 * np.asarray(leaf[k])
            np.testing.assert_allclose(p1u[li][k], want, rtol=1e-5,
                                       atol=1e-7)


def test_clip_norm_requires_fused():
    with pytest.raises(ValueError, match="clip_norm requires fused"):
        StandardWorkflow(
            name="x", loss_function="softmax",
            layers=[{"type": "softmax",
                     "->": {"output_sample_shape": 3}}],
            loader_name="synthetic_classifier",
            loader_config={"n_classes": 3, "sample_shape": (4,),
                           "n_train": 30, "n_valid": 0,
                           "minibatch_size": 30},
            decision_config={"max_epochs": 1}, fused=False, clip_norm=1.0)


def test_clip_norm_rejects_nonpositive():
    for bad in (0.0, -1.0):
        with pytest.raises(ValueError, match="must be positive"):
            StandardWorkflow(
                name="x", loss_function="softmax",
                layers=[{"type": "softmax",
                         "->": {"output_sample_shape": 3}}],
                loader_name="synthetic_classifier",
                loader_config={"n_classes": 3, "sample_shape": (4,),
                               "n_train": 30, "n_valid": 0,
                               "minibatch_size": 30},
                decision_config={"max_epochs": 1}, clip_norm=bad)


def _accum_build(minibatch, accumulate, optimizer="sgd", n_train=64,
                 max_epochs=3):
    prng.seed_all(61)
    return StandardWorkflow(
        name="AccWf", loss_function="softmax", layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 12},
             "<-": {"learning_rate": 0.05, "learning_rate_bias": 0.05,
                    "gradient_moment": 0.9, "gradient_moment_bias": 0.9}},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": {"learning_rate": 0.05, "learning_rate_bias": 0.05,
                    "gradient_moment": 0.9, "gradient_moment_bias": 0.9}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 4, "sample_shape": (6,),
                       "n_train": n_train, "n_valid": 0,
                       "minibatch_size": minibatch, "shuffle_limit": 0},
        decision_config={"max_epochs": max_epochs}, optimizer=optimizer,
        accumulate_steps=accumulate)


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_accumulation_matches_big_minibatch(optimizer):
    """Accumulating 4 minibatches of 16 applies the same updates as one
    minibatch of 64 over the same (unshuffled) data — summed grads and
    sample counts are identical, so the trajectories match."""
    import jax

    weights = {}
    for minibatch, accumulate in ((64, 1), (16, 4)):
        w = _accum_build(minibatch, accumulate, optimizer)
        w.initialize(device=XLADevice())
        w.run()
        w.step.sync_to_units()
        weights[(minibatch, accumulate)] = [
            np.asarray(f.weights.map_read()).copy() for f in w.forwards]
        assert w.step._grad_acc is None       # no dangling accumulation
    for a, b in zip(weights[(64, 1)], weights[(16, 4)]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                   err_msg=optimizer)


def test_accumulation_ragged_tail_applies_at_epoch_end():
    """A train pass shorter than accumulate_steps still applies its
    gradients at the pass boundary (no leak into the next epoch)."""
    w = _accum_build(16, 4, n_train=48, max_epochs=4)
    w.initialize(device=XLADevice())
    w.run()
    assert w.step._grad_acc is None
    hist = [h["metric_train"] for h in w.decision.metrics_history]
    assert hist[-1] < hist[0], hist


def test_accumulation_requires_fused():
    with pytest.raises(ValueError, match="accumulate_steps requires"):
        StandardWorkflow(
            name="x", loss_function="softmax",
            layers=[{"type": "softmax",
                     "->": {"output_sample_shape": 3}}],
            loader_name="synthetic_classifier",
            loader_config={"n_classes": 3, "sample_shape": (4,),
                           "n_train": 30, "n_valid": 0,
                           "minibatch_size": 30},
            decision_config={"max_epochs": 1}, fused=False,
            accumulate_steps=2)


def test_accumulation_composes_with_shard_update(cpu_devices):
    """accumulate_steps + ZeRO shard_update trains identically to
    accumulate_steps with the replicated update."""
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh
    from znicz_tpu.parallel.step import FusedTrainStep

    weights = {}
    for shard in (False, True):
        prng.seed_all(41)
        w = build_fused(max_epochs=3, layers=(16,), minibatch_size=16,
                        n_train=64, n_valid=0,
                        mesh=data_parallel_mesh(8), optimizer="adam",
                        shard_update=shard, accumulate_steps=2)
        w.initialize(device=XLADevice())
        w.run()
        w.step.sync_to_units()
        assert w.step._grad_acc is None
        weights[shard] = [np.asarray(f.weights.map_read()).copy()
                          for f in w.forwards]
    for a, b in zip(weights[True], weights[False]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_pallas_kernels_compose_with_accumulation(cpu_devices):
    """engine.pallas (interpret) composed with accumulate_steps on an
    8-device mesh trains to the same weights as the XLA path.

    (pallas x shard_update is deliberately NOT covered here: the Pallas
    HLO interpreter cannot evaluate kernels whose operands VARY over
    mesh axes under the vma checker — the same interpreter-only
    limitation as multi-device interpret-mode flash attention; the
    Mosaic path on real TPU does not route through the interpreter.)"""
    from znicz_tpu.core.config import root
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    def run(pallas: bool):
        prng.seed_all(47)
        root.common.engine.pallas = pallas
        root.common.engine.pallas_interpret = pallas
        try:
            w = build_fused(max_epochs=2, layers=(12,), minibatch_size=16,
                            n_train=64, n_valid=0,
                            mesh=data_parallel_mesh(8), optimizer="adam",
                            accumulate_steps=2)
            w.initialize(device=XLADevice())
            w.run()
            w.step.sync_to_units()
            return [np.asarray(f.weights.map_read()).copy()
                    for f in w.forwards]
        finally:
            root.common.engine.pallas = False
            root.common.engine.pallas_interpret = False

    for a, b in zip(run(True), run(False)):
        # kernel-vs-XLA op ordering drifts a few ULPs per apply; over
        # multiple applies that accumulates to ~1e-5 absolute
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


def test_ema_matches_manual_average():
    """ema_decay maintains ew = d*ew + (1-d)*w after every optimizer
    apply, seeded exactly — verified against a manually tracked average
    over the per-step parameter trajectory."""
    import jax

    from znicz_tpu.models.mnist_fc import build_fused

    d = 0.8
    prng.seed_all(61)
    w = build_fused(max_epochs=1, layers=(16,), minibatch_size=20,
                    n_train=100, n_valid=0, ema_decay=d)
    w.initialize(device=XLADevice())
    assert all("ew" in leaf for leaf in w.step._params)

    manual = [np.asarray(jax.device_get(leaf["w"]))
              for leaf in w.step._params]
    for _ in range(5):
        w.loader.run()
        w.step.run()
        for i, leaf in enumerate(w.step._params):
            cur = np.asarray(jax.device_get(leaf["w"]))
            manual[i] = d * manual[i] + (1 - d) * cur
    ema = w.step.ema_params()
    for i, leaf in enumerate(ema):
        np.testing.assert_allclose(leaf["w"], manual[i], rtol=1e-5,
                                   atol=1e-6, err_msg=f"layer {i}")
        assert "b" in leaf


def test_ema_snapshots_and_restores():
    """The EMA mirror rides extra_state_arrays: a snapshot/restore into
    a fresh differently-seeded workflow reproduces it bit-exactly."""
    import os
    import tempfile

    from znicz_tpu.snapshotter import (collect_state, restore_state,
                                       write_snapshot)
    from znicz_tpu.standard_workflow import StandardWorkflow

    def build(seed):
        prng.seed_all(seed)
        return StandardWorkflow(
            name="ema", layers=[{"type": "softmax",
                                 "->": {"output_sample_shape": 3},
                                 "<-": {"learning_rate": 0.1}}],
            loss_function="softmax", loader_name="synthetic_classifier",
            loader_config={"n_classes": 3, "sample_shape": (6,),
                           "n_train": 60, "n_valid": 0,
                           "minibatch_size": 20},
            decision_config={"max_epochs": 1}, ema_decay=0.9)

    w = build(5)
    w.initialize(device=XLADevice())
    w.run()
    ema = w.step.ema_params()
    arrays, meta = collect_state(w)
    assert any(".ew" in k for k in arrays)

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "s.npz")
        write_snapshot(path, arrays, meta)
        w2 = build(6)
        w2.initialize(device=XLADevice())
        restore_state(w2, path)
    ema2 = w2.step.ema_params()
    for a, b in zip(ema, ema2):
        np.testing.assert_array_equal(a["w"], b["w"])

    # validation: ema_decay must be in (0, 1), and requires fused
    import pytest
    with pytest.raises(ValueError, match="ema_decay"):
        StandardWorkflow(
            name="bad", layers=[{"type": "softmax",
                                 "->": {"output_sample_shape": 3}}],
            loader_name="synthetic_classifier",
            loader_config={"n_classes": 3, "sample_shape": (6,)},
            fused=False, ema_decay=0.9)
    with pytest.raises(ValueError, match=r"in \(0, 1\)"):
        StandardWorkflow(
            name="oob", layers=[{"type": "softmax",
                                 "->": {"output_sample_shape": 3}}],
            loader_name="synthetic_classifier",
            loader_config={"n_classes": 3, "sample_shape": (6,)},
            ema_decay=1.5)
    # restoring an EMA snapshot into a non-EMA workflow fails loudly
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "s.npz")
        write_snapshot(path, arrays, meta)
        w3 = StandardWorkflow(
            name="noema", layers=[{"type": "softmax",
                                   "->": {"output_sample_shape": 3},
                                   "<-": {"learning_rate": 0.1}}],
            loss_function="softmax", loader_name="synthetic_classifier",
            loader_config={"n_classes": 3, "sample_shape": (6,),
                           "n_train": 60, "n_valid": 0,
                           "minibatch_size": 20},
            decision_config={"max_epochs": 1})
        prng.seed_all(8)
        w3.initialize(device=XLADevice())
        with pytest.raises(ValueError, match="EMA weight mirrors"):
            restore_state(w3, path)


def test_export_forward_with_ema_weights(tmp_path):
    """export_forward(use_ema=True) ships the Polyak mirrors; the loaded
    package predicts with them (serving view), while the default export
    keeps the raw weights."""
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.utils.export import ExportedForward, export_forward

    prng.seed_all(21)
    w = StandardWorkflow(
        name="emaexp", layers=[{"type": "softmax",
                                "->": {"output_sample_shape": 3},
                                "<-": {"learning_rate": 0.2}}],
        loss_function="softmax", loader_name="synthetic_classifier",
        loader_config={"n_classes": 3, "sample_shape": (6,),
                       "n_train": 90, "n_valid": 0,
                       "minibatch_size": 30},
        decision_config={"max_epochs": 2}, ema_decay=0.7)
    w.initialize(device=XLADevice())
    w.run()

    raw_path = export_forward(w, str(tmp_path / "raw.npz"))
    ema_path = export_forward(w, str(tmp_path / "ema.npz"), use_ema=True)
    import json
    raw_w = np.load(raw_path)["0.weights"]
    with np.load(ema_path) as pkg:
        ema_w = pkg["0.weights"]
        assert json.loads(str(pkg["__arch__"]))["ema"] is True
    with np.load(raw_path) as pkg:
        assert json.loads(str(pkg["__arch__"]))["ema"] is False
    assert not np.array_equal(raw_w, ema_w)        # mirrors lag raw
    np.testing.assert_allclose(ema_w, w.step.ema_params()[0]["w"])
    # the loaded EMA package runs inference
    x = np.zeros((4, 6), np.float32)
    out = ExportedForward(ema_path)(x)
    assert out.shape == (4, 3)

    # without ema_decay the flag fails loudly
    import pytest
    prng.seed_all(22)
    w2 = StandardWorkflow(
        name="noema2", layers=[{"type": "softmax",
                                "->": {"output_sample_shape": 3},
                                "<-": {"learning_rate": 0.2}}],
        loss_function="softmax", loader_name="synthetic_classifier",
        loader_config={"n_classes": 3, "sample_shape": (6,),
                       "n_train": 30, "n_valid": 0,
                       "minibatch_size": 10},
        decision_config={"max_epochs": 1})
    w2.initialize(device=XLADevice())
    w2.run()
    with pytest.raises(ValueError, match="ema_decay"):
        export_forward(w2, str(tmp_path / "x.npz"), use_ema=True)
    # and before initialize: clear error, not a TypeError deep inside
    prng.seed_all(23)
    w3 = StandardWorkflow(
        name="uninit", layers=[{"type": "softmax",
                                "->": {"output_sample_shape": 3}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 3, "sample_shape": (6,)},
        ema_decay=0.9)
    with pytest.raises(ValueError, match="initialized"):
        export_forward(w3, str(tmp_path / "y.npz"), use_ema=True)


def test_everything_on_composition(tmp_path, cpu_devices):
    """Capstone: adam + ZeRO update sharding + global clipping + gradient
    accumulation + EMA mirrors, on the 8-device mesh, trains finitely and
    snapshot/restores bit-exactly."""
    from znicz_tpu.parallel.mesh import data_parallel_mesh
    from znicz_tpu.snapshotter import (collect_state, restore_state,
                                       write_snapshot)

    def build(seed):
        prng.seed_all(seed)
        return StandardWorkflow(
            name="allon", loss_function="softmax", layers=[
                {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.01, "weights_decay": 1e-3}},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.01, "weights_decay": 1e-3}}],
            loader_name="synthetic_classifier",
            loader_config={"n_classes": 4, "sample_shape": (6,),
                           "n_train": 64, "n_valid": 32,
                           "minibatch_size": 16},
            decision_config={"max_epochs": 2},
            mesh=data_parallel_mesh(8), optimizer="adam",
            shard_update=True, clip_norm=1.0, accumulate_steps=2,
            ema_decay=0.9)

    w = build(77)
    w.initialize(device=XLADevice())
    w.run()
    hist = [h["metric_validation"] for h in w.decision.metrics_history]
    assert len(hist) == 2 and all(np.isfinite(hist))
    ema = w.step.ema_params()
    assert all(np.isfinite(leaf["w"]).all() for leaf in ema)

    arrays, meta = collect_state(w)
    snap = str(tmp_path / "allon.npz")
    write_snapshot(snap, arrays, meta)
    w2 = build(78)
    w2.initialize(device=XLADevice())
    restore_state(w2, snap)
    for a, b in zip(ema, w2.step.ema_params()):
        np.testing.assert_array_equal(a["w"], b["w"])
    w.step.sync_to_units()
    w2.step.sync_to_units()
    np.testing.assert_array_equal(w.forwards[0].weights.map_read(),
                                  w2.forwards[0].weights.map_read())


# -- narrow optimizer-state storage (state_dtype) ---------------------------

def build_sgd_momentum(max_epochs=3, seed=55, state_dtype=None):
    """SGD+momentum workflow; momentum matters (gradient_moment=0.9)."""
    prng.seed_all(seed)
    hp = {"learning_rate": 0.05, "learning_rate_bias": 0.05,
          "gradient_moment": 0.9, "gradient_moment_bias": 0.9,
          "weights_decay": 1e-4, "weights_decay_bias": 1e-4}
    cfg = {"state_dtype": state_dtype} if state_dtype else None
    return StandardWorkflow(
        name="SgdState", loss_function="softmax", layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
             "<-": dict(hp)},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": dict(hp)}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 4, "sample_shape": (6,), "n_train": 40,
                       "n_valid": 0, "minibatch_size": 40},
        decision_config={"max_epochs": max_epochs},
        optimizer="sgd", optimizer_config=cfg)


def test_state_dtype_bf16_tracks_f32():
    """bf16 momentum storage: velocity leaves live narrow inside the
    step, the unit-facing buffers stay f32, and the 6-epoch trajectory
    tracks the f32 run closely (math is f32 — only persistence narrows)."""
    runs = {}
    for sd in (None, "bfloat16"):
        w = build_sgd_momentum(max_epochs=6, seed=91, state_dtype=sd)
        w.initialize(device=XLADevice())
        want = jnp.bfloat16 if sd else jnp.float32
        assert w.step._params[0]["vw"].dtype == want
        w.run()
        w.step.sync_to_units()
        assert w.forwards[0].weights.map_read().dtype == np.float32
        assert np.asarray(
            w.gds[0].gradient_weights.map_read()).dtype == np.float32
        runs[sd] = [np.asarray(f.weights.map_read()).copy()
                    for f in w.forwards]
    for a, b in zip(runs[None], runs["bfloat16"]):
        np.testing.assert_allclose(a, b, rtol=0.05, atol=5e-3)


def test_state_dtype_snapshot_resume_bit_exact(tmp_path):
    """f32 snapshot of bf16 momenta widens exactly, so interrupt/resume
    under state_dtype reproduces the uninterrupted run bit-exactly."""
    from znicz_tpu.snapshotter import collect_state, restore_state, \
        write_snapshot

    def final_weights(w):
        w.step.sync_to_units()
        return [np.asarray(f.weights.map_read()).copy()
                for f in w.forwards]

    w_full = build_sgd_momentum(max_epochs=6, seed=17,
                                state_dtype="bfloat16")
    w_full.initialize(device=XLADevice())
    w_full.run()
    want = final_weights(w_full)

    w_a = build_sgd_momentum(max_epochs=3, seed=17,
                             state_dtype="bfloat16")
    w_a.initialize(device=XLADevice())
    w_a.run()
    arrays, meta = collect_state(w_a)
    snap = str(tmp_path / "sgdstate.npz")
    write_snapshot(snap, arrays, meta)

    w_b = build_sgd_momentum(max_epochs=6, seed=17,
                             state_dtype="bfloat16")
    w_b.initialize(device=XLADevice())
    restore_state(w_b, snap)
    w_b.decision.max_epochs = 6
    w_b.decision.complete.set(False)
    w_b.run()
    got = final_weights(w_b)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_state_dtype_rejected_for_adam():
    with pytest.raises(ValueError, match="state_dtype"):
        build_adam(optimizer_config={"state_dtype": "bfloat16"})


def test_state_dtype_shard_update_scan(cpu_devices):
    """state_dtype composes with the ZeRO-sharded update and scan-epoch
    dispatch: momenta stay narrow through _flat_shard_put (it must not
    widen them — the scan carry would then flip dtypes and crash) and the
    sharded bf16-state run tracks the replicated one."""
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    weights = {}
    for mode in (False, True):
        prng.seed_all(31)
        w = build_fused(max_epochs=3, layers=(23,), minibatch_size=32,
                        n_train=160, n_valid=64,
                        mesh=data_parallel_mesh(8),
                        optimizer="sgd", shard_update=mode,
                        optimizer_config={"state_dtype": "bfloat16"})
        w.step.scan_epoch = True
        w.initialize(device=XLADevice())
        assert w.step._params[0]["vw"].dtype == jnp.bfloat16, \
            "narrowing undone by the sharded placement"
        w.run()
        w.step.sync_to_units()
        weights[mode] = [np.asarray(f.weights.map_read()).copy()
                        for f in w.forwards]
    for a, b in zip(weights[True], weights[False]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
