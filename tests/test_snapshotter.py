"""Checkpoint/resume tests (SURVEY.md §4.3 + §5 tier-2): snapshot mid-run,
reload into a fresh workflow, continue, assert the metric history is
identical to an uninterrupted run — the reference's resume-exactness trick,
here over the array-based .npz state dict instead of object pickles."""

import os

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.snapshotter import collect_state, restore_state, write_snapshot
from znicz_tpu.standard_workflow import StandardWorkflow

LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 24},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 6},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
]
LOADER = {"n_classes": 6, "sample_shape": (10, 10), "n_train": 240,
          "n_valid": 120, "minibatch_size": 40, "spread": 2.5, "noise": 1.0}


def build(max_epochs, snap_dir=None, fused=True, seed=77, **snap_kw):
    prng.seed_all(seed)
    cfg = None
    if snap_dir is not None:
        cfg = {"directory": str(snap_dir), "prefix": "t",
               "only_improved": False, "keep_all": True, **snap_kw}
    w = StandardWorkflow(
        name="SnapTest", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": max_epochs},
        snapshotter_config=cfg, fused=fused)
    w.initialize(device=XLADevice())
    return w


@pytest.mark.parametrize("fused", [True, False])
def test_resume_is_bit_exact(tmp_path, fused):
    # uninterrupted 4-epoch run, snapshotting every epoch
    w_full = build(4, tmp_path, fused=fused)
    w_full.run()
    full_hist = w_full.decision.metrics_history
    assert len(full_hist) == 4
    snap2 = tmp_path / "t_2.npz"
    assert snap2.exists(), sorted(os.listdir(tmp_path))

    # fresh workflow, restore the epoch-2 snapshot, continue to epoch 4.
    # Same seed: the snapshot stores training state, not the dataset — the
    # loader must reload identical data (reference semantics; synthetic
    # data is seed-derived, a real-file loader would reread the files).
    w_res = build(4, fused=fused, seed=77)
    meta = restore_state(w_res, str(snap2))
    assert meta["loader"]["epoch_number"] == 2
    w_res.run()
    res_hist = w_res.decision.metrics_history
    assert res_hist == full_hist, (res_hist, full_hist)
    # final weights identical too (stop() syncs fused device params back)
    w_full.stop()
    w_res.stop()
    np.testing.assert_array_equal(
        w_full.forwards[0].weights.map_read(),
        w_res.forwards[0].weights.map_read())


def test_snapshot_roundtrip_arrays(tmp_path):
    w = build(1)
    w.run()
    arrays, meta = collect_state(w)
    assert any(k.startswith("forward.0.weights") for k in arrays)
    assert any(k.startswith("gd.0.gradient_weights") for k in arrays)
    path = str(tmp_path / "s.npz")
    write_snapshot(path, arrays, meta)
    w2 = build(1, seed=9)
    restore_state(w2, path)
    np.testing.assert_array_equal(w2.forwards[0].weights.map_read(),
                                  arrays["forward.0.weights"])
    np.testing.assert_array_equal(
        w2.gds[0].gradient_weights.map_read(),
        arrays["gd.0.gradient_weights"])


@pytest.mark.parametrize("from_dev,to_dev", [(1, 8), (8, 1)])
def test_elastic_resume_across_mesh_sizes(tmp_path, cpu_devices, from_dev,
                                          to_dev):
    """SURVEY.md §6.3: the framework's answer to the reference's slave
    churn is snapshot -> restore onto a DIFFERENT mesh size -> continue.
    Params are stored as host arrays and re-placed on the target mesh, so
    the epoch metrics after resume must match an uninterrupted run (data
    parallelism is the same math at any mesh size)."""
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    # uninterrupted 4-epoch reference run (1-device mesh)
    prng.seed_all(77)
    w_full = StandardWorkflow(
        name="SnapTest", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": 4}, fused=True,
        mesh=data_parallel_mesh(1))
    w_full.initialize(device=XLADevice())
    w_full.run()
    full_hist = w_full.decision.metrics_history

    # full run on the source mesh, snapshotting every epoch; the epoch-2
    # snapshot is the "job killed mid-run" state an elastic restart sees
    prng.seed_all(77)
    w_a = StandardWorkflow(
        name="SnapTest", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": 4},
        snapshotter_config={"directory": str(tmp_path), "prefix": "e",
                            "only_improved": False, "keep_all": True},
        fused=True, mesh=data_parallel_mesh(from_dev))
    w_a.initialize(device=XLADevice())
    w_a.run()
    snap = tmp_path / "e_2.npz"
    assert snap.exists()

    # resume onto the TARGET mesh size and finish (same seed: the
    # synthetic dataset derives from it and is not part of the snapshot)
    prng.seed_all(77)
    w_b = StandardWorkflow(
        name="SnapTest", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": 4}, fused=True,
        mesh=data_parallel_mesh(to_dev))
    w_b.initialize(device=XLADevice())
    restore_state(w_b, str(snap))
    w_b.run()
    resumed = w_b.decision.metrics_history
    assert [h["metric_validation"] for h in resumed] == \
        [h["metric_validation"] for h in full_hist], (resumed, full_hist)
    w_full.stop()
    w_b.stop()
    np.testing.assert_allclose(w_b.forwards[0].weights.map_read(),
                               w_full.forwards[0].weights.map_read(),
                               rtol=1e-4, atol=1e-5)


def test_snapshot_kohonen_workflow(tmp_path):
    """Regression (r1 advisor): KohonenTrainer sits in ``forwards`` but has
    no ``bias`` — collect_state/restore_state must tolerate non-standard
    forwards instead of raising AttributeError."""
    from znicz_tpu.models import kohonen as kohonen_model

    prng.seed_all(23)
    w = kohonen_model.build(max_epochs=2, shape=(6, 6), n_train=200)
    w.initialize(device=XLADevice())
    w.run()
    arrays, meta = collect_state(w)
    assert "forward.0.weights" in arrays
    assert "forward.0.bias" not in arrays
    path = str(tmp_path / "som.npz")
    write_snapshot(path, arrays, meta)

    prng.seed_all(9)
    w2 = kohonen_model.build(max_epochs=2, shape=(6, 6), n_train=200)
    w2.initialize(device=XLADevice())
    restore_state(w2, path)
    np.testing.assert_array_equal(w2.trainer.weights.map_read(),
                                  arrays["forward.0.weights"])


def test_only_improved_and_latest_symlink(tmp_path):
    w = build(3, tmp_path, only_improved=True, keep_all=False)
    w.snapshotter.only_improved = True
    w.snapshotter.keep_all = False
    w.run()
    snaps = [f for f in os.listdir(tmp_path) if not f.endswith("latest.npz")]
    # non-improving epochs skipped + old snapshots pruned -> exactly one
    assert len(snaps) == 1, snaps
    latest = tmp_path / "t_latest.npz"
    if latest.exists():
        assert os.readlink(latest) == snaps[0]
