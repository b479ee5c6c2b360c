"""Kohonen SOM + RBM tests (SURVEY.md §3.1 kohonen/rbm rows): op-level
correctness, backend parity, and tier-2 sample convergence."""

import numpy as np
import jax.numpy as jnp

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.models import kohonen as kohonen_model, rbm as rbm_model
from znicz_tpu.ops import kohonen as k_ops
from znicz_tpu.units.kohonen import KohonenForward, KohonenTrainer


def test_kohonen_ops_winners_and_hits():
    w = np.array([[0.0, 0.0], [10.0, 10.0], [0.0, 10.0]], np.float32)
    x = np.array([[1.0, 1.0], [9.0, 9.0], [0.5, 9.5], [-1.0, 0.0]],
                 np.float32)
    idx = k_ops.winners(np, x, w)
    np.testing.assert_array_equal(idx, [0, 1, 2, 0])
    idx_x = np.asarray(k_ops.winners(jnp, jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_array_equal(idx_x, idx)
    np.testing.assert_array_equal(k_ops.hits(np, idx, 3), [2, 1, 1])
    np.testing.assert_array_equal(
        np.asarray(k_ops.hits(jnp, jnp.asarray(idx), 3)), [2, 1, 1])


def test_kohonen_update_moves_toward_data():
    coords = np.asarray(k_ops.grid_coords(np, 2, 2))
    w = np.zeros((4, 2), np.float32)
    x = np.full((8, 2), 4.0, np.float32)
    new_w, idx = k_ops.update(np, x, w, coords, alpha=0.1, sigma=1.0)
    # every neuron moves toward the data (winner most strongly)
    assert np.all(new_w > 0)
    d_before = np.abs(w - 4.0).sum()
    d_after = np.abs(new_w - 4.0).sum()
    assert d_after < d_before


def test_kohonen_trainer_backend_parity():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 3)).astype(np.float32)
    outs = []
    for device in (NumpyDevice(), XLADevice()):
        prng.seed_all(9)
        w = Workflow(name="t")
        tr = KohonenTrainer(w, shape=(3, 3))
        tr.input = Array(x.copy())
        tr.batch_size = 16
        tr.initialize(device=device)
        tr.run()
        outs.append((tr.weights.map_read().copy(),
                     tr.winners.map_read().copy()))
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


def test_kohonen_forward_hits_accumulate():
    prng.seed_all(4)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(10, 2)).astype(np.float32)
    w = Workflow(name="t")
    tr = KohonenTrainer(w, shape=(2, 2))
    tr.input = Array(x)
    tr.initialize(device=NumpyDevice())
    fwd = KohonenForward(w, shape=(2, 2))
    fwd.input = Array(x)
    fwd.weights = tr.weights
    fwd.batch_size = 10
    fwd.initialize(device=NumpyDevice())
    fwd.run()
    assert fwd.hits.sum() == 10
    fwd.run()
    assert fwd.hits.sum() == 20


def test_kohonen_demo_workflow_organizes():
    prng.seed_all(23)
    w = kohonen_model.build(max_epochs=6, shape=(6, 6), n_train=400)
    w.initialize(device=XLADevice())
    w.run()
    dec = w.decision
    assert bool(dec.complete)
    deltas = [h["metric_train"] for h in dec.metrics_history]
    assert deltas[-1] < deltas[0], deltas
    # the map must separate the 4 clusters onto distinct winners
    data = w.loader.original_data.map_read()
    labels = w.loader.original_labels.map_read()
    weights = w.trainer.weights.map_read()
    centroids = np.stack([data[labels == c].mean(axis=0) for c in range(4)])
    win = k_ops.winners(np, centroids.reshape(4, -1), weights)
    assert len(set(win.tolist())) == 4, win


def test_rbm_workflow_reconstruction_improves():
    prng.seed_all(11)
    w = rbm_model.build(max_epochs=6)
    w.initialize(device=XLADevice())
    w.run()
    dec = w.decision
    assert bool(dec.complete)
    hist = [h["metric_validation"] for h in dec.metrics_history]
    assert hist[-1] < hist[0], hist


def test_kohonen_scan_epoch_matches_eager():
    """Epoch-scan mode (one compiled dispatch per class pass) trains to
    the same weights and reports the same |ΔW| trajectory as the
    per-minibatch path — same seed, same data, same step order."""
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.models.kohonen import build

    runs = {}
    for mode in ("eager", "scan"):
        prng.seed_all(77)
        root.common.engine.scan_epoch = (mode == "scan")
        try:
            w = build(max_epochs=4, shape=(6, 6), minibatch_size=40,
                      n_train=200, sample_shape=(3,), min_delta=0.0)
            w.initialize(device=XLADevice())
            w.run()
        finally:
            root.common.engine.scan_epoch = False
        runs[mode] = {
            "weights": np.asarray(w.trainer.weights.map_read()).copy(),
            "deltas": [h["metric_train"] for h in
                       w.decision.metrics_history],
        }
        if mode == "scan":
            assert w.trainer._scan_fn is not None   # mode actually on
    np.testing.assert_allclose(runs["scan"]["weights"],
                               runs["eager"]["weights"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(runs["scan"]["deltas"],
                               runs["eager"]["deltas"], rtol=1e-4)


def test_kohonen_scan_min_delta_still_stops():
    """The Decision's |ΔW| convergence stop keeps working in scan mode
    (the pre-pass weight snapshot keeps the metric honest)."""
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.models.kohonen import build

    prng.seed_all(5)
    root.common.engine.scan_epoch = True
    try:
        w = build(max_epochs=50, shape=(4, 4), minibatch_size=50,
                  n_train=100, sample_shape=(2,), alpha=0.05,
                  radius_decay=0.5, min_delta=0.2)
        w.initialize(device=XLADevice())
        w.run()
    finally:
        root.common.engine.scan_epoch = False
    # must stop on the delta criterion well before max_epochs, with a
    # real (nonzero) first-epoch delta
    hist = [h["metric_train"] for h in w.decision.metrics_history]
    assert hist[0] > 0.01, hist
    assert len(hist) < 50, len(hist)


def test_kohonen_scan_midpass_falls_back_to_eager():
    """A class pass entered mid-way (restored loader state after resume)
    must still train: the scan guard only fires at offset 0, so the
    remainder of the pass goes through the per-minibatch path."""
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.models.kohonen import build

    prng.seed_all(21)
    root.common.engine.scan_epoch = True
    try:
        w = build(max_epochs=3, shape=(4, 4), minibatch_size=25,
                  n_train=100, sample_shape=(2,), min_delta=0.0)
        w.initialize(device=XLADevice())
        assert w.trainer._scan_fn is not None
        # simulate a resume that landed mid-pass: advance the loader two
        # minibatches without letting the trainer see them
        w.loader.run()
        w.loader.run()
        assert int(w.loader.minibatch_offset) > 0
        w0 = np.asarray(w.trainer.weights.map_read()).copy()
        w.trainer.run()          # mid-pass -> eager fallback, must train
        w1 = np.asarray(w.trainer.weights.map_read())
        assert np.abs(w1 - w0).max() > 0, "mid-pass minibatch not trained"
        assert not w.trainer._scan_in_flight
    finally:
        root.common.engine.scan_epoch = False
