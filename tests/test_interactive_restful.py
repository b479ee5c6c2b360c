"""Interactive (stream-fed) loader, RESTful prediction serving, and the
DeviceBenchmark utility (SURVEY.md §3.3 Loaders ``interactive.py``/
``restful.py`` rows; §3.3 Accelerated units ``DeviceBenchmark`` row)."""

import json
import urllib.request

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.interactive import InteractiveLoader


def make_interactive(**kwargs):
    prng.seed_all(31)
    w = Workflow(name="t")
    loader = InteractiveLoader(w, sample_shape=(6,), n_classes=3, **kwargs)
    loader.initialize(device=NumpyDevice())
    return loader


def test_interactive_loader_serves_fed_samples():
    loader = make_interactive(capacity=32, minibatch_size=8)
    rng = np.random.default_rng(0)
    data = rng.normal(size=(8, 6)).astype(np.float32)
    labels = np.arange(8, dtype=np.int32) % 3
    assert loader.feed(data, labels) == 8

    loader.run()
    assert loader.minibatch_class == TRAIN
    assert loader.minibatch_size == 8
    # every served row must be one of the fed samples with its label
    served = loader.minibatch_data.mem
    served_labels = loader.minibatch_labels.mem
    for row, lab in zip(served, served_labels):
        match = np.where((data == row).all(axis=1))[0]
        assert len(match) >= 1
        assert lab == labels[match[0]]


def test_interactive_loader_ring_wraps_and_grows():
    loader = make_interactive(capacity=16, minibatch_size=4)
    a = np.full((2, 6), 1.0, np.float32)
    loader.feed(a, np.zeros(2, np.int32))
    loader.run()
    assert set(np.unique(loader.minibatch_data.mem)) == {1.0}
    # feeding more samples makes them visible to later minibatches
    b = np.full((14, 6), 2.0, np.float32)
    loader.feed(b, np.ones(14, np.int32))
    assert loader.available == 16
    seen = set()
    for _ in range(8):
        loader.run()
        seen |= set(np.unique(loader.minibatch_data.mem))
    assert seen == {1.0, 2.0}


def test_interactive_loader_rejects_shape_and_empty():
    loader = make_interactive(capacity=8, minibatch_size=4)
    try:
        loader.feed(np.zeros((2, 5), np.float32))
        raise AssertionError("shape mismatch accepted")
    except ValueError:
        pass
    try:
        loader.run()
        raise AssertionError("served before any feed")
    except RuntimeError:
        pass


def test_interactive_online_training_learns(tmp_path):
    """Online training: a fused workflow trains on streamed batches."""
    from znicz_tpu.standard_workflow import StandardWorkflow

    prng.seed_all(17)
    w = StandardWorkflow(
        name="Online", loss_function="softmax",
        layers=[{"type": "all2all_tanh", "->": {"output_sample_shape": 16}},
                {"type": "softmax", "->": {"output_sample_shape": 3}}],
        loader_name="interactive",
        loader_config={"sample_shape": (6,), "n_classes": 3,
                       "capacity": 96, "minibatch_size": 24},
        decision_config={"max_epochs": 6})
    rng = np.random.default_rng(5)
    centers = rng.normal(0, 2.0, (3, 6)).astype(np.float32)
    labels = rng.integers(0, 3, 96).astype(np.int32)
    data = centers[labels] + rng.normal(0, 0.3, (96, 6)).astype(np.float32)
    w.loader.feed(data, labels)
    w.initialize(device=XLADevice())
    w.run()
    hist = w.decision.metrics_history
    assert hist[-1]["metric_train"] < hist[0]["metric_train"]


def _train_tiny_exported(tmp_path):
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.utils.export import ExportedForward, export_forward

    prng.seed_all(23)
    w = StandardWorkflow(
        name="Srv", loss_function="softmax",
        layers=[{"type": "all2all_tanh", "->": {"output_sample_shape": 8}},
                {"type": "softmax", "->": {"output_sample_shape": 3}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 3, "sample_shape": (6,), "n_train": 60,
                       "n_valid": 0, "minibatch_size": 20},
        decision_config={"max_epochs": 1})
    w.initialize(device=XLADevice())
    w.run()
    pkg = str(tmp_path / "srv.npz")
    export_forward(w, pkg)
    return ExportedForward(pkg), pkg


def test_prediction_server_serves_exported_model(tmp_path):
    from znicz_tpu.loader.restful import PredictionServer

    model, pkg = _train_tiny_exported(tmp_path)
    server = PredictionServer(pkg, max_batch=16)
    port = server.start()
    try:
        x = np.random.default_rng(1).normal(size=(4, 6)).astype(np.float32)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"input": x.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            out = np.asarray(json.loads(r.read())["output"])
        np.testing.assert_allclose(out, model(x), rtol=1e-5, atol=1e-6)
        # metadata endpoint reports the package and request count
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=5) as r:
            meta = json.loads(r.read())
        assert meta["model"]["name"] == "Srv"
        assert meta["n_requests"] == 1
        # malformed request -> 400, not a crash
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict", data=b"{}",
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(bad, timeout=5)
            raise AssertionError("malformed request accepted")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
    finally:
        server.stop()


def test_device_benchmark_reports_throughput():
    from znicz_tpu.core.accelerated_units import DeviceBenchmark

    result = DeviceBenchmark(size=128, reps=2).run(device=XLADevice())
    assert result["gflops"] > 0
    assert result["size"] == 128
