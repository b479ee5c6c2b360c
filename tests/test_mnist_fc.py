"""Tier-2 functional test: the minimum end-to-end slice (SURVEY.md §8 step 2)
— an MNIST-shaped FC workflow (All2AllTanh -> All2AllSoftmax ->
EvaluatorSoftmax -> DecisionGD -> GDSoftmax -> GDTanh) converging under the
Repeater loop, deterministic across runs with the same seed.

Wiring mirrors the reference call stack (SURVEY.md §4.1):
Repeater -> Loader -> forwards -> Evaluator -> Decision -> gds (reverse) ->
Repeater, with end_point gated on ~decision.complete and gds skipped on
non-train minibatches.
"""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.core.mutable import Bool
from znicz_tpu.core.plumbing import Repeater
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.synthetic import SyntheticClassifierLoader
from znicz_tpu.units.all2all import All2AllSoftmax, All2AllTanh
from znicz_tpu.units.decision import DecisionGD
from znicz_tpu.units.evaluator import EvaluatorSoftmax
from znicz_tpu.units.gd import GDSoftmax, GDTanh
from znicz_tpu.units.nn_units import NNWorkflow


def build_fc_workflow(max_epochs=4, lr=0.05):
    w = NNWorkflow(name="MnistFC")
    w.repeater = Repeater(w)
    loader = w.loader = SyntheticClassifierLoader(
        w, n_classes=10, sample_shape=(28, 28), n_train=600, n_valid=200,
        minibatch_size=50, spread=2.5, noise=1.0)
    fc1 = All2AllTanh(w, output_sample_shape=64, name="fc1")
    fc2 = All2AllSoftmax(w, output_sample_shape=10, name="fc2")
    w.forwards = [fc1, fc2]
    ev = w.evaluator = EvaluatorSoftmax(w)
    dec = w.decision = DecisionGD(w, max_epochs=max_epochs)
    gd2 = GDSoftmax(w, learning_rate=lr, gradient_moment=0.9, name="gd2")
    gd1 = GDTanh(w, learning_rate=lr, gradient_moment=0.9, name="gd1")
    w.gds = [gd1, gd2]

    # control chain (reference §4.1 hot loop)
    w.repeater.link_from(w.start_point)
    loader.link_from(w.repeater)
    fc1.link_from(loader)
    fc2.link_from(fc1)
    ev.link_from(fc2)
    dec.link_from(ev)
    gd2.link_from(dec)
    gd1.link_from(gd2)
    w.repeater.link_from(gd1)
    # end after the full backward chain so the last minibatch is symmetric
    w.end_point.link_from(gd1)
    w.end_point.gate_block = ~dec.complete

    # gradient units run on train minibatches only
    for gd in (gd1, gd2):
        gd.gate_skip = Bool(lambda: int(loader.minibatch_class) != TRAIN)

    # data links
    fc1.link_attrs(loader, ("input", "minibatch_data"))
    fc2.link_attrs(fc1, ("input", "output"))
    ev.link_attrs(fc2, "output", "max_idx")
    ev.link_attrs(loader, ("labels", "minibatch_labels"),
                  ("batch_size", "minibatch_size"))
    dec.link_attrs(loader, "minibatch_class", "last_minibatch",
                   "class_lengths", "epoch_number", "minibatch_size")
    dec.link_attrs(ev, ("minibatch_n_err", "n_err"))
    dec.evaluator = ev
    gd2.link_from_forward(fc2)
    gd2.link_attrs(ev, "err_output")
    gd2.link_attrs(loader, ("batch_size", "minibatch_size"))
    gd1.link_from_forward(fc1)
    gd1.link_attrs(gd2, ("err_output", "err_input"))
    gd1.link_attrs(loader, ("batch_size", "minibatch_size"))
    return w


def run_workflow(device, seed=123, max_epochs=4):
    prng.seed_all(seed)
    w = build_fc_workflow(max_epochs=max_epochs)
    w.initialize(device=device)
    w.run()
    return w


@pytest.mark.parametrize("device_cls", [NumpyDevice, XLADevice])
def test_fc_workflow_converges(device_cls):
    w = run_workflow(device_cls())
    dec = w.decision
    assert bool(dec.complete)
    assert len(dec.metrics_history) == 4
    # synthetic blobs are nearly separable: validation error must collapse
    first = dec.metrics_history[0]["metric_validation"]
    last = dec.metrics_history[-1]["metric_validation"]
    assert last < first, (first, last)
    assert dec.epoch_n_err_pt[1] < 15.0, dec.metrics_history


def test_fc_workflow_deterministic():
    h1 = run_workflow(XLADevice(), seed=7, max_epochs=2)
    h2 = run_workflow(XLADevice(), seed=7, max_epochs=2)
    assert h1.decision.metrics_history == h2.decision.metrics_history
    np.testing.assert_array_equal(h1.forwards[0].weights.map_read(),
                                  h2.forwards[0].weights.map_read())


def test_fc_workflow_backends_agree():
    """numpy oracle vs XLA backend: same seed, same epoch error counts
    (float32 GEMM on CPU-XLA matches numpy within integer-count tolerance)."""
    h_np = run_workflow(NumpyDevice(), seed=11, max_epochs=2)
    h_x = run_workflow(XLADevice(), seed=11, max_epochs=2)
    for m_np, m_x in zip(h_np.decision.metrics_history,
                         h_x.decision.metrics_history):
        assert abs(m_np["metric_validation"] - m_x["metric_validation"]) <= 2


def test_evaluator_class_weights_scale_err_output():
    """class_weights scales each err_output row by its TRUE class's
    weight; n_err stays the unweighted count (reference semantics)."""
    from znicz_tpu.core.workflow import Workflow

    w = Workflow(name="cw")
    y = np.array([[0.7, 0.2, 0.1],
                  [0.1, 0.8, 0.1],
                  [0.3, 0.3, 0.4]], np.float32)
    labels = np.array([0, 2, 2], np.int32)
    weights = np.array([1.0, 1.0, 3.0], np.float32)

    def build_eval(**kw):
        ev = EvaluatorSoftmax(w, compute_confusion_matrix=False, **kw)
        ev.output.mem = y.copy()
        ev.labels.mem = labels.copy()
        ev.batch_size = 3
        ev.initialize(device=NumpyDevice())
        ev.run()
        return ev

    plain = build_eval()
    weighted = build_eval(class_weights=weights)
    scale = weights[labels][:, None]
    np.testing.assert_allclose(weighted.err_output.mem,
                               plain.err_output.mem * scale, rtol=1e-6)
    assert weighted.n_err == plain.n_err == 1


def test_class_weights_fused_matches_eager():
    """One weighted TRAIN minibatch through the eager unit chain and the
    fused AD step must produce identical weight updates — the class
    weighting enters via err_output scaling in one and via the loss term
    in the other."""
    from znicz_tpu.standard_workflow import StandardWorkflow

    cw = [0.5, 2.0, 1.0]
    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
         "<-": {"learning_rate": 0.1, "gradient_moment": 0.0}},
        {"type": "softmax", "->": {"output_sample_shape": 3},
         "<-": {"learning_rate": 0.1, "gradient_moment": 0.0}},
    ]
    loader_cfg = {"n_classes": 3, "sample_shape": (8,), "n_train": 60,
                  "n_valid": 0, "minibatch_size": 30, "spread": 2.0}

    def one_step(fused, device):
        prng.seed_all(123)
        w = StandardWorkflow(
            name="CW", layers=[dict(d) for d in layers],
            loss_function="softmax",
            evaluator_config={"class_weights": cw},
            loader_name="synthetic_classifier", loader_config=loader_cfg,
            decision_config={"max_epochs": 1}, fused=fused)
        w.initialize(device=device)
        w.loader.run()
        if fused:
            w.step.run()
            w.step.sync_to_units()
        else:
            for f in w.forwards:
                f.run()
            w.evaluator.run()
            for gd in reversed(w.gds):
                gd.run()
        return w

    we = one_step(False, NumpyDevice())
    wf = one_step(True, XLADevice())
    for i, (fe, ff) in enumerate(zip(we.forwards, wf.forwards)):
        np.testing.assert_allclose(
            ff.weights.map_read(), fe.weights.map_read(),
            rtol=1e-4, atol=1e-5, err_msg=f"layer {i} weights")
        np.testing.assert_allclose(
            ff.bias.map_read(), fe.bias.map_read(),
            rtol=1e-4, atol=1e-5, err_msg=f"layer {i} bias")
    # and the weighting really changed the update (vs unweighted run)
    prng.seed_all(123)
    w0 = StandardWorkflow(
        name="CW0", layers=[dict(d) for d in layers],
        loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=loader_cfg,
        decision_config={"max_epochs": 1}, fused=True)
    w0.initialize(device=XLADevice())
    w0.loader.run()
    w0.step.run()
    w0.step.sync_to_units()
    assert not np.allclose(w0.forwards[-1].weights.map_read(),
                           wf.forwards[-1].weights.map_read())


def test_class_weights_misconfiguration_fails_loudly():
    """Wrong-length weight vectors and misplaced/typo'd evaluator_config
    keys must raise, not train silently unweighted (XLA's clamped gather
    would otherwise hide both)."""
    import pytest

    from znicz_tpu.standard_workflow import StandardWorkflow

    layers = [{"type": "softmax", "->": {"output_sample_shape": 3},
               "<-": {"learning_rate": 0.1}}]
    cfg = {"n_classes": 3, "sample_shape": (6,), "n_train": 30,
           "n_valid": 0, "minibatch_size": 10}

    with pytest.raises(ValueError, match="not accepted"):
        StandardWorkflow(
            name="bad-key", layers=[dict(d) for d in layers],
            loss_function="softmax",
            evaluator_config={"class_weight": [1, 1, 1]},   # typo'd key
            loader_name="synthetic_classifier", loader_config=dict(cfg))

    prng.seed_all(5)
    w = StandardWorkflow(
        name="bad-len", layers=[dict(d) for d in layers],
        loss_function="softmax",
        evaluator_config={"class_weights": [1.0, 2.0]},     # 2 for 3
        loader_name="synthetic_classifier", loader_config=dict(cfg))
    with pytest.raises(ValueError, match="entries"):
        w.initialize(device=NumpyDevice())


def test_fused_confusion_matrix_matches_eager():
    """Fused workflows tally the same per-class-pass confusion matrixes
    the eager evaluator produces (Decision owns collection + reset)."""
    from znicz_tpu.loader.base import TRAIN, VALID
    from znicz_tpu.standard_workflow import StandardWorkflow

    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 12},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.0}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.0}},
    ]
    cfg = {"n_classes": 4, "sample_shape": (6,), "n_train": 80,
           "n_valid": 40, "minibatch_size": 20, "spread": 1.5}

    def run(fused, device):
        prng.seed_all(44)
        w = StandardWorkflow(
            name="conf", layers=[dict(d) for d in layers],
            loss_function="softmax", loader_name="synthetic_classifier",
            loader_config=dict(cfg), decision_config={"max_epochs": 1},
            fused=fused)
        w.initialize(device=device)
        w.run()
        return w

    we = run(False, NumpyDevice())
    wf = run(True, XLADevice())
    for cls in (VALID, TRAIN):
        me = we.decision.confusion_matrixes[cls]
        mf = wf.decision.confusion_matrixes[cls]
        assert me is not None and mf is not None
        expected = cfg["n_train"] if cls == TRAIN else cfg["n_valid"]
        assert me.sum() == expected
        # column sums = per-class label counts: data-determined, exact on
        # any backend; cell values may differ by boundary-sample flips
        # between numpy and XLA float trajectories (precedent:
        # test_fc_workflow_backends_agree's +/-2 tolerance)
        np.testing.assert_array_equal(mf.sum(axis=0), me.sum(axis=0),
                                      err_msg=f"class {cls} label counts")
        assert np.abs(mf - me).sum() <= 4, (cls, mf, me)


def test_fused_confusion_matrix_survives_midpass_flush():
    """A probe calling flush_metrics() mid class pass must not
    double-count the already-published minibatches (deferred mode keeps
    cumulative sums; only the delta may fold in)."""
    from znicz_tpu.loader.base import TRAIN
    from znicz_tpu.standard_workflow import StandardWorkflow

    layers = [{"type": "softmax", "->": {"output_sample_shape": 3},
               "<-": {"learning_rate": 0.05}}]
    cfg = {"n_classes": 3, "sample_shape": (5,), "n_train": 60,
           "n_valid": 0, "minibatch_size": 20, "spread": 2.0}
    prng.seed_all(11)
    w = StandardWorkflow(
        name="flush", layers=layers, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=dict(cfg),
        decision_config={"max_epochs": 1}, fused=True)
    w.initialize(device=XLADevice())
    # run the pass by hand, flushing after every minibatch
    while True:
        w.loader.run()
        w.step.run()
        w.step.flush_metrics()
        w.step.flush_metrics()      # repeated probe: still no double count
        if bool(w.loader.last_minibatch):
            break
    w.decision.run()
    mat = w.decision.confusion_matrixes[TRAIN]
    assert mat is not None and mat.sum() == cfg["n_train"], mat


def test_evaluator_mse_nearest_target_unit():
    """Direct nearest-target check: hand-set prototypes, outputs nearer
    the wrong prototype count as errors; padded rows do not."""
    from znicz_tpu.core.workflow import Workflow
    from znicz_tpu.units.evaluator import EvaluatorMSE

    w = Workflow(name="nt")
    ev = EvaluatorMSE(w)
    protos = np.array([[0.0, 0.0], [10.0, 10.0]], np.float32)
    ev.output.mem = np.array([[0.1, 0.2],     # -> proto 0, label 0: ok
                              [9.0, 9.5],     # -> proto 1, label 0: ERR
                              [9.9, 9.9],     # padded row: would be an
                              ], np.float32)  # error if mask broke
    ev.target.mem = protos[[0, 0, 1]]
    # padded row's label DISAGREES with its nearest prototype, so a
    # batch_size-mask regression flips n_err to 2
    ev.labels.mem = np.array([0, 0, 0], np.int32)
    ev.class_targets.mem = protos
    ev.batch_size = 2
    ev.initialize(device=NumpyDevice())
    ev.run()
    assert ev._classifies
    assert ev.n_err == 1
    assert ev.rmse > 0.0
