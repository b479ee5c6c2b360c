"""Tests for the aux-unit long tail (SURVEY.md §3.1): LR schedules,
rollback, mean/disp normalization, cutter, resizable FC, zero-filling."""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.standard_workflow import StandardWorkflow
from znicz_tpu.units.cutter import Cutter, GDCutter
from znicz_tpu.units.lr_adjust import (ArbitraryStepPolicy, ExpPolicy,
                                       InvPolicy, LearningRateAdjust,
                                       StepExpPolicy)
from znicz_tpu.units.mean_disp_normalizer import MeanDispNormalizer
from znicz_tpu.units.nn_rollback import NNRollback
from znicz_tpu.units.resizable_all2all import ResizableAll2All
from znicz_tpu.units.weights_zerofilling import ZeroFiller


def test_lr_policies():
    assert ExpPolicy(0.5)(1.0, 2) == 0.25
    assert abs(InvPolicy(1.0, 1.0)(1.0, 1) - 0.5) < 1e-9
    assert StepExpPolicy(0.1, 10)(1.0, 25) == pytest.approx(0.01)
    pol = ArbitraryStepPolicy([(0.1, 2), (0.01, 3)])
    assert [pol(1.0, i) for i in range(7)] == \
        [0.1, 0.1, 0.01, 0.01, 0.01, 0.01, 0.01]


def test_lr_adjust_mutates_gds():
    class FakeGD:
        learning_rate = 0.1
        learning_rate_bias = 0.2

    gd = FakeGD()
    adj = LearningRateAdjust(None, lr_policy=ExpPolicy(0.5))
    adj.add_gd_unit(gd)
    adj.run()
    assert gd.learning_rate == 0.1
    adj.run()
    assert gd.learning_rate == 0.05
    assert gd.learning_rate_bias == 0.1


def test_lr_adjust_in_training_loop():
    """Schedule takes effect inside the fused step (no recompile needed)."""
    prng.seed_all(12)
    w = StandardWorkflow(
        name="LRTest",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16,
                 "<-": {"learning_rate": 0.1}},
                {"type": "softmax", "output_sample_shape": 4,
                 "<-": {"learning_rate": 0.1}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 4, "sample_shape": (8,), "n_train": 80,
                       "n_valid": 0, "minibatch_size": 20},
        decision_config={"max_epochs": 3})
    adj = LearningRateAdjust(w, lr_policy=ExpPolicy(0.5), by_epoch=True)
    adj.decision = w.decision
    for gd in w.gds:
        adj.add_gd_unit(gd)
    # wire into the loop: decision -> adj -> repeater
    w.repeater.links_from.clear()
    w.decision.links_to.remove(w.repeater)
    adj.link_from(w.decision)
    w.repeater.link_from(adj)
    w.initialize(device=XLADevice())
    w.run()
    # epochs 1 and 2 end with an adjustment (iterations 0, 1); the walk
    # stops at end_point on epoch 3's completion before the adjuster fires
    assert w.gds[0].learning_rate == pytest.approx(0.1 * 0.5)


def test_mean_disp_normalizer_backends():
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(6, 4, 4, 2)) * 3 + 1).astype(np.float32)
    outs = []
    for device in (NumpyDevice(), XLADevice()):
        w = Workflow(name="t")
        u = MeanDispNormalizer(w)
        u.input = Array(x.copy())
        u.fit(x)
        u.initialize(device=device)
        u.run()
        outs.append(u.output.map_read())
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)
    # normalized range is within [-1, 1] per feature by construction
    assert np.abs(outs[0]).max() <= 1.0 + 1e-5


def test_cutter_and_gd():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 8, 8, 3)).astype(np.float32)
    for device in (NumpyDevice(), XLADevice()):
        w = Workflow(name="t")
        cut = Cutter(w, offset=(2, 1), size=(4, 5))
        cut.input = Array(x.copy())
        cut.initialize(device=device)
        cut.run()
        np.testing.assert_array_equal(cut.output.map_read(),
                                      x[:, 2:6, 1:6, :])
        gd = GDCutter(w)
        gd.link_from_forward(cut)
        err = rng.normal(size=cut.output.shape).astype(np.float32)
        gd.err_output = Array(err)
        gd.initialize(device=device)
        gd.run()
        ein = gd.err_input.map_read()
        np.testing.assert_array_equal(ein[:, 2:6, 1:6, :], err)
        assert ein.sum() == pytest.approx(err.sum(), rel=1e-6)


def test_resizable_all2all():
    prng.seed_all(3)
    rng = np.random.default_rng(2)
    x = rng.normal(size=(4, 6)).astype(np.float32)
    w = Workflow(name="t")
    u = ResizableAll2All(w, output_sample_shape=5)
    u.input = Array(x)
    u.initialize(device=XLADevice())
    u.run()
    w_before = u.weights.map_read().copy()
    y_before = u.output.map_read().copy()
    u.resize(8)
    u.run()
    assert u.output.shape == (4, 8)
    np.testing.assert_array_equal(u.weights.map_read()[:, :5], w_before)
    np.testing.assert_allclose(u.output.map_read()[:, :5], y_before,
                               rtol=1e-5, atol=1e-6)
    u.resize(3)
    u.run()
    assert u.output.shape == (4, 3)
    np.testing.assert_array_equal(u.weights.map_read(), w_before[:, :3])


def test_zero_filler():
    prng.seed_all(4)
    rng = np.random.default_rng(5)
    w = Workflow(name="t")
    u = ResizableAll2All(w, output_sample_shape=4)
    u.input = Array(rng.normal(size=(2, 6)).astype(np.float32))
    u.initialize(device=NumpyDevice())
    mask = np.ones((6, 4), np.float32)
    mask[2:4, :] = 0.0
    zf = ZeroFiller(w)
    zf.add_target(u, mask)
    zf.run()
    assert np.all(u.weights.map_read()[2:4, :] == 0.0)
    assert np.all(u.weights.map_read()[0] != 0.0)
    with pytest.raises(ValueError):
        zf.add_target(u, np.ones((3, 3)))


def test_nn_rollback_restores_and_cuts_lr():
    prng.seed_all(6)
    w = StandardWorkflow(
        name="RbTest",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 8,
                 "<-": {"learning_rate": 0.1}},
                {"type": "softmax", "output_sample_shape": 3,
                 "<-": {"learning_rate": 0.1}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 3, "sample_shape": (6,), "n_train": 60,
                       "n_valid": 30, "minibatch_size": 10},
        decision_config={"max_epochs": 2})
    w.initialize(device=XLADevice())
    w.run()
    rb = NNRollback(w, lr_cut=0.5, fail_iterations=1)
    rb.link_workflow_state(w)
    # simulate: improvement -> store
    w.decision.epoch_ended.set(True)
    w.decision.improved.set(True)
    rb.run()
    good = w.forwards[0].weights.map_read().copy()
    # corrupt weights, then a failing epoch triggers restore + lr cut
    w.step.sync_to_units()
    w.forwards[0].weights.map_invalidate()
    w.forwards[0].weights.mem = np.full_like(good, np.nan)
    w.step._params = w.step.gather_params()
    w.decision.improved.set(False)
    rb.run()
    assert rb.rollback_count == 1
    np.testing.assert_array_equal(w.forwards[0].weights.map_read(), good)
    assert w.gds[0].learning_rate == pytest.approx(0.05)
    # training continues from the restored state: one more fused step
    # runs with the CUT learning rate (hyper cache re-reads the gd units)
    import jax

    assert float(jax.device_get(
        w.step._hyper_device()[0]["lr"])) == pytest.approx(0.05)
    w.loader.run()
    w.step.run()
    w.step.flush_metrics()
    assert np.isfinite(w.step.loss)
    w.step.sync_to_units()
    assert np.isfinite(w.forwards[0].weights.map_read()).all()


# -- diversity diagnostic (SURVEY §3.1) --------------------------------------

def test_diversity_groups_duplicate_kernels():
    from znicz_tpu.units.diversity import (Diversity, get_similar_kernels,
                                           kernels_of, similarity_matrix)

    rng = np.random.default_rng(4)
    w = rng.normal(size=(6, 20)).astype(np.float32)
    w[3] = w[0] * 2.0 + 0.1          # correlated with kernel 0
    w[5] = w[2] * 0.5                # correlated with kernel 2
    sim = similarity_matrix(w)
    np.testing.assert_allclose(np.diag(sim), 1.0, rtol=1e-5)
    groups = get_similar_kernels(w, threshold=0.95)
    assert [0, 3] in groups and [2, 5] in groups
    assert get_similar_kernels(rng.normal(size=(6, 20)), 0.95) == []


def test_diversity_unit_reports_on_workflow():
    from znicz_tpu.core.workflow import Workflow
    from znicz_tpu.units.all2all import All2All
    from znicz_tpu.units.diversity import Diversity
    from znicz_tpu.core.memory import Array

    prng.seed_all(8)
    w = Workflow(name="d")
    fc = All2All(w, output_sample_shape=8)
    fc.input = Array()
    fc.input.mem = np.zeros((4, 10), np.float32)
    fc.initialize(device=NumpyDevice())
    # plant duplicates: two output kernels share a column direction
    wm = fc.weights.map_read().copy()
    wm[:, 5] = wm[:, 1] * 3.0
    fc.weights.map_invalidate()
    fc.weights.mem = wm
    unit = Diversity(w, threshold=0.95).link_forwards([fc])
    unit.run()
    assert 0 in unit.report
    assert [1, 5] in unit.report[0]


# -- publishing (SURVEY §3.3) ------------------------------------------------

def test_publisher_markdown_and_html(tmp_path):
    from znicz_tpu.models import wine
    from znicz_tpu.utils.publishing import Publisher

    prng.seed_all(3)
    w = wine.build(max_epochs=2, n_train=60, n_valid=30, minibatch_size=10)
    w.initialize(device=XLADevice())
    w.run()
    md = Publisher(backend="markdown",
                   directory=str(tmp_path)).publish(w)
    text = open(md).read()
    assert "training report" in text
    assert "metric_validation" in text
    assert "Timing" in text and "Config" in text
    assert str(int(w.decision.best_metric)) in text
    ht = Publisher(backend="html", directory=str(tmp_path)).publish(w)
    html_text = open(ht).read()
    assert html_text.startswith("<!doctype html>")
    assert "metric_validation" in html_text


def test_cli_publish_flag(tmp_path, monkeypatch):
    import textwrap
    from znicz_tpu.__main__ import main as cli_main

    wf = tmp_path / "wf.py"
    wf.write_text(textwrap.dedent("""
        from znicz_tpu.models import wine
        def run(load, main):
            load(wine.build, max_epochs=1, n_train=60, n_valid=30,
                 minibatch_size=10)
            main()
        """))
    monkeypatch.chdir(tmp_path)
    rc = cli_main([str(wf), "--publish", "markdown", "-d", "auto",
                   "--random-seed", "4"])
    assert rc == 0
    assert (tmp_path / "winedemo_report.md").exists() or \
        any(p.suffix == ".md" for p in tmp_path.iterdir()), \
        list(tmp_path.iterdir())
