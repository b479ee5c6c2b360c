"""Tier-2 tests for the declarative StandardWorkflow builder (SURVEY.md §2
L7): layers=[{...}] -> full training graph, both execution shapes (fused
one-XLA-program and eager per-unit), softmax and mse losses."""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.loader.base import get_loader
from znicz_tpu.standard_workflow import StandardWorkflow


CONV_LAYERS = [
    {"type": "conv_relu", "->": {"n_kernels": 8, "kx": 3, "ky": 3,
                                 "padding": (1, 1, 1, 1)},
     "<-": {"learning_rate": 0.03, "gradient_moment": 0.9}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "all2all_tanh", "->": {"output_sample_shape": 32},
     "<-": {"learning_rate": 0.03, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 5},
     "<-": {"learning_rate": 0.03, "gradient_moment": 0.9}},
]

IMAGE_LOADER = {"n_classes": 5, "sample_shape": (12, 12, 3), "n_train": 250,
                "n_valid": 100, "minibatch_size": 50, "spread": 2.5,
                "noise": 1.0}


def build_conv(fused, max_epochs=3, seed=21):
    prng.seed_all(seed)
    w = StandardWorkflow(
        name="ConvStd", layers=CONV_LAYERS, loss_function="softmax",
        loader_name="synthetic_image", loader_config=IMAGE_LOADER,
        decision_config={"max_epochs": max_epochs}, fused=fused)
    w.initialize(device=XLADevice())
    w.run()
    return w


@pytest.mark.parametrize("fused", [True, False])
def test_conv_standard_workflow_converges(fused):
    w = build_conv(fused)
    dec = w.decision
    assert bool(dec.complete)
    assert len(dec.metrics_history) == 3
    first = dec.metrics_history[0]["metric_validation"]
    last = dec.metrics_history[-1]["metric_validation"]
    assert last < first, dec.metrics_history
    assert dec.epoch_n_err_pt[1] < 20.0, dec.metrics_history


def test_fused_and_eager_shapes_agree():
    """Both execution shapes, same seed: error trajectories in the same
    ballpark (backward math identity is pinned per-op elsewhere; here we
    check the builder wired both graphs correctly)."""
    w_f = build_conv(True, max_epochs=2, seed=33)
    w_e = build_conv(False, max_epochs=2, seed=33)
    # identical init: same seed -> same first-epoch forward weights
    np.testing.assert_array_equal(w_f.forwards[0].weights.map_read().shape,
                                  w_e.forwards[0].weights.map_read().shape)
    for m_f, m_e in zip(w_f.decision.metrics_history,
                        w_e.decision.metrics_history):
        assert abs(m_f["metric_validation"] - m_e["metric_validation"]) <= 8, \
            (w_f.decision.metrics_history, w_e.decision.metrics_history)


@pytest.mark.parametrize("fused", [True, False])
def test_mse_standard_workflow(fused):
    prng.seed_all(5)
    w = StandardWorkflow(
        name="RegStd",
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 24},
             "<-": {"learning_rate": 0.02, "gradient_moment": 0.9}},
            {"type": "all2all", "->": {"output_sample_shape": 4},
             "<-": {"learning_rate": 0.02, "gradient_moment": 0.9}},
        ],
        loss_function="mse", loader_name="synthetic_regression",
        loader_config={"sample_shape": (16,), "target_shape": (4,),
                       "n_train": 256, "n_valid": 64, "minibatch_size": 32},
        decision_config={"max_epochs": 3}, fused=fused)
    w.initialize(device=XLADevice())
    w.run()
    dec = w.decision
    assert bool(dec.complete)
    first = dec.metrics_history[0]["metric_validation"]
    last = dec.metrics_history[-1]["metric_validation"]
    assert last < first * 0.9, dec.metrics_history


def test_flat_shorthand_and_registry():
    assert get_loader("synthetic_classifier").LOADER_NAME == \
        "synthetic_classifier"
    with pytest.raises(KeyError):
        get_loader("nope")
    prng.seed_all(3)
    w = StandardWorkflow(
        name="Flat",
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16,
                 "<-": {"learning_rate": 0.1}},
                {"type": "softmax", "output_sample_shape": 10}],
        loader_name="synthetic_classifier",
        loader_config={"minibatch_size": 20, "n_train": 100, "n_valid": 0},
        decision_config={"max_epochs": 1})
    w.initialize(device=XLADevice())
    w.run()
    assert bool(w.decision.complete)
    assert w.forwards[0].output_sample_shape == (16,)


def test_bad_specs_raise():
    with pytest.raises(KeyError):
        StandardWorkflow(layers=[{"type": "wat"}],
                         loader_name="synthetic_classifier")
    with pytest.raises(ValueError):
        StandardWorkflow(
            layers=[{"type": "all2all", "output_sample_shape": 4}],
            loss_function="softmax", loader_name="synthetic_classifier")
    with pytest.raises(ValueError):
        StandardWorkflow(layers=[], loader_name="synthetic_classifier")
