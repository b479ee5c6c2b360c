"""Tier-2 sample-zoo tests: each models/ entry builds, trains a few epochs
on TPU/XLA, and the metric history matches EXACT pinned seeded values —
the reference's functional tests pin integer error counts the same way
(SURVEY.md §5 tier-2).  Any numeric drift in ops, loaders, PRNG streams or
the fused step fails these, not just "did it improve".

Values were captured on the virtual-CPU platform (tests/conftest.py) —
the platform every CI run uses — with f32 compute (the fused step's CPU
dtype), so they are bit-stable run to run.
"""

import time

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.models import (alexnet, autoencoder, cifar_conv, mnist_conv,
                              wine)


def _train(build, seed=31, **kw):
    prng.seed_all(seed)
    w = build(**kw)
    w.initialize(device=XLADevice())
    w.run()
    assert bool(w.decision.complete)
    return w.decision.metrics_history


def _validation(hist):
    return [int(h["metric_validation"]) for h in hist]


def test_wine_sample():
    hist = _train(wine.build, max_epochs=10)
    assert _validation(hist) == [19, 0, 0, 0, 0, 0, 0, 0, 0, 0], hist
    assert int(hist[0]["metric_train"]) == 8, hist


def test_mnist_conv_sample():
    hist = _train(mnist_conv.build, max_epochs=3, n_train=300, n_valid=100,
                  minibatch_size=50)
    assert _validation(hist) == [94, 92, 90], hist
    assert [int(h["metric_train"]) for h in hist] == [268, 256, 263], hist


def test_cifar_conv_sample():
    hist = _train(cifar_conv.build, max_epochs=3, n_train=300, n_valid=100,
                  minibatch_size=50)
    assert _validation(hist) == [92, 90, 88], hist
    assert [int(h["metric_train"]) for h in hist] == [267, 271, 278], hist


def test_autoencoder_sample():
    hist = _train(autoencoder.build, max_epochs=4, n_train=200, n_valid=64,
                  sample_shape=(12, 12, 1))
    np.testing.assert_allclose(
        [h["metric_validation"] for h in hist],
        [1.2079215, 0.39782357, 0.32945922, 0.25455874],
        rtol=1e-5, err_msg=str(hist))


def test_alexnet_sample():
    """Shrunk AlexNet (67px input, soft dropout, separable data) must
    collapse validation error within 5 epochs — the north-star workflow's
    functional pin (BASELINE.md config 3)."""
    hist = _train(alexnet.build, seed=1, max_epochs=5, minibatch_size=50,
                  n_classes=10, input_size=67, n_train=300, n_valid=100,
                  lr=0.003, dropout=0.2, loader_config={"spread": 2.0})
    assert _validation(hist) == [90, 73, 38, 0, 0], hist


def test_mnist_conv_reaches_two_percent():
    """BASELINE.md config 2 ("MNIST-conv wall-clock to 99%") at CI scale:
    the full IDX pipeline at n_train=2000 must reach <= 2% validation
    error (10 of 500) within 12 epochs, wall-clock reported.  The early
    epochs are pinned exactly; the tail is thresholded (it sits at the
    scale of single samples)."""
    t0 = time.time()
    hist = _train(mnist_conv.build, max_epochs=12, n_train=2000,
                  n_valid=500, minibatch_size=100)
    wall = time.time() - t0
    val = _validation(hist)
    assert val[:6] == [451, 443, 411, 315, 228, 128], hist
    assert val[-1] <= 10, hist
    print(f"\nmnist_conv to {val[-1]}/500 errors in {len(hist)} epochs, "
          f"{wall:.1f}s wall")


def test_run_load_main_shape():
    """Samples expose the reference's run(load, main) CLI contract."""
    built = {}

    def load(builder, **kw):
        prng.seed_all(1)
        built["w"] = builder(max_epochs=1, n_train=60, n_valid=30,
                             minibatch_size=10, **kw)

    def main():
        built["w"].initialize(device=XLADevice())
        built["w"].run()

    wine.run(load, main)
    assert bool(built["w"].decision.complete)


def test_approximator_sample():
    """Function-approximation MSE workflow (reference: Approximator
    sample): validation mse must follow the pinned seeded trajectory."""
    from znicz_tpu.models import approximator

    prng.seed_all(31)
    w = approximator.build(max_epochs=5)
    w.initialize(device=XLADevice())
    w.run()
    np.testing.assert_allclose(
        [h["metric_validation"] for h in w.decision.metrics_history],
        [2.572527, 0.283226, 0.18658, 0.079837, 0.054828],
        rtol=1e-4, err_msg=str(w.decision.metrics_history))


def test_approximator_nearest_target_classification():
    """prototypes=P: nearest-target n_err (reference: the approximator
    samples' classification metric) on both eager backends AND the fused
    step (which recovers labels as the target's nearest prototype), and
    training drives it to zero."""
    from znicz_tpu.core.backends import NumpyDevice
    from znicz_tpu.models import approximator

    for device_cls in (NumpyDevice, XLADevice):
        prng.seed_all(31)
        w = approximator.build(max_epochs=5, prototypes=5, fused=False)
        w.initialize(device=device_cls())
        assert w.evaluator.class_targets.shape == (5, 4)
        w.run()
        assert w.evaluator._classifies
        assert isinstance(w.evaluator.n_err, int)
        assert w.evaluator.n_err == 0, device_cls  # final batch classified
    eager_hist = [h["metric_validation"]
                  for h in w.decision.metrics_history]

    prng.seed_all(31)
    wf = approximator.build(max_epochs=5, prototypes=5)   # fused default
    wf.initialize(device=XLADevice())
    wf.run()
    np.testing.assert_allclose(
        [h["metric_validation"] for h in wf.decision.metrics_history],
        eager_hist, rtol=1e-4)
    # deferred metrics: step.n_err is the LAST CLASS PASS's summed
    # nearest-target errors (400 train samples) — near-converged, a
    # handful at most, vs ~320 for an untrained net
    assert isinstance(wf.step.n_err, int)
    assert wf.step.n_err <= 10, wf.step.n_err


def test_fused_nearest_target_skipped_for_noisy_targets():
    """The fused label-recovery shortcut only engages when targets are
    PROVEN to be exact prototype rows; a loader with noisy targets must
    not emit a silently-wrong fused n_err."""
    from znicz_tpu.models import approximator

    prng.seed_all(31)
    w = approximator.build(max_epochs=1, prototypes=5)
    w.initialize(device=XLADevice())
    # sabotage one stored target AFTER load: recovery assumption broken
    w.loader.original_targets.map_write()[0, 0] += 0.25
    assert not w.step._nt_recovery_valid()
    w.run()
    assert w.step.n_err == 0        # metric absent, attr untouched

    prng.seed_all(31)
    w2 = approximator.build(max_epochs=1, prototypes=5)
    w2.initialize(device=XLADevice())
    assert w2.step._nt_recovery_valid()   # pristine loader: proven exact


def test_tv_channels_sample():
    """TvChannels sample: corner-logo identification with the Cutter
    cropping the logo region before the conv stack (the unit's first
    model-zoo consumer).  Pinned seeded trajectory."""
    from znicz_tpu.models import tv_channels

    prng.seed_all(31)
    w = tv_channels.build(max_epochs=6)
    w.initialize(device=XLADevice())
    w.run()
    assert _validation(w.decision.metrics_history) == \
        [176, 178, 82, 37, 0, 0], w.decision.metrics_history
    assert w.forwards[0].output.shape == (50, 10, 10, 3)   # cropped


def test_tv_channels_eager_gd_cutter():
    """The eager chain routes gradients through GDCutter (zero-padding
    the cropped err back into frame geometry) and still converges."""
    from znicz_tpu.core.backends import NumpyDevice
    from znicz_tpu.models import tv_channels

    prng.seed_all(31)
    w = tv_channels.build(max_epochs=8, n_train=400, n_valid=100,
                          lr=0.05, fused=False)
    w.initialize(device=NumpyDevice())
    w.run()
    val = _validation(w.decision.metrics_history)
    assert val == [84, 88, 78, 10, 25, 16, 2, 0], val


def test_image_ae_sample():
    """ImagenetAE analog: conv->deconv reconstruction over the image-FILE
    pipeline (decode -> normalize -> identity targets), pinned seeded
    trajectory."""
    from znicz_tpu.models import image_ae

    prng.seed_all(31)
    w = image_ae.build(max_epochs=6)
    w.initialize(device=XLADevice())
    w.run()
    np.testing.assert_allclose(
        [h["metric_validation"] for h in w.decision.metrics_history],
        [0.086547, 0.034062, 0.022606, 0.021269, 0.009212, 0.008824],
        rtol=1e-4, err_msg=str(w.decision.metrics_history))
    # identity-target contract: the arrays the pinned path consumes...
    np.testing.assert_array_equal(w.loader.original_targets.mem,
                                  w.loader.original_data.mem)
    # ...and the eager fill path's served copy (drive one fill directly)
    w.loader.serve_indices_only = False
    w.loader.fill_minibatch()
    assert np.any(w.loader.minibatch_data.mem)
    np.testing.assert_array_equal(w.loader.minibatch_targets.mem,
                                  w.loader.minibatch_data.mem)


def test_deep_autoencoder_sample():
    """ImagenetAE-scale builder (BASELINE.md config 4 at representative
    geometry): strided conv pyramid mirrors back to the input shape and
    the reconstruction improves over epochs.  (Exact pin omitted: this
    builder's bench geometry is 64x64x3 — the test uses a shrunk variant
    and pins the trend plus the round-trip shape contract.)"""
    prng.seed_all(7)
    w = autoencoder.build_deep(max_epochs=3, minibatch_size=16,
                               sample_shape=(16, 16, 3),
                               n_kernels=(8, 16), n_train=64)
    w.initialize(device=XLADevice())
    w.run()
    hist = w.decision.metrics_history
    assert w.forwards[-1].output.shape[1:] == (16, 16, 3)
    spatial = [f.output.shape[1] for f in w.forwards]
    assert spatial == [8, 4, 8, 16], spatial      # halve, halve, mirror
    assert hist[-1]["metric_train"] < hist[0]["metric_train"], hist


def test_mnist_conv_bf16_convergence_pin():
    """Tier-2 convergence under the bf16 precision policy (VERDICT r3
    weak #4): the SAME seeded MNIST-conv run as the 2%-test, forced
    through compute_dtype=bfloat16, with its own pinned early trajectory
    and converged tail — so a precision-policy regression (e.g. an
    accumulation silently moved to bf16) fails CI as a degraded
    converged metric, not just a loose "tracks f32" check.

    A bf16 trajectory is deterministic on one installation and moves in
    the last digits between jaxlib versions, so the early counts are
    held to what it can promise across them: the first epoch's count
    equal (it precedes any rounding that differs), each of the next five
    within 3 % of the pin, the sequence falling.  Readings:
    jax 0.4.37 (when the pin was written) [451, 446, 411, 322, 227, 129];
    jax 0.9.0                              [451, 445, 410, 319, 232, 126]."""
    import jax.numpy as jnp

    prng.seed_all(31)
    w = mnist_conv.build(max_epochs=12, minibatch_size=100, n_train=2000,
                         n_valid=500)
    w.step.compute_dtype = jnp.bfloat16
    w.initialize(device=XLADevice())
    w.run()
    val = [int(h["metric_validation"]) for h in w.decision.metrics_history]
    # f32 pin for the same seed/config: [451, 443, 411, 315, 228, 128]
    pin = [451, 446, 411, 322, 227, 129]
    assert val[0] == pin[0], val
    np.testing.assert_allclose(val[1:6], pin[1:], rtol=0.03, err_msg=str(val))
    assert all(a > b for a, b in zip(val[:5], val[1:6])), val
    assert val[-1] <= 10, val    # converged: <= 2% of 500
