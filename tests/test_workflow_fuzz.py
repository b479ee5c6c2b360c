"""Composition fuzzing: random declarative layer stacks through
StandardWorkflow, asserting the fused one-XLA-program step produces the
SAME weight updates as the eager per-unit chain (autograd-composed
backward == hand-written backward) for arbitrary compositions — the
tier-2 analog of the per-op geometry fuzz.

Each example compiles a small program, so the example count is low; the
value is coverage of layer ADJACENCIES (conv->dropout->pool->fc etc.)
that the fixed model-zoo stacks never permute.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, XLADevice
from znicz_tpu.standard_workflow import StandardWorkflow

SETTINGS = dict(max_examples=8, deadline=None, derandomize=True)

HYPER = {"learning_rate": 0.05, "gradient_moment": 0.5,
         "weights_decay": 1e-4}


@st.composite
def layer_stacks(draw):
    """A random (but always-valid) conv/pool/norm/fc stack ending in
    softmax, on an 8x8x2 input."""
    stack = []
    n_conv_blocks = draw(st.integers(0, 2))
    for _ in range(n_conv_blocks):
        kind = draw(st.sampled_from(["conv", "conv_relu", "conv_tanh",
                                     "conv_str"]))
        stack.append({"type": kind,
                      "->": {"n_kernels": draw(st.sampled_from([4, 8])),
                             "kx": 3, "ky": 3, "padding": (1, 1, 1, 1)},
                      "<-": dict(HYPER)})
        extra = draw(st.sampled_from(
            ["none", "max_pooling", "maxabs_pooling", "avg_pooling",
             "stochastic_pooling", "norm", "dropout"]))
        if extra in ("max_pooling", "maxabs_pooling", "avg_pooling",
                     "stochastic_pooling"):
            stack.append({"type": extra, "->": {"kx": 2, "ky": 2}})
        elif extra == "norm":
            stack.append({"type": "norm",
                          "->": {"alpha": 1e-4, "beta": 0.75, "k": 2.0,
                                 "n": 3}})
        elif extra == "dropout":
            stack.append({"type": "dropout", "->": {"dropout_ratio": 0.2}})
    n_fc = draw(st.integers(0, 2))
    for _ in range(n_fc):
        kind = draw(st.sampled_from(["all2all", "all2all_tanh",
                                     "all2all_relu", "all2all_str",
                                     "all2all_sigmoid"]))
        stack.append({"type": kind,
                      "->": {"output_sample_shape":
                             draw(st.sampled_from([8, 16]))},
                      "<-": dict(HYPER)})
    stack.append({"type": "softmax", "->": {"output_sample_shape": 3},
                  "<-": dict(HYPER)})
    seed = draw(st.integers(1, 2 ** 20))
    return stack, seed


def _build(stack, seed, fused=True):
    prng.seed_all(seed)
    return StandardWorkflow(
        name="fuzz", layers=[dict(d) for d in stack],
        loss_function="softmax", loader_name="synthetic_image",
        loader_config={"n_classes": 3, "sample_shape": (8, 8, 2),
                       "n_train": 24, "n_valid": 0, "minibatch_size": 12,
                       "spread": 2.0},
        decision_config={"max_epochs": 1}, fused=fused)


def _run_one_minibatch(w, fused):
    """The one-train-minibatch protocol shared by every fuzz test."""
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


def _one_step(stack, seed, fused, device):
    w = _build(stack, seed, fused)
    w.initialize(device=device)
    return _run_one_minibatch(w, fused)


@given(layer_stacks())
@settings(**SETTINGS)
def test_fused_matches_eager_for_random_stacks(case):
    stack, seed = case
    stochastic = any(d["type"] in ("dropout", "stochastic_pooling")
                     for d in stack)
    if stochastic:
        # dropout masks / stochastic-pool draws come from different PRNG
        # systems in the two execution shapes (host xorshift vs
        # counter-based) — exact update parity does not apply; instead
        # assert BOTH shapes actually trained: finite params that moved
        # from their init, captured AFTER initialize, BEFORE the step
        for fused, device in ((True, XLADevice()), (False, NumpyDevice())):
            w = _build(stack, seed, fused)
            w.initialize(device=device)
            init = [f.weights.map_read().copy() for f in w.forwards
                    if f.weights]
            _run_one_minibatch(w, fused)
            trained = [f.weights.map_read() for f in w.forwards
                       if f.weights]
            assert any(not np.array_equal(a, b)
                       for a, b in zip(init, trained)), fused
            for t in trained:
                assert np.isfinite(t).all(), fused
        return
    we = _one_step(stack, seed, False, NumpyDevice())
    wf = _one_step(stack, seed, True, XLADevice())
    checked = 0
    for i, (fe, ff) in enumerate(zip(we.forwards, wf.forwards)):
        if not fe.weights:
            continue
        np.testing.assert_allclose(
            ff.weights.map_read(), fe.weights.map_read(),
            rtol=2e-4, atol=2e-5,
            err_msg=f"layer {i} ({stack[i]['type']}) weights")
        np.testing.assert_allclose(
            ff.bias.map_read(), fe.bias.map_read(),
            rtol=2e-4, atol=2e-5,
            err_msg=f"layer {i} ({stack[i]['type']}) bias")
        checked += 1
    assert checked >= 1


@given(layer_stacks())
@settings(max_examples=4, deadline=None, derandomize=True)
def test_random_stacks_snapshot_roundtrip(case):
    """Any random stack snapshots and restores bit-exactly into a
    fresh differently-seeded workflow (the collect/restore contract
    holds for arbitrary compositions, not just the zoo models)."""
    import os
    import tempfile

    from znicz_tpu.snapshotter import (collect_state, restore_state,
                                       write_snapshot)

    stack, seed = case
    w = _one_step(stack, seed, True, XLADevice())
    arrays, meta = collect_state(w)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "s.npz")
        write_snapshot(path, arrays, meta)
        # fresh build, DIFFERENT seed: restore must overwrite everything
        w2 = _one_step(stack, seed + 1, True, XLADevice())
        restore_state(w2, path)
        w2.step.sync_to_units()
    for i, (fa, fb) in enumerate(zip(w.forwards, w2.forwards)):
        if fa.weights:
            np.testing.assert_array_equal(
                fb.weights.map_read(), fa.weights.map_read(),
                err_msg=f"layer {i} ({stack[i]['type']}) weights")
            np.testing.assert_array_equal(
                fb.bias.map_read(), fa.bias.map_read(),
                err_msg=f"layer {i} ({stack[i]['type']}) bias")


@st.composite
def ae_stacks(draw):
    """Random conv->deconv reconstruction geometry (kernel size, stride,
    kernel count, channels) — the deconv must exactly invert the conv's
    spatial map for the MSE-vs-input loss to typecheck."""
    k = draw(st.integers(2, 4))
    stride = draw(st.integers(1, 2))
    nk = draw(st.sampled_from([4, 8]))
    c = draw(st.integers(1, 2))
    # invertible geometry: (H - k) % stride == 0, else the conv drops
    # tail rows and the reconstruction cannot match the input shape
    H = k + stride * draw(st.integers(2, 4))
    lr = 0.002
    stack = [
        {"type": "conv", "->": {"n_kernels": nk, "kx": k, "ky": k,
                                "sliding": (stride, stride)},
         "<-": {"learning_rate": lr, "gradient_moment": 0.5}},
        {"type": "deconv", "->": {"n_kernels": nk, "kx": k, "ky": k,
                                  "sliding": (stride, stride),
                                  "n_channels": c},
         "<-": {"learning_rate": lr, "gradient_moment": 0.5}},
    ]
    seed = draw(st.integers(1, 2 ** 20))
    return stack, H, c, seed


@given(ae_stacks())
@settings(max_examples=6, deadline=None, derandomize=True)
def test_ae_fused_matches_eager_for_random_geometry(case):
    """Conv->deconv autoencoder: fused AD backward equals the
    hand-written GDDeconv/GDConv chain for random geometry (the adjoint
    pair composed end-to-end through the MSE evaluator)."""
    stack, H, c, seed = case

    def one_step(fused, device):
        prng.seed_all(seed)
        w = StandardWorkflow(
            name="aefuzz", layers=[dict(d) for d in stack],
            loss_function="mse", loader_name="synthetic_regression",
            loader_config={"sample_shape": (H, H, c), "identity": True,
                           "n_train": 24, "n_valid": 0,
                           "minibatch_size": 12},
            decision_config={"max_epochs": 1}, fused=fused)
        w.initialize(device=device)
        return _run_one_minibatch(w, fused)

    we = one_step(False, NumpyDevice())
    wf = one_step(True, XLADevice())
    for i, (fe, ff) in enumerate(zip(we.forwards, wf.forwards)):
        np.testing.assert_allclose(
            ff.weights.map_read(), fe.weights.map_read(),
            rtol=3e-4, atol=3e-5,
            err_msg=f"layer {i} ({stack[i]['type']}) weights")
        if fe.bias:          # deconv carries no bias; conv does
            np.testing.assert_allclose(
                ff.bias.map_read(), fe.bias.map_read(),
                rtol=3e-4, atol=3e-5,
                err_msg=f"layer {i} ({stack[i]['type']}) bias")

    # snapshot roundtrip holds for the MSE/deconv composition too
    import os
    import tempfile

    from znicz_tpu.snapshotter import (collect_state, restore_state,
                                       write_snapshot)

    arrays, meta = collect_state(wf)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "s.npz")
        write_snapshot(path, arrays, meta)
        w2 = one_step(True, XLADevice())
        restore_state(w2, path)
        w2.step.sync_to_units()
    for fa, fb in zip(wf.forwards, w2.forwards):
        np.testing.assert_array_equal(fb.weights.map_read(),
                                      fa.weights.map_read())


@given(layer_stacks())
@settings(max_examples=4, deadline=None, derandomize=True)
def test_pallas_engine_matches_xla_for_random_stacks(case):
    """root.common.engine.pallas must be output-preserving for arbitrary
    compositions, not just the fixed selection tests: the same random
    stack trained eagerly on the XLA paths and on the hand-written
    kernel paths (conv fwd + conv/deconv backward, interpret mode)
    produces the same weights."""
    from hypothesis import assume

    from znicz_tpu.core.config import root

    stack, seed = case
    # different PRNG systems — covered by the finite/moved fuzz; assume()
    # regenerates the example so the budget stays 4 real comparisons
    assume(not any(d["type"] in ("dropout", "stochastic_pooling")
                   for d in stack))

    def run(pallas):
        root.common.engine.pallas = pallas
        root.common.engine.pallas_interpret = pallas
        try:
            w = _build(stack, seed, fused=False)
            w.initialize(device=XLADevice())
            return _run_one_minibatch(w, fused=False)
        finally:
            root.common.engine.pallas = False
            root.common.engine.pallas_interpret = False

    base = run(False)
    pall = run(True)
    checked = 0
    for i, (fb, fp) in enumerate(zip(base.forwards, pall.forwards)):
        if not fb.weights:
            continue
        np.testing.assert_allclose(
            fp.weights.map_read(), fb.weights.map_read(),
            rtol=2e-4, atol=2e-5,
            err_msg=f"layer {i} ({stack[i]['type']}) weights")
        np.testing.assert_allclose(
            fp.bias.map_read(), fb.bias.map_read(),
            rtol=2e-4, atol=2e-5,
            err_msg=f"layer {i} ({stack[i]['type']}) bias")
        checked += 1
    assert checked >= 1


@st.composite
def fused_flag_combos(draw):
    """A random fused-step flag combination (sharding layout x optimizer
    x EMA x narrow momenta) for the quantized-collectives gate."""
    layout = draw(st.sampled_from(["replicated", "shard_update",
                                   "shard_params"]))
    optimizer = draw(st.sampled_from(["sgd", "adam"]))
    ema_decay = draw(st.sampled_from([None, 0.9]))
    state_dtype = (draw(st.sampled_from([None, "bfloat16"]))
                   if optimizer == "sgd" else None)   # SGD-only knob
    seed = draw(st.integers(1, 2 ** 20))
    return layout, optimizer, ema_decay, state_dtype, seed


@given(fused_flag_combos())
@settings(max_examples=4, deadline=None, derandomize=True)
def test_quantized_collectives_across_flag_combos(case):
    """ISSUE 18 gate: the quantized-collective codec composes with the
    whole fused-step flag surface — for random shard_update/
    shard_params/optimizer/ema/state_dtype combinations, mode=off stays
    BIT-IDENTICAL to a build that never passed the config, and
    int8+error-feedback trains within a pinned validation-error band of
    the exact run (the fused step's exact path already psums grads
    explicitly, so the quantized run differs by codec noise only)."""
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    layout, optimizer, ema_decay, state_dtype, seed = case
    flags = {"shard_update": layout == "shard_update",
             "shard_params": layout == "shard_params"}

    def run(qc):
        prng.seed_all(seed)
        w = build_fused(
            max_epochs=2, layers=(16,), minibatch_size=16,
            n_train=96, n_valid=32, mesh=data_parallel_mesh(4),
            optimizer=optimizer,
            optimizer_config=({"state_dtype": state_dtype}
                              if state_dtype else None),
            ema_decay=ema_decay, quantized_collectives=qc, **flags)
        w.initialize(device=XLADevice())
        w.run()
        return [h["metric_validation"]
                for h in w.decision.metrics_history]

    exact = run(None)
    assert run({"mode": "off"}) == exact, case
    quant = run({"mode": "int8", "chunk": 64, "error_feedback": True})
    assert len(quant) == len(exact), case
    band = max(3.0, 0.05 * 32)     # validation-error counts out of 32
    for e, q in zip(exact, quant):
        assert abs(e - q) <= band, (case, exact, quant)
