"""The index-fed mode of FusedTrainStep pins the full batch once, in the
form the step consumes (``_pin_dataset`` / ``_gather_batch``): in the
compute dtype and in rows whose gather needs no relayout, so that the
step's program touches the rows it gathers and nothing else of the
dataset.  On the CPU the compute dtype is float32; the tests force
bfloat16 the way a TPU reports it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.config import root
from znicz_tpu.models import mnist_fc
from znicz_tpu.parallel.step import pinned_row_shape
from znicz_tpu.standard_workflow import StandardWorkflow

HYPER = {"learning_rate": 0.01, "gradient_moment": 0.9}


def _fc():
    """28x28 rows: under one (8, 128) tile, pinned flat."""
    return mnist_fc.build_fused(max_epochs=1, n_train=192, n_valid=0,
                                minibatch_size=32)


def _conv():
    """26x26x3 = 2,028 values a row: pinned as (16, 128), 20 padded."""
    return StandardWorkflow(
        name="PinConv",
        layers=[
            {"type": "conv_str", "->": {"n_kernels": 4, "kx": 3, "ky": 3,
                                        "sliding": (2, 2)},
             "<-": dict(HYPER)},
            {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": dict(HYPER)},
        ],
        loader_name="synthetic_image",
        loader_config={"sample_shape": (26, 26, 3), "n_classes": 4,
                       "n_train": 96, "n_valid": 0, "minibatch_size": 32},
        decision_config={"max_epochs": 1}, fused=True)


def _mse():
    """An autoencoder: the second pinned array is the targets."""
    return StandardWorkflow(
        name="PinAE",
        layers=[
            {"type": "conv", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
             "<-": dict(HYPER)},
            {"type": "deconv", "->": {"n_kernels": 4, "kx": 3, "ky": 3,
                                      "n_channels": 1},
             "<-": dict(HYPER)},
        ],
        loss_function="mse", loader_name="synthetic_regression",
        loader_config={"sample_shape": (8, 8, 1), "identity": True,
                       "n_train": 96, "n_valid": 0, "minibatch_size": 32},
        decision_config={"max_epochs": 1}, fused=True)


BUILDERS = {"fc": _fc, "conv": _conv, "mse": _mse}


def _initialized(kind, dtype, limit=None):
    prev = root.common.engine.get("dataset_on_device_max_bytes", 1 << 30)
    if limit is not None:
        root.common.engine.dataset_on_device_max_bytes = limit
    try:
        prng.seed_all(26)
        w = BUILDERS[kind]()
        w.step.compute_dtype = dtype
        w.initialize(device=XLADevice())
    finally:
        root.common.engine.dataset_on_device_max_bytes = prev
    return w


def _host_arrays(w, kind):
    loader = w.loader
    second = loader.original_targets if kind == "mse" else \
        loader.original_labels
    return np.asarray(loader.original_data.mem), np.asarray(second.mem)


@pytest.mark.parametrize("n_values, row", [
    (227 * 227 * 3, (1208, 128)),     # AlexNet: 37 values of padding
    (32 * 32 * 3, (24, 128)),         # whole tiles as it is
    (26 * 26 * 3, (16, 128)),
    (28 * 28, (28 * 28,)),            # under a tile: padding costs 31 %
    (1025, (1025,)),                  # a tile and a bit: would double
    (4, (4,)),
])
def test_pinned_row_shape(n_values, row):
    assert pinned_row_shape(n_values) == row
    assert np.prod(row) >= n_values
    if len(row) == 2:
        assert row[0] % 8 == 0 and (np.prod(row) - n_values) * 8 <= n_values


@pytest.mark.parametrize("kind", ["fc", "conv", "mse"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_pinned_arrays_dtype_and_values(kind, dtype):
    """(a), (d): the data is pinned in the compute dtype, rounded as the
    step's own cast rounds it; labels and MSE targets stay as they are."""
    w = _initialized(kind, dtype)
    data, second = w.step._dataset_dev
    host, host_second = _host_arrays(w, kind)
    n, n_values = len(host), int(np.prod(host.shape[1:]))
    assert data.dtype == dtype
    assert data.shape == (n, *pinned_row_shape(n_values))
    assert w.step._sample_shape == host.shape[1:]
    want = np.asarray(jnp.asarray(host).astype(dtype)).reshape(n, -1)
    got = np.asarray(data).reshape(n, -1)
    np.testing.assert_array_equal(got[:, :n_values], want)
    assert not got[:, n_values:].any()
    assert second.dtype == host_second.dtype
    assert second.dtype == (np.float32 if kind == "mse" else np.int32)
    np.testing.assert_array_equal(np.asarray(second), host_second)


def _copies(tree):
    return jax.tree.map(jnp.copy, tree)


@pytest.mark.parametrize("kind", ["fc", "conv", "mse"])
def test_index_fed_steps_equal_host_fed_bitwise(kind):
    """(b): three index-fed train steps in bfloat16 give the losses and
    parameters of the same steps fed from the host, which cast after the
    gather; so does the evaluation program."""
    w = _initialized(kind, jnp.bfloat16)
    st = w.step
    data, second = st._dataset_dev
    host, host_second = _host_arrays(w, kind)
    mb = int(w.loader.max_minibatch_size)
    mask = np.ones(mb, bool)
    hyper = st._hyper_device()
    p_idx, k_idx = _copies(st._params), jnp.copy(st._key)
    p_host, k_host = _copies(st._params), jnp.copy(st._key)
    for step in range(3):
        idx = np.arange(step * mb, (step + 1) * mb, dtype=np.int32)
        p_idx, k_idx, m_idx = st._train_fn_idx(
            p_idx, k_idx, hyper, data, second, idx, mask)
        p_host, k_host, m_host = st._train_fn(
            p_host, k_host, hyper, host[idx], host_second[idx], mask)
        for name in m_idx:
            np.testing.assert_array_equal(
                np.asarray(m_idx[name]), np.asarray(m_host[name]),
                err_msg=f"step {step} metric {name}")
        assert np.isfinite(float(m_idx["loss"]))
    for a, b in zip(jax.tree.leaves(p_idx), jax.tree.leaves(p_host)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    e_idx = st._eval_fn_idx(p_idx, data, second, idx, mask)
    e_host = st._eval_fn(p_host, host[idx], host_second[idx], mask)
    for name in e_idx:
        np.testing.assert_array_equal(np.asarray(e_idx[name]),
                                      np.asarray(e_host[name]))


_SUB_JAXPRS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _converts_downstream(jaxpr, tainted, until, walked):
    """``convert_element_type`` equations applied to a value derived from
    the variables ``tainted``, walking into nested jaxprs; derivation
    stops at the primitives ``until`` (the first layer's own work).
    Appends the name of every equation it walks to ``walked``; returns
    ``(equations found, tainted variables)``."""
    found, tainted = [], set(tainted)
    for eqn in jaxpr.eqns:
        hit = [i for i, v in enumerate(eqn.invars)
               if not isinstance(v, Literal) and v in tainted]
        if not hit:
            continue
        walked.append(eqn.primitive.name)
        if eqn.primitive.name == "convert_element_type":
            found.append(eqn)
        if eqn.primitive.name in until:
            continue
        sub = next((eqn.params[k] for k in _SUB_JAXPRS
                    if k in eqn.params), None)
        inner = getattr(sub, "jaxpr", sub)
        if inner is None or len(inner.invars) != len(eqn.invars):
            tainted.update(eqn.outvars)
            continue
        sub_found, sub_tainted = _converts_downstream(
            inner, {inner.invars[i] for i in hit}, until, walked)
        found += sub_found
        tainted.update(o for o, io in zip(eqn.outvars, inner.outvars)
                       if io in sub_tainted)
    return found, tainted


@pytest.mark.parametrize("kind", ["fc", "conv"])
def test_no_cast_of_the_dataset_or_the_gathered_rows(kind):
    """(c): on this path the step's cast is the identity: the traced
    program converts neither the pinned array nor anything gathered
    from it before the first layer consumes it.  (Pinned in float32,
    the cast stood after the gather, for the compiler to hoist.)"""
    w = _initialized(kind, jnp.bfloat16)
    st = w.step
    data, labels = st._dataset_dev
    mb = int(w.loader.max_minibatch_size)
    args = (st._params, st._key, st._hyper_device(), data, labels,
            np.arange(mb, dtype=np.int32), np.ones(mb, bool))
    closed = jax.make_jaxpr(st._train_fn_idx._fn)(*args)
    flat_index = len(jax.tree.leaves(args[:3]))
    data_var = closed.jaxpr.invars[flat_index]
    assert data_var.aval.shape == data.shape
    assert data_var.aval.dtype == jnp.bfloat16
    walked = []
    found, _ = _converts_downstream(
        closed.jaxpr, {data_var},
        ("conv_general_dilated", "dot_general"), walked)
    assert "gather" in walked and walked[-1] in (
        "conv_general_dilated", "dot_general"), walked
    assert not found, [str(e) for e in found]


def test_the_walk_finds_a_cast_after_a_gather():
    """The yardstick of (c), on the form the parent pinned."""
    def parent_form(data, idx):
        return jnp.dot(data[idx].astype(jnp.bfloat16),
                       jnp.ones((4, 2), jnp.bfloat16))
    closed = jax.make_jaxpr(parent_form)(
        jnp.zeros((8, 4), jnp.float32), jnp.arange(2))
    walked = []
    found, _ = _converts_downstream(
        closed.jaxpr, {closed.jaxpr.invars[0]}, ("dot_general",), walked)
    assert len(found) == 1 and walked[-1] == "dot_general", walked


@pytest.mark.parametrize("dtype, pinned", [(jnp.bfloat16, True),
                                           (jnp.float32, False)],
                         ids=["bf16", "f32"])
def test_gate_counts_the_bytes_that_stay_pinned(dtype, pinned):
    """(e): a dataset whose float32 bytes pass the gate and whose
    bfloat16 bytes do not is pinned only when the step computes in
    bfloat16; padding counts (the conv rows are pinned 20 wider)."""
    rows, row = 96, pinned_row_shape(26 * 26 * 3)
    limit = rows * int(np.prod(row)) * 2
    assert rows * 26 * 26 * 3 * 2 < limit < rows * 26 * 26 * 3 * 4
    w = _initialized("conv", dtype, limit=limit)
    assert (w.step._dataset_dev is not None) == pinned
    assert bool(w.loader.serve_indices_only) == pinned
    if pinned:
        assert w.step._dataset_dev[0].nbytes == limit
        under = _initialized("conv", dtype, limit=limit - 1)
        assert under.step._dataset_dev is None


def test_pin_logs_once_what_it_pinned(caplog):
    """The mechanism's counter: one line a run, rows, dtype and bytes."""
    import logging
    with caplog.at_level(logging.INFO):
        w = _initialized("conv", jnp.bfloat16)
    lines = [r.getMessage() for r in caplog.records
             if "pinned the dataset on the device" in r.getMessage()]
    assert len(lines) == 1, lines
    data = w.step._dataset_dev[0]
    assert f"{len(data)} rows" in lines[0]
    assert "bfloat16[96, 16, 128]" in lines[0]
    assert f"{data.nbytes} bytes" in lines[0]
