"""The state-space scan's Pallas kernels (``ops/pallas/ssd.py``, called
from ``parallel/ssm.py::ssd``), interpreted on the CPU: against the literal
recurrence (the benchmark's plain references walk the positions one by one)
and against ``ssm.py``'s ``jax.numpy`` form, in values and every gradient;
a faulty carry against the same tolerance; each refusal by name; the gauge
that says which form a step's state-space layers got.  Both benchmark
cells' steps compiled at their real widths for a described TPU v5e, with
the kernels in them, are ``tests/test_checkpoint_plan.py``'s, which compiles
those two steps anyway: one compile a cell serves both files' questions."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import nemotron_h as ref                     # noqa: E402

from test_lfm2_arch import _pallas_interpret                # noqa: E402
from znicz_tpu.ops.pallas import ssd as pssd                # noqa: E402
from znicz_tpu.parallel import ssm, transformer as tfm      # noqa: E402
from znicz_tpu.parallel.mesh import make_mesh               # noqa: E402

STATE = 128


def _operands(seed, t, heads, pd, groups, dtype=jnp.float32, rows=2,
              dt_most=0.2, a_most=16.0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(rows, t, heads, pd)).astype(np.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(dt_most), (rows, t, heads))
                ).astype(np.float32)
    a = -r.uniform(a_most / 16, a_most, heads).astype(np.float32)
    bm, cm = (r.normal(size=(rows, t, groups, STATE)).astype(np.float32)
              for _ in range(2))
    d = r.normal(size=heads).astype(np.float32)
    return (jnp.asarray(x, dtype), jnp.asarray(dt), jnp.asarray(a),
            jnp.asarray(bm, dtype), jnp.asarray(cm, dtype), jnp.asarray(d))


def _scan(ops, chunk):
    """``ssm.ssd`` on operands whose ``B`` and ``C`` carry a group axis
    (one group goes in without it, as the mixer hands it over)."""
    x, dt, a, bm, cm, d = ops
    if bm.shape[2] == 1:
        bm, cm = bm[:, :, 0], cm[:, :, 0]
    return ssm.ssd(x, dt, a, bm, cm, d, chunk)


def _numpy_form(ops, chunk):
    with _pallas_interpret(False):
        return _scan(ops, chunk)


def _literal(x, dt, a, bm, cm, d):
    ys, lasts = zip(*(ref.recurrence(x[r], dt[r], a, bm[r], cm[r], d)
                      for r in range(x.shape[0])))
    return jnp.stack(ys), jnp.stack(lasts)


def _functional(form, w, w_last):
    """A random functional of the scan's two results, so that both
    cotangents the backward kernel takes are exercised."""
    def f(*ops):
        y, last = form(ops)
        return (y.astype(jnp.float32) * w).sum() + (last * w_last).sum()
    return f


def _rel(got, want):
    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# -- (a) values and gradients -------------------------------------------------

@pytest.mark.parametrize("groups,heads,t,chunk,dtype", [
    (1, 8, 256, 128, "float32"),      # one group, two chunks a row
    (8, 64, 256, 128, "float32"),     # eight groups of eight heads
    (1, 16, 512, 256, "float32"),     # two blocks of heads share B and C
    (8, 64, 300, 128, "float32"),     # the last chunk is filled (300 = 2 *
                                      # 128 + 44)
    (1, 8, 128, 256, "float32"),      # the chunk is the whole row
    (1, 16, 300, 256, "bfloat16"),    # 16-bit operands, a filled chunk
    (8, 64, 256, 128, "bfloat16"),
])
def test_the_kernels_are_the_literal_recurrence_and_the_numpy_form(
        groups, heads, t, chunk, dtype):
    """``ssm.ssd`` on the kernels (interpreted) against ``lax.scan`` over the
    positions and against the ``jax.numpy`` form: ``y``, the state behind the
    last position and the gradient of a random functional of both in every
    operand (``x``, ``dt``, ``A``, ``B``, ``C``, ``D``).  In float32 at the
    tolerance ``test_chunked_scan_is_the_literal_recurrence_in_values_and_
    gradients`` uses, 5e-5 (rounding: a decay is the exp of a difference of
    two running sums; the three cotangents a head and a position leave the
    backward kernel as sums of two or three 16-bit terms).  In bfloat16 the
    two forms round the same products, so ``y`` and the state are equal to a
    rounding of ``y`` and the gradients stand as near the float32 literal's
    as the ``jax.numpy`` form's do (the sums differ in order, and ``d cs``
    takes ``G`` rounded where AD takes it whole)."""
    dtype = jnp.dtype(dtype)
    ops = _operands(5, t, heads, 16, groups, dtype)
    r = np.random.default_rng(6)
    w = jnp.asarray(r.normal(size=ops[0].shape).astype(np.float32))
    w_last = jnp.asarray(r.normal(size=(2, heads, 16, STATE)
                                  ).astype(np.float32))
    args = range(6)
    with jax.default_matmul_precision("highest"):
        with _pallas_interpret(True):
            assert ssm.scan_kernel_refusal(
                t, heads, 16, STATE, groups, chunk, dtype.itemsize,
                True) is None
            y, last = _scan(ops, chunk)
            got = jax.grad(_functional(lambda o: _scan(o, chunk), w, w_last),
                           argnums=args)(*ops)
        y_np, last_np = _numpy_form(ops, chunk)
        by_np = jax.grad(_functional(lambda o: _numpy_form(o, chunk), w,
                                     w_last), argnums=args)(*ops)
        whole = tuple(v.astype(jnp.float32) for v in ops)
        want_y, want_last = _literal(*whole)
        want = jax.grad(_functional(lambda o: _literal(*o), w, w_last),
                        argnums=args)(*whole)
    scale = float(jnp.abs(want_y).max())
    names = "x dt A B C D".split()
    if dtype == jnp.float32:
        assert float(jnp.abs(y - want_y).max()) < 5e-5 * scale
        np.testing.assert_allclose(last, want_last, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(last, last_np, rtol=2e-5, atol=2e-6)
        for name, g, g_np, g_want in zip(names, got, by_np, want):
            assert _rel(g, g_want) < 5e-5, (name, _rel(g, g_want))
            assert _rel(g, g_np) < 5e-5, (name, _rel(g, g_np))
        return
    assert y.dtype == dtype
    # one rounding of y apart at most (2^-8 of an entry), and few entries
    apart = jnp.abs(y.astype(jnp.float32) - y_np.astype(jnp.float32))
    assert float(apart.max()) <= 2.0 ** -7 * scale
    assert float((apart > 0).mean()) < 0.02
    np.testing.assert_allclose(last, last_np, rtol=1e-5, atol=1e-5)
    for name, g, g_np, g_want in zip(names, got, by_np, want):
        assert g.dtype == g_np.dtype, name
        near, other = _rel(g, g_want), _rel(g_np, g_want)
        assert near < max(2.5 * other, 2e-3), (name, near, other)


@pytest.mark.parametrize("fault", ["dropped", "decayed twice"])
def test_a_faulty_carry_in_the_kernel_fails_the_same_tolerance(monkeypatch,
                                                               fault):
    """The tolerance above is tight enough: with the carried state scaled
    by zero over a chunk (dropped) or by its decay twice, ``y`` leaves the
    literal recurrence by far more than 5e-5."""
    keep = pssd._keep

    def faulty(rows, at, p):
        k = keep(rows, at, p)
        return jnp.zeros_like(k) if fault == "dropped" else k * k

    monkeypatch.setattr(pssd, "_keep", faulty)
    jax.clear_caches()
    # decays slow enough for a state to matter 128 positions on
    ops = _operands(5, 384, 8, 16, 1, dt_most=0.02, a_most=2.0)
    try:
        with jax.default_matmul_precision("highest"), \
                _pallas_interpret(True):
            y, _ = _scan(ops, 128)
            want, _ = _literal(*ops)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    err = float(jnp.abs(y - want).max() / jnp.abs(want).max())
    assert err > 5e-4, err


# -- (b) the one question -----------------------------------------------------

SHAPE = dict(t=8192, heads=64, head_dim=64, state=128, groups=1, chunk=256,
             itemsize=2, interpret=True)


@pytest.mark.parametrize("over,why", [
    ({"interpret": False}, "the backend is cpu and the step's kernels are "
                           "not interpreted"),
    ({"chunk": 192}, "a chunk of 192 positions is no multiple of 128"),
    ({"t": 100}, "a chunk of 100 positions is no multiple of 128"),
    ({"groups": 16}, "64 heads in 16 groups are not whole blocks of 8 heads "
                     "a group"),
    ({"groups": 3}, "64 heads in 3 groups are not whole blocks"),
    ({"head_dim": 24}, "head_dim=24: a block of 8 heads is 192 lanes, no "
                       "multiple of 128"),
    ({"head_dim": 48}, "head_dim=48: heads of 48 neither divide 128 lanes "
                       "nor are whole tiles of them"),
    ({"state": 64}, "a state of 64 is no multiple of 128 that divides"),
    ({"state": 384, "heads": 8, "groups": 1},
     "a state of 384 is no multiple of 128 that divides the 512 entries"),
    ({"chunk": 1024}, "a chunk of 1024 positions needs 60 MiB of the "
                      "kernels' 32 MiB of VMEM"),
])
def test_each_refusal_by_name(over, why):
    """``ssm.scan_kernel_refusal``: None at both cells' shapes, and each
    reason in words where the platform or the shape turns the kernels
    down."""
    assert ssm.scan_kernel_refusal(**SHAPE) is None
    assert ssm.scan_kernel_refusal(**{**SHAPE, "groups": 8, "chunk": 128}
                                   ) is None
    got = ssm.scan_kernel_refusal(**{**SHAPE, **over})
    assert got is not None and why in got, got


def test_a_refused_scan_runs_the_numpy_form_and_says_why_once(caplog):
    """A chunk the kernels refuse, with the kernels interpreted: the
    ``jax.numpy`` form's very result, and one warning a shape."""
    ops = _operands(3, 192, 8, 16, 1)
    ssm._report_refusal.cache_clear()
    with caplog.at_level("INFO", logger="znicz_tpu.transformer"):
        with _pallas_interpret(True):
            y, last = _scan(ops, 96)
            _scan(ops, 96)
        want_y, want_last = _numpy_form(ops, 96)
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(last, want_last)
    said = [r for r in caplog.records if "scan kernels refused" in r.message]
    assert [r.levelname for r in said] == ["WARNING", "INFO"]
    assert "a chunk of 96 positions is no multiple of 128" in said[0].message
    assert "the backend is cpu" in said[1].message


# -- (c) the gauge ------------------------------------------------------------

def _built_step(chunk: int, interpret: bool):
    """A one-layer state-space step unit at the kernels' widths, built (not
    run) at 256 positions."""
    from builders import lm_train_keys
    import test_granitemoehybrid_arch as granite
    from reference import granitemoehybrid as granite_ref
    from znicz_tpu.core.backends import XLADevice

    cfg = {**granite._cfg(
        num_hidden_layers=1, layer_types=["mamba"], mamba_n_heads=8,
        mamba_d_head=16, mamba_expand=4, mamba_d_state=STATE,
        mamba_chunk_size=chunk), "builders": {"lm_train_keys": {
            "model_keys": [k for k in granite.TINY if k != "hyper"],
            "loss_chunks": 2}}}
    traffic = {"minibatch_size": 1, "seq_len": 256}
    rows = granite_ref.make_tokens(17, cfg, 256, 0, 1)
    with _pallas_interpret(interpret):
        w = lm_train_keys.build_workflow(rows, cfg, traffic)
        w.step._params = granite_ref.init_params(17, cfg)
        w.initialize(device=XLADevice())
    return w.step


@pytest.mark.parametrize("chunk,interpret,share", [
    (128, True, 1.0),       # the kernels' shape, kernels interpreted
    (128, False, 0.0),      # the same shape on this backend as it is
    (64, True, 0.0),        # a chunk the kernels refuse
])
def test_the_unit_publishes_the_scan_kernels_share(chunk, interpret, share):
    """``znicz_lm_ssm_scan_kernel_share`` and the unit's mirror, set as the
    step is built from what :func:`ssm.scan_kernel_refusal` says of its
    shape: 1.0 where the kernels run the scan, 0.0 where the shape or the
    backend leaves it to the ``jax.numpy`` form."""
    from znicz_tpu.observe import registry

    step = _built_step(chunk, interpret)
    assert step.ssm_scan_kernel_share == share
    fam = registry.REGISTRY.get("znicz_lm_ssm_scan_kernel_share")
    assert fam is not None and fam.labels(unit=step.name).get() == share


def test_a_stack_without_state_space_layers_has_no_share():
    import test_lfm2_arch as lfm2

    arch = lfm2._arch(lfm2._cfg(["conv", "full_attention"], 1))
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])
    assert tfm.step_choices(mesh, arch, 1, 16)["ssm_scan_kernel_share"] \
        is None
