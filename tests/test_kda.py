"""The chunked delta rule with a decay a key channel (``parallel/kda.py``)
against the positional recurrence (``benchmark/reference/solar_open2.py::
recurrence``: one position a step), on the CPU in float32: values, the state
behind the last position and the gradients of q, k, v, g and beta at several
chunk sizes; a strong decay and ``beta`` at both ends of (0, 2), which the
split ``exp(G_i) exp(-G_j)`` fails; a carry that is dropped, rounded or
shifted, a rounded decay and a rounded triangular inverse each failing the
same tolerance; what the backward pass holds; the layer's counters, which do
not depend on the chunk."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import solar_open2 as ref                   # noqa: E402

from znicz_tpu.parallel import kda                         # noqa: E402

#: float32 rounding of the chunked form against the walk: a decay is the exp
#: of a difference of two running sums, whose float32 error grows with the
#: sum (2^-24 x some tens), and the triangular inverse sums a chunk's
#: positions in another order; the readings are 3e-7 to 4e-6.  A carry that is
#: dropped, shifted or rounded to bfloat16, a bfloat16 decay or inverse read
#: 1e-3 and more (the tests below)
TOL = 2e-5


def _operands(seed, t, heads=3, width=16, b=2, g_hi=0.2):
    """q, k L2-normalised as the layer makes them, v normal, the log-decays
    log-uniform down to ``-g_hi`` a position, beta uniform over (0, 2)."""
    r = np.random.default_rng(seed)
    q, k, v = (r.normal(size=(b, t, heads, width)).astype(np.float32)
               for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(width)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(r.uniform(np.log(1e-3), np.log(g_hi),
                          (b, t, heads, width))).astype(np.float32)
    beta = r.uniform(0.0, 2.0, (b, t, heads)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


def _walked(*ops):
    o, last = zip(*(ref.recurrence(*(a[r] for a in ops))
                    for r in range(ops[0].shape[0])))
    return jnp.stack(o), jnp.stack(last)


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("t,chunk", [
    (48, 8),         # six chunks a row
    (48, 16),        # three, in sub-blocks of 4 over two levels of halves
    (44, 16),        # the last chunk is filled (44 = 2 * 16 + 12)
    (48, 64),        # the tile is wider than the row
    (8, 2),          # a chunk smaller than a direct sub-block
])
def test_chunked_rule_is_the_positional_recurrence_in_values_and_gradients(
        t, chunk):
    """``kda.delta`` against ``lax.scan`` over the positions: ``o``, the
    state behind the last position, and the gradient of a random functional
    of ``o`` in every operand (q, k, v, g, beta), float32 on both sides."""
    ops = _operands(5, t)
    w = jnp.asarray(np.random.default_rng(6).normal(
        size=ops[2].shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        o, last = kda.delta(*ops, chunk)
        want_o, want_last = _walked(*ops)
        got = jax.grad(lambda *a: (kda.delta(*a, chunk)[0] * w).sum(),
                       argnums=range(5))(*ops)
        want = jax.grad(lambda *a: (_walked(*a)[0] * w).sum(),
                        argnums=range(5))(*ops)
    assert _rel(o, want_o) < TOL and _rel(last, want_last) < TOL
    for name, g, g_want in zip("q k v g beta".split(), got, want):
        err = float(jnp.linalg.norm(g - g_want) / jnp.linalg.norm(g_want))
        assert err < TOL, (name, err)


def test_two_chunk_sizes_give_the_same_values_within_rounding():
    ops = _operands(7, 64)
    with jax.default_matmul_precision("highest"):
        (o8, s8), (o32, s32) = kda.delta(*ops, 8), kda.delta(*ops, 32)
    assert _rel(o8, o32) < TOL and _rel(s8, s32) < TOL


def test_a_strong_decay_and_beta_at_both_ends_stay_finite_and_exact():
    """``g`` down to -20 a position on some channels and ``beta`` at 1e-6
    and 2 - 1e-6 on some positions: every value and every gradient of the
    chunked form is finite and the recurrence's, where the split ``exp(G_i)
    exp(-G_j)`` over a chunk (``G`` reaches -600 in 32 positions) is not
    finite at all."""
    q, k, v, g, beta = _operands(9, 64, g_hi=20.0)
    ends = np.random.default_rng(10).integers(0, 3, beta.shape)
    beta = jnp.where(ends == 0, 1e-6, jnp.where(ends == 1, 2.0 - 1e-6, beta))
    ops = (q, k, v, g, beta)
    assert float(g.min()) < -15.0
    w = jnp.asarray(np.random.default_rng(11).normal(
        size=v.shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        o, last = kda.delta(*ops, 32)
        want_o, want_last = _walked(*ops)
        got = jax.grad(lambda *a: (kda.delta(*a, 32)[0] * w).sum(),
                       argnums=range(5))(*ops)
        want = jax.grad(lambda *a: (_walked(*a)[0] * w).sum(),
                        argnums=range(5))(*ops)
        # the split form of one chunk's key scores
        gs = jnp.cumsum(g[:, :32], axis=1)
        split = jnp.einsum("bihc,bjhc->bhij", k[:, :32] * jnp.exp(gs),
                           k[:, :32] * jnp.exp(-gs))
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    assert _rel(o, want_o) < TOL and _rel(last, want_last) < TOL
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < TOL, name
    assert not bool(jnp.isfinite(split).all())


@pytest.fixture
def fresh_traces():
    """``jax.checkpoint`` keeps a function's trace by shapes: a test that
    patches a name the rule looks up traces anew, and leaves none behind."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", ["dropped", "state_bfloat16", "shifted",
                                   "decay_bfloat16", "inverse_bfloat16"])
def test_a_fault_in_the_carry_the_decay_or_the_inverse_fails_the_tolerance(
        monkeypatch, fresh_traces, fault):
    """The tolerance above is tight enough: with each chunk's opening state
    set to zero, rounded to bfloat16 or taken from the chunk before, with
    the running sums of the log-decays or the diagonal blocks of the
    triangular inverse rounded to bfloat16, ``o`` leaves the recurrence by
    ten times ``TOL`` and more.  A carry dropped at the last chunk's edge
    moves the state behind the last position, which ``kda_state_rms``
    reads."""
    states, scores, small = kda._chunk_states, kda._scores, \
        kda._small_inverse

    def rounded(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def faulty_states(k, v, g, beta):
        w, u, opening, last = states(k, v, g, beta)
        if fault == "dropped":
            # ... and the last chunk starts from nothing
            return w, u, jnp.zeros_like(opening), states(
                k[:, :, -1:], v[:, :, -1:], g[:, :, -1:], beta[:, :, -1:])[3]
        if fault == "state_bfloat16":
            return w, u, rounded(opening), last
        return w, u, jnp.roll(opening, 1, axis=2), last

    if fault in ("dropped", "state_bfloat16", "shifted"):
        monkeypatch.setattr(kda, "_chunk_states", faulty_states)
    elif fault == "decay_bfloat16":
        monkeypatch.setattr(kda, "_scores", lambda rows, cols, gs: scores(
            rows, cols, rounded(gs)))
    else:
        monkeypatch.setattr(kda, "_small_inverse",
                            lambda n: rounded(small(n)))
    ops = _operands(5, 48)
    with jax.default_matmul_precision("highest"):
        o, last = kda.delta(*ops, 8)
        want_o, want_last = _walked(*ops)
    assert _rel(o, want_o) > 10 * TOL, _rel(o, want_o)
    if fault == "dropped":
        rms = jnp.sqrt((last * last).mean())
        want_rms = jnp.sqrt((want_last * want_last).mean())
        assert abs(float(rms / want_rms) - 1) > 0.01


def _shapes_of(jaxpr, out=None) -> set:
    """Every array shape a jaxpr and its inner jaxprs write."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.update(tuple(v.aval.shape) for v in eqn.outvars
                   if hasattr(v.aval, "shape"))
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _shapes_of(inner, out)
    return out


def test_nothing_chunk_by_chunk_by_channel_and_nothing_row_by_row_is_made():
    """Forward and backward, no array holds two chunk-length axes beside the
    channels (a chunk's decay matrix a channel: 17 GB at the cell's size) and
    none two row-length axes; what is kept between the two halves is each
    chunk's opening state."""
    t, chunk, heads, width = 96, 32, 3, 24
    ops = _operands(3, t, heads=heads, width=width, b=1)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: kda.delta(*a, chunk)[0].sum(), argnums=range(5)))(*ops)
    shapes = _shapes_of(jaxpr.jaxpr)
    assert (1, heads, t // chunk, width, width) in shapes     # the states
    for shape in shapes:
        assert not (shape.count(chunk) >= 2 and width in shape), shape
        assert shape.count(t) < 2, shape


def _leaves(seed, d, heads, width, taps=4):
    from znicz_tpu.parallel.params import _kda_leaf_shapes
    r = np.random.default_rng(seed)
    out = {}
    for name, shape in _kda_leaf_shapes(d, heads, width, width, taps).items():
        out[name] = jnp.asarray(r.normal(size=shape).astype(np.float32) /
                                np.sqrt(shape[0]))
    out["kda_a_log"] = jnp.log(jnp.asarray(
        r.uniform(1, 16, heads).astype(np.float32)))
    out["kda_dt_b"] = jnp.full((heads * width,), -3.0)
    return out


def test_the_layers_counters_do_not_depend_on_the_chunk():
    """``kda.mixer`` at two chunk sizes: the same output within rounding and
    the same four counters (the mean decay strictly inside (0, 1), the mean
    ``beta`` inside (0, 2), the last state's RMS above 0, one layer)."""
    d, heads, width = 32, 4, 8
    p = _leaves(1, d, heads, width)
    u = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 40, d)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        (y8, s8), (y32, s32) = (kda.mixer(u, p, heads, width, chunk, True,
                                          1e-5, "blockX.kda")
                                for chunk in (8, 32))
    assert _rel(y8, y32) < TOL
    assert set(s8) == {"kda_decay", "kda_beta", "kda_state_rms",
                       "kda_layers"}
    for key in s8:
        assert float(s8[key]) == pytest.approx(float(s32[key]), rel=TOL)
    assert 0 < float(s8["kda_decay"]) < 1 and 0 < float(s8["kda_beta"]) < 2
    assert float(s8["kda_state_rms"]) > 0 and float(s8["kda_layers"]) == 1
    # without negative eigenvalues beta stays under 1
    _, plain = kda.mixer(u, p, heads, width, 8, False, 1e-5, "blockX.kda")
    assert float(plain["kda_beta"]) == pytest.approx(
        float(s8["kda_beta"]) / 2, rel=1e-6)
