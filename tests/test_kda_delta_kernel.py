"""The delta rule's Pallas kernels (``ops/pallas/kda_delta.py``, called from
``parallel/kda.py::delta``), interpreted on the CPU: against ``kda.py``'s
``jax.numpy`` form and against the positional recurrence
(``benchmark/reference/solar_open2.py::recurrence``: one position a step),
in values, the state behind the last position and all five gradients, in
float32 and in bfloat16; a strong decay with ``beta`` at 0 and 2; a row the
chunk does not divide; a fault planted in the carry, the decay or the
inverse against the same tolerance; each refusal by name.  The cell's step
compiled at its real widths for a described TPU v5e, with the kernels in it,
is ``tests/test_checkpoint_plan.py``'s."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_kda import TOL, _operands, _rel, _walked
from test_lfm2_arch import _pallas_interpret
from znicz_tpu.ops.pallas import kda_delta as pdelta
from znicz_tpu.parallel import kda

HEADS, WIDTH, CHUNK = 2, 128, 64


def _kernels(ops, chunk=CHUNK):
    with _pallas_interpret(True):
        return kda.delta(*ops, chunk)


def _numpy_form(ops, chunk=CHUNK):
    with _pallas_interpret(False):
        return kda.delta(*ops, chunk)


def _functional(form, w, w_last):
    """A random functional of the rule's two results, so that both
    cotangents the backward kernel takes are exercised."""
    def f(*ops):
        o, last = form(ops)
        return (o.astype(jnp.float32) * w).sum() + (last * w_last).sum()
    return f


def _weights(seed, ops):
    r = np.random.default_rng(seed)
    b, _, heads, width = ops[0].shape
    return (jnp.asarray(r.normal(size=ops[2].shape).astype(np.float32)),
            jnp.asarray(r.normal(size=(b, heads, width, width)
                                 ).astype(np.float32)))


def _norm_gap(got, want):
    got, want = (jnp.asarray(v, jnp.float32) for v in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _all(form, ops, w, w_last):
    """``(o, last, the five gradients)`` of ``form``."""
    with jax.default_matmul_precision("highest"):
        o, last = form(ops)
        grads = jax.grad(_functional(form, w, w_last),
                         argnums=range(5))(*ops)
    return o, last, grads


@pytest.fixture(scope="module")
def float32_case():
    """Two rows of 192 positions (three chunks) of two heads of 128."""
    ops = _operands(5, 192, heads=HEADS, width=WIDTH)
    w, w_last = _weights(6, ops)
    return ops, tuple(_all(form, ops, w, w_last) for form in (
        _kernels, _numpy_form, lambda a: _walked(*a)))


@pytest.mark.parametrize("against", ["jax.numpy form", "recurrence"])
@pytest.mark.parametrize("what", ["o", "last", "q", "k", "v", "g", "beta"])
def test_kernels_are_the_rule_in_float32(float32_case, against, what):
    """The two kernels against ``kda.delta``'s ``jax.numpy`` form and
    against ``lax.scan`` over the positions: ``o``, the state behind the
    last position, and the gradient of a random functional of both in every
    operand, float32 on all sides, at ``tests/test_kda.py``'s tolerance."""
    _, (got, numpy_form, walked) = float32_case
    want = numpy_form if against == "jax.numpy form" else walked
    names = "q k v g beta".split()
    if what in names:
        i = names.index(what)
        assert _norm_gap(got[2][i], want[2][i]) < TOL
    else:
        i = ("o", "last").index(what)
        assert _rel(got[i], want[i]) < TOL


def test_kernels_in_bfloat16_stand_where_the_numpy_form_does():
    """With ``q``, ``k``, ``v`` in bfloat16 the kernels leave the float32
    recurrence (on the rounded operands) by no more than 1.5 times what the
    ``jax.numpy`` form does, in values and in every gradient, and by under a
    hundredth."""
    ops = _operands(7, 128, heads=HEADS, width=WIDTH)
    low = tuple(a.astype(jnp.bfloat16) for a in ops[:3]) + ops[3:]
    back = tuple(a.astype(jnp.float32) for a in low)
    w, w_last = _weights(8, ops)
    want = _all(lambda a: _walked(*a), back, w, w_last)
    got = _all(_kernels, low, w, w_last)
    numpy_form = _all(_numpy_form, low, w, w_last)

    def flat(o, last, grads):
        return [o, last, *grads]

    for name, a, c, want_ in zip("o last q k v g beta".split(), flat(*got),
                                 flat(*numpy_form), flat(*want)):
        mine, theirs = _norm_gap(a, want_), _norm_gap(c, want_)
        assert mine < 0.01 and mine < 1.5 * theirs + 1e-4, (name, mine,
                                                            theirs)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    assert all(g.dtype == a.dtype for g, a in zip(got[2], low))


def test_a_strong_decay_and_beta_at_zero_and_two_stay_finite_and_exact():
    """``g`` down to -20 a position on some channels and ``beta`` at exactly
    0 and 2 on some positions: every value and every gradient of the kernels
    is finite and the recurrence's."""
    q, k, v, g, beta = _operands(9, 128, heads=HEADS, width=WIDTH, g_hi=20.0)
    ends = np.random.default_rng(10).integers(0, 3, beta.shape)
    beta = jnp.where(ends == 0, 0.0, jnp.where(ends == 1, 2.0, beta))
    ops = (q, k, v, g, beta)
    assert float(g.min()) < -15.0
    w, w_last = _weights(11, ops)
    got = _all(_kernels, ops, w, w_last)
    want = _all(lambda a: _walked(*a), ops, w, w_last)
    assert _rel(got[0], want[0]) < TOL and _rel(got[1], want[1]) < TOL
    for name, a, c in zip("q k v g beta".split(), got[2], want[2]):
        assert bool(jnp.isfinite(a).all()), name
        assert _norm_gap(a, c) < TOL, name


def test_a_row_the_chunk_does_not_divide_and_chunks_of_128():
    """200 positions in chunks of 64 (the last one filled) and in chunks of
    128 (one head a stack): the ``jax.numpy`` form's values and state."""
    ops = _operands(12, 200, heads=HEADS, width=WIDTH, b=1)
    with jax.default_matmul_precision("highest"):
        want_o, want_last = _numpy_form(ops)
        for chunk in (64, 128):
            o, last = _kernels(ops, chunk)
            assert o.shape == want_o.shape
            assert _rel(o, want_o) < TOL and _rel(last, want_last) < TOL


def test_the_packed_operand_and_the_norms_in_the_kernels():
    """``delta_packed`` on ONE ``q | k | v`` array as the convolution leaves
    it, the three cut by block specs and a head's rows of q and k L2-normed
    inside the kernels, against ``kda.l2_normed`` and the ``jax.numpy`` rule
    on the cuts: values, the last state and the gradients of the packed
    array (one cotangent), ``g`` and ``beta``."""
    b, t = 2, 128
    _, _, _, g, beta = _operands(13, t, heads=HEADS, width=WIDTH, b=b)
    qkv = jnp.asarray(np.random.default_rng(14).normal(
        size=(b, t, 3 * HEADS * WIDTH)).astype(np.float32))
    w, w_last = _weights(15, (g,) * 3)

    def kernels(a):
        qkv, g, beta = a
        o, last = pdelta.delta_packed(qkv, g.reshape(b, t, -1), beta, CHUNK,
                                      1e-6, True)
        return o.reshape(g.shape), last

    def numpy_form(a):
        qkv, g, beta = a
        q, k, v = (qkv[..., i * HEADS * WIDTH:(i + 1) * HEADS * WIDTH
                       ].reshape(g.shape) for i in range(3))
        return kda.delta(kda.l2_normed(q) * WIDTH ** -0.5, kda.l2_normed(k),
                         v, g, beta, CHUNK)

    with jax.default_matmul_precision("highest"):
        got, want = kernels((qkv, g, beta)), numpy_form((qkv, g, beta))
        grads, wanted = (jax.grad(_functional(form, w, w_last),
                                  argnums=range(3))(qkv, g, beta)
                         for form in (kernels, numpy_form))
    assert _rel(got[0], want[0]) < TOL and _rel(got[1], want[1]) < TOL
    for name, a, c in zip("qkv g beta".split(), grads, wanted):
        assert _norm_gap(a, c) < TOL, name


@pytest.fixture
def fresh_traces():
    """The kernels' callers are jitted: a test that patches a name a kernel
    looks up traces anew, and leaves none behind."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", ["dropped", "state_bfloat16", "undecayed",
                                   "decay_bfloat16", "inverse_bfloat16"])
def test_a_fault_in_the_carry_the_decay_or_the_inverse_fails_the_tolerance(
        monkeypatch, fresh_traces, fault):
    """The tolerance above is tight enough: with the carry dropped, rounded
    to bfloat16 or passed on without its decay over the chunk, with a
    level's block sums of the log-decays or the inverse's products rounded
    to bfloat16, ``o`` leaves the recurrence by ten times ``TOL`` and more,
    and a dropped carry moves the state behind the last position."""
    closing, partner, mm32 = pdelta._closing_state, pdelta._partner, \
        pdelta._mm32

    def rounded(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    if fault == "dropped":
        monkeypatch.setattr(pdelta, "_closing_state",
                            lambda s0, keep, newb, kd: closing(
                                jnp.zeros_like(s0), keep, newb, kd))
    elif fault == "state_bfloat16":
        monkeypatch.setattr(pdelta, "_closing_state",
                            lambda *a: rounded(closing(*a)))
    elif fault == "undecayed":
        monkeypatch.setattr(pdelta, "_closing_state",
                            lambda s0, keep, newb, kd: closing(
                                s0, jnp.ones_like(keep), newb, kd))
    elif fault == "decay_bfloat16":
        monkeypatch.setattr(pdelta, "_partner",
                            lambda x, bit, h: rounded(partner(x, bit, h)))
    else:
        monkeypatch.setattr(pdelta, "_mm32", lambda a, b: rounded(mm32(a, b)))
    # slow decays (at most 0.01 a position), so that a chunk's opening
    # state weighs in the state behind the last one
    ops = _operands(5, 192, heads=HEADS, width=WIDTH, b=1, g_hi=0.01)
    with jax.default_matmul_precision("highest"):
        o, last = _kernels(ops)
        want_o, want_last = _walked(*ops)
    assert _rel(o, want_o) > 10 * TOL, _rel(o, want_o)
    if fault == "dropped":
        rms = jnp.sqrt((last * last).mean())
        want_rms = jnp.sqrt((want_last * want_last).mean())
        assert abs(float(rms / want_rms) - 1) > 0.01


@pytest.mark.parametrize("shape,word", [
    ((64, 4, 64, 2), "head_dim=64"),          # a head of half a lane tile
    ((8, 4, 128, 2), "a chunk of 8"),         # under a 16-bit sublane tile
    ((96, 4, 128, 2), "a chunk of 96"),       # no power of two
    ((32, 6, 128, 2), "6 heads"),             # no whole stack of four
    ((64, 512, 128, 2), "MiB"),               # a row's states overrun VMEM
])
def test_each_refusal_names_its_reason(shape, word):
    why = pdelta.unsupported_reason(*shape)
    assert why is not None and word in why, why


def test_the_cells_shape_is_taken_and_the_backend_decides():
    """64 heads of 128 in chunks of 32, 64 and 128 in 16 bits are shapes
    the kernels take; ``kda.delta_kernel_refusal`` adds the backend's word:
    refused on the CPU as it is, taken where the kernels are interpreted."""
    for chunk in (32, 64, 128):
        assert pdelta.unsupported_reason(chunk, 64, 128, 2) is None
    assert "backend" in kda.delta_kernel_refusal(8192, 64, 128, 64, 2, False)
    assert kda.delta_kernel_refusal(8192, 64, 128, 64, 2, True) is None
    assert "head_dim=16" in kda.delta_kernel_refusal(48, 3, 16, 8, 4, True)
