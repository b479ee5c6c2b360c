"""The chunked delta rule under a decay a key channel as two Pallas kernels
(``parallel/kda.py`` has the layer, the mathematics and the ``jax.numpy``
form these stand in for): steps 1 to 4 of :func:`~znicz_tpu.parallel.kda.
delta`, the carry between the chunks among them, with the chunk axis of the
grid sequential.  In the ``jax.numpy`` form a chunk's scores, the
unit-triangular inverse, ``W``, ``U``, ``K e^(G_last - G)`` and a head-major
copy of every operand go through HBM in every pass, and the three
checkpointed parts make them three times a step; here nothing with two
chunk-length axes leaves VMEM and the operands are read where the layer left
them.

**A visit** is one chunk of ``C`` positions of a block of heads of one row,
the chunks of a row in order.  A head is one lane tile (``K = V = 128``), so
``q``, ``k``, ``v`` ``(b, t, H K)`` and the log-decays ``g (b, t, H K)``
float32 are cut by lanes: from three arrays (:func:`delta`) or, as the
layer calls them (:func:`delta_packed`), from the ONE ``q | k | v (b, t, 3 H
K)`` array its convolution wrote, three block specs on it and no copy of any
cut; there the kernels also take the L2 norms of a head's ``q`` and ``k``
rows (a row sum over one lane tile, float32) and the norms' cotangents, which
as ``jax.numpy`` needed a head-major view of both and cost as much as the
kernels (10 ms a layer and pass at the cell's shape; my chip run, PR 53).
Inside a visit the heads are worked in STACKS of
``128 / C`` heads whose chunks lie one under the other along the sublanes
(two heads of 64 positions: 128 rows): every product then has 128 rows and
every score matrix is ``(128, 128)`` with a head's ``(C, C)`` block on its
diagonal, which the masks keep apart.

**The scores** ``sum_c rows_ic cols_jc exp(G_ic - G_jc)`` (``j < i``) are
made by halves all the way down, a level ``h = 1, 2, 4, .., C / 2`` at a
time: the rows in the second half of a segment of ``2 h`` positions against
the columns of its first half, both factors against the running sum at the
first half's last position, ``exp(G_i - G_edge) exp(G_edge - G_j)``, each the
``exp`` of a number that is at most 0.  The two exponents need no gather:
with ``P_h`` the running sum of ``g`` inside a position's block of ``h`` and
``B_h`` that block's whole sum, they are ``P_h`` (second half) and ``B_h -
P_h`` (first half), and ``P_2h = P_h + [second half] B_h(partner)``, ``B_2h
= B_h + B_h(partner)``, the partner block ``h`` rows up or down (two sublane
rotations a level); the last level leaves ``G`` and ``G_last``, so the
kernels take ``g`` itself.  A level is ONE product ``[k-rows; q-rows] (256,
K) x cols^T`` whose entries outside the level's own blocks are dropped by a
mask (an input, :func:`_levels_of`); the diagonal of the queries' scores is
a row sum.

**The inverse** ``M = (I + N)^-1`` of the strictly lower ``N = Diag(beta)
A`` follows the same levels: with ``M_h`` the inverse of the diagonal blocks
of ``h`` and ``C_h`` the level's blocks of ``N``, ``M_2h = M_h - M_h C_h M_h``
(block forward substitution: ``[[a, 0], [c, b]]^-1 = [[a^-1, 0], [-b^-1 c
a^-1, b^-1]]``), float32 throughout, its products in three 16-bit passes
that keep 16 bits of every operand (:func:`_mm32`).

Then, a head at a time where the state enters (kept TRANSPOSED, ``S^T (V,
K)``, so that the decay over a chunk scales its lanes): ``new = M (beta (v -
(K e^G) S))`` (the jax.numpy form's ``U - W S``), ``o = (Q e^G) S + tril(QK)
new``, ``S' = Diag(e^G_last) S + (K e^(G_last - G))^T new``.  The forward
kernel (:data:`FWD_KERNEL_NAME`) writes ``o``, each chunk's OPENING state
cast as the products read it (what the backward pass keeps) and, once a row,
the float32 state behind the last position (the resident block that is the
carry).

**Backward** (:data:`BWD_KERNEL_NAME`): the same visits with the chunks in
reverse and the state's cotangent ``(V, K)`` float32 of a row's heads
carried in VMEM.  It makes the scores, the inverse and ``new`` again from
the operands and the kept opening state, ``dN = -M^T dM M^T``, and sends the
scores' cotangents back through each level's product.  The decays'
cotangent needs no ``(C, C, K)`` array: every appearance of a running sum
scales one channel of a row or column operand, so a level's ``d exponent``
is the channel-wise product of the operand with its cotangent, and the
recurrences of ``P`` and ``B`` are walked backwards (the same two rotations
a level) down to ``dg``.  Returns ``dq``, ``dk``, ``dv`` in the operands'
dtype and lanes, ``dg`` float32 and ``dbeta`` float32.

Precision is ``kda.py``'s: float32 log-decays, running sums, every decay
factor, ``beta``, the inverse and the carried state and its cotangent;
products on the operands' dtype accumulated in float32; ``M``, ``beta (v - K
e^G S)``, ``new`` and a state rounded only where a product reads them.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct
from znicz_tpu.ops.pallas.attention import _nt
from znicz_tpu.ops.pallas.ssd import _mm, _tn

#: the kernels' names in the lowered program and in device traces
FWD_KERNEL_NAME = "kda_delta_fwd"
BWD_KERNEL_NAME = "kda_delta_bwd"
#: a head's entries, keys and values alike: one lane tile
LANES = 128
#: stacks of ``128 / C`` heads a visit works at most (fewer where the
#: layer's heads are no multiple of that many)
STACKS = 2
#: scoped VMEM asked of the compiler
_VMEM_LIMIT = 32 * 1024 * 1024

_F32 = jnp.float32


def heads_a_visit(chunk: int, heads: int) -> int:
    """Heads a visit holds at chunks of ``chunk`` positions of ``heads``
    heads: as many whole stacks, :data:`STACKS` at most, as divide them (0
    where not even one does)."""
    per = LANES // chunk
    return max((n * per for n in range(1, STACKS + 1)
                if heads % (n * per) == 0), default=0)


def delta_vmem(chunk: int, heads: int, itemsize: int) -> int:
    """Bytes of VMEM the backward kernel's visit takes at most (the forward
    kernel's takes less) at chunks of ``chunk`` positions of ``heads`` heads
    of 128 and operands of ``itemsize`` bytes: the blocks of ``q``, ``k``,
    ``v``, ``do``, ``dq``, ``dk``, ``dv``, the float32 ``g`` and ``dg``, the
    opening states and ``beta`` / ``dbeta`` (a lane tile wide in VMEM), a
    row's whole last state's cotangent and the levels' mask, double-buffered;
    a row's whole carried cotangent in scratch; live values a stack: the
    levels' three operands and factor, some forty float32 ``(rows, 128)``
    arrays."""
    hv = heads_a_visit(chunk, heads)
    wide = hv * LANES
    state = heads * LANES * LANES * 4
    blocks = 2 * (7 * chunk * wide * itemsize + 2 * chunk * wide * 4 +
                  hv * LANES * LANES * itemsize + 2 * chunk * LANES * 4 +
                  state + LANES * LANES * 4)
    levels = max(1, chunk.bit_length() - 1)
    live = hv * chunk * LANES * (40 * 4 + levels * (3 * itemsize + 4))
    return blocks + state + live


def unsupported_reason(chunk: int, heads: int, head_dim: int,
                       itemsize: int) -> str | None:
    """Why the kernels cannot take chunks of ``chunk`` positions of
    ``heads`` heads of ``head_dim`` entries and operands of ``itemsize``
    bytes, or ``None``: a head that is one lane tile (the operands are cut
    by lanes and a stack of heads is 128 lanes wide), a chunk that is a
    power of two of 16 to 128 positions (whole sublane tiles of 16-bit
    operands; the stacks are 128 rows), heads in whole visits, a visit
    inside the VMEM limit."""
    if head_dim != LANES:
        return (f"head_dim={head_dim}: a head's entries are not the "
                f"{LANES} lanes of one tile")
    if chunk & (chunk - 1) or not 16 <= chunk <= LANES:
        return (f"a chunk of {chunk} positions is no power of two from 16 "
                f"to {LANES}")
    if not heads_a_visit(chunk, heads):
        return (f"{heads} heads are not whole stacks of {LANES // chunk} "
                f"chunks of {chunk} positions")
    need = delta_vmem(chunk, heads, itemsize)
    if need > _VMEM_LIMIT:
        return (f"{heads} heads in chunks of {chunk} need {need >> 20} MiB "
                f"of the kernels' {_VMEM_LIMIT >> 20} MiB of VMEM")
    return None


def _levels_of(chunk: int) -> np.ndarray:
    """``(128, 128)`` int32: for row ``i`` and column ``j < i`` of one
    head's chunk in a stack of ``128 / chunk`` of them the level ``h`` (1,
    2, 4, .., ``chunk / 2``) at which the two part, position ``i`` in the
    second half and ``j`` in the first half of one segment of ``2 h`` (the
    highest bit in which the two positions differ); 0 everywhere else (the
    diagonal, above it, another head's chunk)."""
    i, j = np.arange(LANES)[:, None], np.arange(LANES)[None, :]
    top = 1 << np.frexp(np.maximum(i ^ j, 1))[1] - 1
    return np.where((i // chunk == j // chunk) & (i > j), top,
                    0).astype(np.int32)


def _split(a):
    """``a`` float32 as two 16-bit terms, 8 bits of it each and the next 8."""
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(_F32)).astype(jnp.bfloat16)


def _mm32(a, b):
    """``a @ b`` of float32 matrices to float32's own precision but for the
    last bits: each operand in two 16-bit terms and the three products that
    hold 16 bits of the result or more (``hi hi + hi lo + lo hi``; the one
    left out is 2^-16 of the result), accumulated in float32.  Against the
    six passes of the highest precision: the kernels alone 11.3 for 14.7 ms
    forward and 26.2 for 32.4 forward and backward a layer at the cell's
    shape, every value and gradient the same to four digits against the
    float32 rule (my chip run, PR 53)."""
    (ah, al), (bh, bl) = _split(a), _split(b)

    def mm(x, y):
        # one pass each whatever ``jax.default_matmul_precision`` says
        # around the call: a float32 contraction of 16-bit operands is not
        # a product the compiler has
        return jnp.dot(x, y, precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=_F32)

    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def _partner(x, bit, h: int):
    """Row ``r`` of ``x (rows, 128)`` gets the row ``h`` up where ``bit``
    (``r`` lies in the second block of ``h`` of its pair), ``h`` down
    where not."""
    rows = x.shape[0]
    return jnp.where(bit, pltpu.roll(x, h, 0), pltpu.roll(x, rows - h, 0))


class _Stack:
    """Steps 1 and 2 on a stack of heads: ``qf``, ``kf`` ``(rows, 128)``
    float32 (the operands, or their L2-normed rows), ``g (rows, 128)``
    float32, ``beta (rows, 1)`` float32, ``lv`` :func:`_levels_of``,
    ``dtype`` the products' -> the keys' scores ``a`` (strictly
    lower) and the queries' ``qk`` (strictly lower; their diagonal ``dd
    (rows, 1)``), the inverse ``m``, the running sums ``p`` (``G``) and the
    chunks' whole sums ``b`` (``G_last`` in every row), and each level's
    ``(h, second-half rows, factor, k-rows, q-rows, k-columns)`` for the
    backward pass."""

    def __init__(self, qf, kf, g, beta, lv, chunk: int, dtype):
        rows = qf.shape[0]
        self.kf, self.qf = kf, qf
        pos = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
        p = b = g
        a = qk = jnp.zeros((rows, rows), _F32)
        self.levels = []
        h = 1
        while h < chunk:
            bit = (pos & h) != 0
            # each the exp of a number that is at most 0
            f = jnp.exp(jnp.where(bit, p, b - p))
            kfac = kf * f
            lk = jnp.where(bit, kfac, 0.0).astype(dtype)
            rk = jnp.where(bit, 0.0, kfac).astype(dtype)
            lq = jnp.where(bit, qf * f, 0.0).astype(dtype)
            both = _nt(jnp.concatenate([lk, lq], axis=0), rk)
            mine = lv == h
            a = a + jnp.where(mine, both[:rows], 0.0)
            qk = qk + jnp.where(mine, both[rows:], 0.0)
            self.levels.append((h, bit, f, lk, lq, rk))
            other = _partner(b, bit, h)
            p, b = p + jnp.where(bit, other, 0.0), b + other
            h *= 2
        self.a, self.qk, self.p, self.b = a, qk, p, b
        self.dd = (qf * kf).sum(axis=1, keepdims=True)
        # the unit-triangular inverse by the same levels, float32
        n = beta * a
        eye = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0) ==
               jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
               ).astype(_F32)
        m = eye - jnp.where(lv == 1, n, 0.0)
        h = 2
        while h < chunk:
            m = m - _mm32(_mm32(m, jnp.where(lv == h, n, 0.0)), m)
            h *= 2
        self.m = m
        self.eg = jnp.exp(p)
        self.ed = jnp.exp(b - p)


def _under(parts):
    """The heads' ``(C, n)`` parts one under the other: a stack's ``(128,
    n)``."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _normed(x, scale: float, eps: float):
    """A head's rows L2-normed, ``scale x / sqrt(sum(x^2) + eps)`` over the
    lanes, float32 -> ``(the normed rows, 1 / sqrt(sum + eps) (rows, 1))``."""
    r = jax.lax.rsqrt((x * x).sum(axis=1, keepdims=True) + eps)
    return x * (r * scale), r


def _normed_back(x, r, dy, scale: float):
    """The cotangent of ``x`` from :func:`_normed`'s result's ``dy``."""
    return (scale * r) * dy - x * ((scale * r * r * r) *
                                   (x * dy).sum(axis=1, keepdims=True))


def _stacked(ref, heads, dtype=None):
    """A visit's block ``(1, C, heads x 128)`` -> the stack of ``heads``'
    chunks ``(len(heads) x C, 128)``."""
    out = _under([ref[0, :, h * LANES:(h + 1) * LANES] for h in heads])
    return out if dtype is None else out.astype(dtype)


def _beta_of(beta_ref, heads):
    """``(1, 1, C, heads a visit)`` -> the stack's ``(128, 1)``."""
    return _under([beta_ref[0, 0, :, h:h + 1] for h in heads])


def _state_rows(j, hv: int, head: int):
    """The rows of a row's whole transposed state ``(heads x 128, 128)``
    that hold head ``head`` of visit ``j`` (traced)."""
    return pl.ds(pl.multiple_of((j * hv + head) * LANES, LANES), LANES)


def _closing_state(s0, keep, newb, kd):
    """Step 3, transposed: the state ``(V, K)`` float32 behind a chunk from
    the one that opened it, ``keep (1, K)`` the decay over the whole chunk,
    ``newb (C, V)`` and ``kd (C, K)`` (``K e^(G_last - G)``) as the product
    reads them."""
    return s0 * keep + _tn(newb, kd)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, lv_ref, o_ref,
                open_ref, last_ref, *, chunk: int, eps):
    c, j = pl.program_id(1), pl.program_id(2)
    per = LANES // chunk
    hv = q_ref.shape[2] // LANES
    dtype = q_ref.dtype

    @pl.when(c == 0)
    def _init():
        last_ref[0, pl.ds(pl.multiple_of(j * hv * LANES, LANES),
                          hv * LANES), :] = \
            jnp.zeros((hv * LANES, LANES), _F32)

    lv = lv_ref[...]
    for s in range(hv // per):
        heads = range(s * per, (s + 1) * per)
        beta = _beta_of(beta_ref, heads)
        qf, kf = _stacked(q_ref, heads, _F32), _stacked(k_ref, heads, _F32)
        if eps is not None:
            qf, kf = _normed(qf, LANES ** -0.5, eps)[0], \
                _normed(kf, 1.0, eps)[0]
        st = _Stack(qf, kf, _stacked(g_ref, heads), beta, lv, chunk, dtype)
        kg = (st.kf * st.eg).astype(dtype)
        qg = (st.qf * st.eg).astype(dtype)
        kd = (st.kf * st.ed).astype(dtype)
        states, pred, carried = [], [], []
        for u, head in enumerate(heads):
            at = slice(u * chunk, (u + 1) * chunk)
            s0 = last_ref[0, _state_rows(j, hv, head), :]
            # what the backward pass keeps is what the products read
            open_ref[0, 0, head * LANES:(head + 1) * LANES, :] = s0b = \
                s0.astype(dtype)
            states.append((at, head, s0))
            pred.append(_nt(kg[at], s0b))
            carried.append(_nt(qg[at], s0b))
        pred, carried = _under(pred), _under(carried)
        vf = _stacked(v_ref, heads, _F32)
        new = _mm(st.m.astype(dtype), (beta * (vf - pred)).astype(dtype))
        newb = new.astype(dtype)
        o = carried + _mm(st.qk.astype(dtype), newb) + st.dd * new
        for at, head, s0 in states:
            o_ref[0, :, head * LANES:(head + 1) * LANES] = \
                o[at].astype(o_ref.dtype)
            keep = jnp.exp(st.b[at.start:at.start + 1, :])
            last_ref[0, _state_rows(j, hv, head), :] = \
                _closing_state(s0, keep, newb[at], kd[at])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, lv_ref, open_ref,
                do_ref, dlast_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, ds_sc, *, chunk: int, eps):
    c, j = pl.program_id(1), pl.program_id(2)
    per = LANES // chunk
    hv = q_ref.shape[2] // LANES
    dtype = q_ref.dtype

    @pl.when(c == 0)
    def _init():
        mine = pl.ds(pl.multiple_of(j * hv * LANES, LANES), hv * LANES)
        ds_sc[mine, :] = dlast_ref[0, mine, :]

    lv = lv_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1)
    dbeta_all = jnp.zeros((chunk, LANES), _F32)
    for s in range(hv // per):
        heads = range(s * per, (s + 1) * per)
        beta = _beta_of(beta_ref, heads)
        qx, kx = _stacked(q_ref, heads, _F32), _stacked(k_ref, heads, _F32)
        qf, kf = qx, kx
        if eps is not None:
            (qf, qr), (kf, kr) = _normed(qx, LANES ** -0.5, eps), \
                _normed(kx, 1.0, eps)
        st = _Stack(qf, kf, _stacked(g_ref, heads), beta, lv, chunk, dtype)
        kg32, qg32, kd32 = kf * st.eg, qf * st.eg, kf * st.ed
        kg, qg, kd = (x.astype(dtype) for x in (kg32, qg32, kd32))
        dof = _stacked(do_ref, heads, _F32)
        dob = dof.astype(dtype)
        mb = st.m.astype(dtype)
        # the forward pass again as far as ``new``
        states, pred = [], []
        for u, head in enumerate(heads):
            at = slice(u * chunk, (u + 1) * chunk)
            s0b = open_ref[0, 0, head * LANES:(head + 1) * LANES, :]
            ds1 = ds_sc[_state_rows(j, hv, head), :]
            states.append((at, head, s0b, ds1, ds1.astype(dtype)))
            pred.append(_nt(kg[at], s0b))
        left = _stacked(v_ref, heads, _F32) - _under(pred)
        rb = (beta * left).astype(dtype)
        new = _mm(mb, rb)
        newb = new.astype(dtype)
        # ... and back: what the outputs and the closing state gave ``new``
        dnew, dkd, dqg = [], [], []
        for at, head, s0b, ds1, ds1b in states:
            dnew.append(_nt(kd[at], ds1b))
            dkd.append(_mm(newb[at], ds1b))
            dqg.append(_mm(dob[at], s0b))
        dkd, dqg = _under(dkd), _under(dqg)
        dnew = _under(dnew) + _tn(st.qk.astype(dtype), dob) + st.dd * dof
        ddd = (dof * new).sum(axis=1, keepdims=True)
        under = lv > 0
        dqk = jnp.where(under, _nt(dob, newb), 0.0)
        dnewb = dnew.astype(dtype)
        dr = _tn(mb, dnewb)
        # d (I + N)^-1 = -M^T dM M^T, its strictly lower part
        dm = _nt(dnewb, rb)
        dn = jnp.where(under, -_tn(mb, _nt(dm.astype(dtype), mb).astype(
            dtype)), 0.0)
        da = beta * dn
        dbeta = (dn * st.a).sum(axis=1, keepdims=True) + \
            (dr * left).sum(axis=1, keepdims=True)
        dv = beta * dr
        dpredb = (-dv).astype(dtype)
        dkg, dgl = [], []
        for at, head, s0b, ds1, ds1b in states:
            dkg.append(_mm(dpredb[at], s0b))
            keep = jnp.exp(st.b[at.start:at.start + 1, :])
            # the state's own decay over the chunk gathers dS' x S a channel
            gathered = (ds1 * s0b.astype(_F32)).sum(axis=0, keepdims=True) * \
                keep
            dgl.append(jnp.concatenate(
                [gathered, jnp.zeros((chunk - 1, LANES), _F32)], axis=0))
            ds_sc[_state_rows(j, hv, head), :] = ds1 * keep + \
                _tn(dob[at], qg[at]) + _tn(dpredb[at], kg[at])
        dkg, dgl = _under(dkg), _under(dgl)
        w = dkd * kd32
        dp = dkg * kg32 + dqg * qg32 - w
        db = w + dgl
        dk = dkg * st.eg + dkd * st.ed + ddd * qf
        dq = dqg * st.eg + ddd * kf
        # the levels backwards: the scores' cotangents through each level's
        # product, the exponents' through the recurrences of P and B
        for h, bit, f, lk, lq, rk in reversed(st.levels):
            mine = lv == h
            both = jnp.concatenate(
                [jnp.where(mine, da, 0.0).astype(dtype),
                 jnp.where(mine, dqk, 0.0).astype(dtype)], axis=0)
            dl = _mm(both, rk)
            drk = _tn(both, jnp.concatenate([lk, lq], axis=0))
            dkfac = jnp.where(bit, dl[:LANES], drk)
            dqfac = jnp.where(bit, dl[LANES:], 0.0)
            dk = dk + dkfac * f
            dq = dq + dqfac * f
            z = (dkfac * kf + dqfac * qf) * f
            dother = jnp.where(bit, dp, 0.0) + db
            dp = dp + jnp.where(bit, z, -z)
            db = db + jnp.where(bit, 0.0, z) + _partner(dother, bit, h)
        dg = dp + db
        if eps is not None:
            dq = _normed_back(qx, qr, dq, LANES ** -0.5)
            dk = _normed_back(kx, kr, dk, 1.0)
        for u, head in enumerate(heads):
            at = slice(u * chunk, (u + 1) * chunk)
            lanes = slice(head * LANES, (head + 1) * LANES)
            dq_ref[0, :, lanes] = dq[at].astype(dq_ref.dtype)
            dk_ref[0, :, lanes] = dk[at].astype(dk_ref.dtype)
            dv_ref[0, :, lanes] = dv[at].astype(dv_ref.dtype)
            dg_ref[0, :, lanes] = dg[at]
            dbeta_all = jnp.where(lane == head, dbeta[at], dbeta_all)
    dbeta_ref[0, 0] = dbeta_all[:, :hv]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _specs(chunk: int, hv: int, chunk_of):
    """The block specs both kernels share, for grid point ``(row, step,
    visit's block of heads)`` at chunk ``chunk_of(step)``: a wide operand's
    block (``wide(first)``: the visit's lanes ``first`` blocks of lanes
    further on, for a cut of a packed array), ``beta``'s, the levels' mask's
    and the opening states'."""
    vm = pltpu.VMEM

    def wide(first: int = 0):
        return pl.BlockSpec((1, chunk, hv * LANES), lambda i, c, j:
                            (i, chunk_of(c), first + j), memory_space=vm)

    beta = pl.BlockSpec((1, 1, chunk, hv),
                        lambda i, c, j: (i, j, chunk_of(c), 0),
                        memory_space=vm)
    lv = pl.BlockSpec((LANES, LANES), lambda i, c, j: (0, 0),
                      memory_space=vm)
    opening = pl.BlockSpec((1, 1, hv * LANES, LANES),
                           lambda i, c, j: (i, chunk_of(c), j, 0),
                           memory_space=vm)
    return wide, beta, lv, opening


def _by_visits(beta, hv: int):
    """``(b, t, heads)`` -> ``(b, heads / hv, t, hv)``: a visit's heads side
    by side."""
    b, t, heads = beta.shape
    return beta.reshape(b, t, heads // hv, hv).transpose(0, 2, 1, 3)


def _cuts(q, heads: int, hv: int):
    """The three operands' first blocks of lanes: of separate ``q``, ``k``,
    ``v`` ``(b, t, H 128)`` each its own first; of ONE packed ``q | k | v
    (b, t, 3 H 128)`` handed over three times (``q`` three times as wide as
    the heads) the three cuts'."""
    packed = q.shape[2] == 3 * heads * LANES
    return tuple(n * (heads // hv) if packed else 0 for n in range(3))


@partial(jax.jit, static_argnames=("chunk", "eps", "interpret"))
def delta_fwd(q, k, v, g, beta, *, chunk: int, eps, interpret: bool):
    """-> ``(o (b, t, H V) in q's dtype, each chunk's opening state (b, t /
    chunk, H V, K) transposed and in q's dtype, the state behind the last
    position (b, H V, K) transposed, float32)``.

    ``q``, ``k``, ``v`` ``(b, t, H 128)`` in the compute dtype, or one packed
    ``q | k | v (b, t, 3 H 128)`` handed over three times, each cut from its
    lanes by its block spec; ``g (b, t, H 128)`` float32 (the log-decays, at
    most 0), ``beta (b, t, H)`` float32; ``eps``: None for ``q`` and ``k``
    as they are, else a head's rows of both are L2-normed in the kernel
    (``x / sqrt(sum(x^2) + eps)``, the queries times ``128^-1/2``), float32;
    ``t`` a multiple of ``chunk``."""
    b, t, inner = g.shape
    heads = inner // LANES
    hv = heads_a_visit(chunk, heads)
    firsts = _cuts(q, heads, hv)
    chunks = t // chunk
    wide, beta_spec, lv_spec, opening = _specs(chunk, hv, lambda c: c)
    return pl.pallas_call(
        partial(_fwd_kernel, chunk=chunk, eps=eps),
        grid=(b, chunks, heads // hv),
        in_specs=[*(wide(first) for first in firsts), wide(), beta_spec,
                  lv_spec],
        # a row's whole state stays in VMEM over the row's visits: it is
        # the carry, and leaves once as the state behind the last position
        out_specs=[wide(), opening,
                   pl.BlockSpec((1, inner, LANES), lambda i, c, j: (i, 0, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[_out_struct((b, t, inner), q.dtype, q),
                   _out_struct((b, chunks, inner, LANES), q.dtype, q),
                   _out_struct((b, inner, LANES), _F32, q)],
        compiler_params=_PARAMS,
        name=FWD_KERNEL_NAME,
        interpret=interpret,
    )(q, k, v, g, _by_visits(beta, hv), jnp.asarray(_levels_of(chunk)))


@partial(jax.jit, static_argnames=("chunk", "eps", "interpret"))
def delta_bwd(q, k, v, g, beta, opening, do, dlast, *, chunk: int, eps,
              interpret: bool):
    """-> ``(dq, dk, dv (b, t, H 128) in q's dtype, dg float32 like g, dbeta
    float32 (b, t, H))``: the gradients of ``sum(o * do) + sum(last *
    dlast)`` (:func:`delta_fwd`; ``dlast (b, H V, K)`` transposed as
    ``last`` is) from the scores and the inverse made again in the chunk and
    the kept ``opening`` states."""
    b, t, inner = g.shape
    heads = inner // LANES
    hv = heads_a_visit(chunk, heads)
    firsts = _cuts(q, heads, hv)
    chunks = t // chunk
    back = lambda c: chunks - 1 - c                         # noqa: E731
    wide, beta_spec, lv_spec, opening_spec = _specs(chunk, hv, back)
    like = _out_struct(g.shape, q.dtype, q)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        partial(_bwd_kernel, chunk=chunk, eps=eps),
        grid=(b, chunks, heads // hv),
        in_specs=[*(wide(first) for first in firsts), wide(), beta_spec,
                  lv_spec, opening_spec, wide(),
                  pl.BlockSpec((1, inner, LANES), lambda i, c, j: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[wide(), wide(), wide(), wide(), beta_spec],
        out_shape=[like, like, like, _out_struct(g.shape, _F32, q),
                   _out_struct((b, heads // hv, t, hv), _F32, q)],
        scratch_shapes=[pltpu.VMEM((inner, LANES), _F32)],
        compiler_params=_PARAMS,
        name=BWD_KERNEL_NAME,
        interpret=interpret,
    )(q, k, v, g, _by_visits(beta, hv), jnp.asarray(_levels_of(chunk)),
      opening, do, dlast)
    return dq, dk, dv, dg, dbeta.transpose(0, 2, 1, 3).reshape(b, t, heads)


def _forward(q, k, v, g, beta, chunk, eps, interpret):
    """:func:`delta_fwd` with its results named and the last state as the
    layer has it, ``(b, H, K, V)`` -> ``(o, last, the opening states)``."""
    o, opening, last = delta_fwd(q, k, v, g, beta, chunk=chunk, eps=eps,
                                 interpret=interpret)
    # a kernel's results leaving a custom_vjp: named for the layer's
    # checkpoint policy (``plan.py::_KEPT_ALWAYS``), or the kernel runs twice
    o = checkpoint_name(o, "kda_y")
    opening = checkpoint_name(opening, "kda_state")
    b, inner, _ = last.shape
    last = last.reshape(b, inner // LANES, LANES, LANES).transpose(0, 1, 3, 2)
    return o, last, opening


def _backward(q, k, v, g, beta, opening, cts, chunk, eps, interpret):
    do, dlast = cts
    b, heads = dlast.shape[:2]
    dlast = dlast.transpose(0, 1, 3, 2).reshape(b, heads * LANES, LANES)
    return delta_bwd(q, k, v, g, beta, opening, do, dlast, chunk=chunk,
                     eps=eps, interpret=interpret)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def delta(q, k, v, g, beta, chunk: int, interpret: bool):
    """The rule over whole chunks by the two kernels, differentiable in its
    five operands: ``q``, ``k``, ``v`` ``(b, t, H 128)`` in one dtype, ``g``
    like them float32, ``beta (b, t, H)`` float32 -> ``(o (b, t, H 128) in
    that dtype, the state behind the last position (b, H, K, V)
    float32)``."""
    return _delta_fwd(q, k, v, g, beta, chunk, interpret)[0]


def _delta_fwd(q, k, v, g, beta, chunk, interpret):
    o, last, opening = _forward(q, k, v, g, beta, chunk, None, interpret)
    return (o, last), (q, k, v, g, beta, opening)


def _delta_bwd(chunk, interpret, kept, cts):
    return _backward(*kept, cts, chunk, None, interpret)


delta.defvjp(_delta_fwd, _delta_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def delta_packed(qkv, g, beta, chunk: int, eps: float, interpret: bool):
    """:func:`delta` on the layer's own ``q | k | v (b, t, 3 H 128)`` as
    its convolution leaves it, each of the three cut from its lanes by a
    block spec (no copy of any is prepared), a head's rows of ``q`` and ``k``
    L2-normed in the kernels (``eps`` under the root; the queries times
    ``128^-1/2``); differentiable in ``qkv`` (ONE cotangent, the three cuts'
    side by side), ``g`` and ``beta``."""
    return _packed_fwd(qkv, g, beta, chunk, eps, interpret)[0]


def _packed_fwd(qkv, g, beta, chunk, eps, interpret):
    o, last, opening = _forward(qkv, qkv, qkv, g, beta, chunk, eps,
                                interpret)
    return (o, last), (qkv, g, beta, opening)


def _packed_bwd(chunk, eps, interpret, kept, cts):
    qkv, g, beta, opening = kept
    dq, dk, dv, dg, dbeta = _backward(qkv, qkv, qkv, g, beta, opening, cts,
                                      chunk, eps, interpret)
    return jnp.concatenate([dq, dk, dv], axis=-1), dg, dbeta


delta_packed.defvjp(_packed_fwd, _packed_bwd)
