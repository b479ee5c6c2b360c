"""The Mamba-2 scan's chunk work as Pallas kernels (``parallel/ssm.py`` has
the layer, the mathematics and the ``jax.numpy`` form these stand in for):
steps 1, 2 and 4 of :func:`~znicz_tpu.parallel.ssm.ssd` and, with the chunk
axis of the grid sequential, the carry between chunks (3) too.  In the
``jax.numpy`` form a chunk's ``(heads, Q, Q)`` float32 decay matrix, its
product with the group's scores and that product's 16-bit copy go through
HBM in every pass; here nothing with two chunk-length axes leaves VMEM.

**Forward** (:data:`FWD_KERNEL_NAME`): a grid of ``(row, chunk, block of
:data:`HEAD_BLOCK` heads)``, the chunks in order and a group's blocks of
heads one after the other (its ``B`` and ``C`` are fetched once).  A visit
makes ``scores = C B^T (Q, Q)`` once (a block of heads lies inside one
group) and for each head ``L = exp(mask(cs_i - cs_j))`` in float32, ``(scores
* L)`` rounded to the operands' dtype times ``dt x``, plus ``exp(cs_i) * (C
h^T)`` of the chunk's opening state ``h``, plus the skip.  The state of the
row's heads, ``(heads x P, N)`` float32, stays in VMEM over the row's visits
(it is the resident block of the output that leaves as the state behind the
last position): a visit writes its block's part out as the chunk's OPENING
state, cast to the operands' dtype as its product reads it (what the
backward pass keeps: half the bytes, and the form the ``jax.numpy`` path's
compiled step kept too), then ``h <- exp(cs_last) h + (dt x exp(cs_last -
cs))^T B``.

What is whole lanes wide is done whole lanes wide.  Everything a position
and an entry of a head needs (``dt x``, the skip, the carried state's part,
the closing state's operand) is made for a SPAN of lanes at a time (two
heads of 64 share 128 lanes), from each head's ``dt`` and ``cs`` spread
over its lanes once (:class:`_Span`); the products that involve the state
run on such spans too, 128 rows or columns of the state a product.  Only the
quadratic form differs a head: its ``(Q, Q)`` operand times the span's ``dt
x`` with the other heads' lanes ZEROED (:meth:`_Span.only`), so that a head's
product lands in its own lanes of the span's result, full width, and no
operand or result is ever cut, shifted or joined at half a lane tile (on a
v5e a ``(256, 64)`` float32 value costs what a ``(256, 128)`` one does, a
lane broadcast of a ``(256, 1)`` column five times an elementwise pass over
``(256, 256)``, and a sum over half a lane tile thirty times; my chip run,
PR 46).

**Backward** (:data:`BWD_KERNEL_NAME`): the same visits with the chunks in
reverse and the closing state's cotangent, a row's whole, carried in VMEM.
It makes ``scores`` and ``L`` again from the operands and reads the kept
opening state; returns ``dx``, a group's ``dB`` and ``dC`` (float32, summed
in their block over the group's blocks of heads, which follow one another),
and a head's cotangents a position of ``dt``, of the running sum ``cs`` and
of the skip.  What the decays contribute to ``d cs`` is row sums less column
sums of ``W = (dY (dt x)^T) * G``, ``G = scores * L``; neither reads ``W``:
the row sums are ``sum_p dY_ip (G dt x)_ip`` and the column sums ``sum_p (dt
x)_jp (G^T dY)_jp``, sums over a head's entries of products the spans have
(both with ``G`` as the products take it, rounded to the operands' dtype: the
two cancel over a chunk as the exact ones do).  Those three sums over a
head's lanes a position (``d dt``, ``d cs``, ``d skip``) run on the MXU
against 0/1 weights that drop each head's sum into a lane of its own
(:func:`_sum_heads`), the float32 summand in two or three 16-bit terms.

Precision is ``ssm.py``'s: products on the operands' dtype with float32
accumulation; decays, running sums and the carried state (and its
cotangent) float32; a state enters a product cast to the operands' dtype;
every decay an ``exp`` of a masked sum that is at most 0.

Layouts.  ``x``, ``B`` and ``C`` come as the layer's convolution leaves
them, one ``(b, t, heads x P + 2 x groups x N)`` array: a block of heads is
a block of lanes (a head ``P`` lanes of it) and a group's ``B`` or ``C`` a
block of ``N`` lanes further on, each cut by its block spec, so that no copy
of any of the three is prepared; ``y`` and the cotangents of ``y`` and ``x``
``(b, t, heads x P)``, ``dB`` and ``dC`` float32 ``(b, t, groups x N)``.  A
head's ``dt`` and ``cs`` are needed along the rows of ``L`` and along its
columns: they come heads-major, ``(b, blocks, 2 x HEAD_BLOCK, t)`` float32 (a
row a head: ``dt`` then ``cs``), positions in the lanes, and the kernel
transposes the ``(16, Q)`` block once a visit for the other form; the
cotangents leave the same way, ``(b, blocks, 3 x HEAD_BLOCK, t)``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct
from znicz_tpu.ops.pallas.attention import _nt

#: the kernels' names in the lowered program and in device traces
FWD_KERNEL_NAME = "ssd_scan_fwd"
BWD_KERNEL_NAME = "ssd_scan_bwd"
#: heads a visit holds; a group's heads are whole blocks
HEAD_BLOCK = 8
#: positions a chunk holds are whole lane tiles
LANES = 128
#: scoped VMEM asked of the compiler (the blocked flash kernels' limit)
_VMEM_LIMIT = 32 * 1024 * 1024


def scan_vmem(q: int, heads: int, p: int, n: int, itemsize: int) -> int:
    """Bytes of VMEM the backward kernel's visit takes at most (the forward
    kernel's takes less), for chunks of ``q`` positions of ``heads`` heads
    of ``p``, a state of ``n`` and operands of ``itemsize`` bytes: the
    blocks of ``x``, ``dy``, ``dx``, ``B``, ``C``, the float32 ``dB``,
    ``dC``, the rows of ``dt`` / ``cs`` and their cotangents, the opening
    state (counted float32) and a row's whole last state's cotangent,
    double-buffered, and the head sums' weights; a row's whole carried
    cotangent in scratch; live
    values: eight ``(q, q)`` float32 (scores, their gradient's sum and a
    head's ``L``, ``G``, its 16-bit copy, ``dG``), the two 16-bit ``(q,
    block x p)`` slabs the state's products read and four float32 ones,
    three ``(q, 128)`` and three of the block's states.  15.2 MiB at 256
    positions, 64 heads of 64, a state of 128 in 16 bits; 26.4 at 512; 60.8
    at 1,024."""
    wide = HEAD_BLOCK * p
    blocks = 2 * (3 * q * wide * itemsize + 2 * q * n * itemsize +
                  2 * q * n * 4 + 5 * HEAD_BLOCK * q * 4 + wide * n * 4 +
                  heads * p * n * 4 + 3 * wide * LANES * 2)
    held = heads * p * n * 4 + 2 * q * wide * itemsize
    live = (8 * q * q + 4 * q * wide + 3 * q * LANES + 3 * wide * n) * 4
    return blocks + held + live


def unsupported_reason(q: int, heads: int, groups: int, p: int, n: int,
                       itemsize: int) -> str | None:
    """Why the kernels cannot take chunks of ``q`` positions of ``heads``
    heads of ``p`` entries in ``groups`` groups with a state of ``n`` and
    operands of ``itemsize`` bytes, or ``None``: a chunk of whole lane
    tiles (its positions are the lanes of a head's ``dt`` and ``cs``), a
    group of whole blocks of :data:`HEAD_BLOCK` heads, a block of heads and
    a state that are whole lane tiles, the state dividing the heads' entries
    (blocks of the layer's ``(b, t, x | B | C)`` array cut by lanes), heads
    that share a lane tile whole or are whole tiles, and a
    visit inside the VMEM limit."""
    if q % LANES:
        return f"a chunk of {q} positions is no multiple of {LANES}"
    if heads % groups or (heads // groups) % HEAD_BLOCK:
        return (f"{heads} heads in {groups} groups are not whole blocks of "
                f"{HEAD_BLOCK} heads a group")
    if (HEAD_BLOCK * p) % LANES:
        return (f"head_dim={p}: a block of {HEAD_BLOCK} heads is "
                f"{HEAD_BLOCK * p} lanes, no multiple of {LANES}")
    if LANES % p and p % LANES:
        return (f"head_dim={p}: heads of {p} neither divide {LANES} lanes "
                f"nor are whole tiles of them")
    if n % LANES or (heads * p) % n:
        return (f"a state of {n} is no multiple of {LANES} that divides the "
                f"{heads * p} entries of the heads")
    need = scan_vmem(q, heads, p, n, itemsize)
    if need > _VMEM_LIMIT:
        return (f"a chunk of {q} positions needs {need >> 20} MiB of the "
                f"kernels' {_VMEM_LIMIT >> 20} MiB of VMEM")
    return None


def _tn(a, b):
    """``a.T @ b`` in float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mm(a, b):
    """``a @ b`` in float32."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _columns(rows):
    """``rows (k, q)`` float32, ``k`` at most 128 -> ``(q, 128)``: column
    ``j`` is row ``j`` (a transpose of whole tiles)."""
    k, q = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((LANES - k, q), jnp.float32)], axis=0).T


def _seen(q: int):
    """``(q, q)``: position ``j`` is at or before ``i``."""
    return jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


class _Span:
    """What a visit knows of the ``heads a span`` heads whose entries share
    a span of lanes (two heads of 64 in 128 lanes; one head of 128 or more
    a span of its own): their ``dt`` and ``cs`` along the positions, each
    head's value in its own lanes, ``(q, span)`` float32, from the
    ``(q, 128)`` columns of the visit's rows.  A head's column is spread
    over the lanes once (the one costly step: a lane broadcast a head and
    a quantity) and everything else a position and a head needs (``exp(cs)``,
    the decays to the chunk's end) is made from the spread arrays, whole
    lanes at a time."""

    def __init__(self, rows, cols, first: int, masks: list, p: int):
        q, count = cols.shape[0], len(masks)
        self.rows, self.first, self.count, self.p = rows, first, count, p
        #: ``(q, 128)``: a head's lanes of the span; ``[None]`` of one head
        self.masks = masks
        over = lambda k: jnp.broadcast_to(                  # noqa: E731
            cols[:, k:k + 1], (q, LANES))
        #: each head's ``cs`` over all lanes: the rows of its ``L``
        self.cs_lanes = [over(HEAD_BLOCK + first + u) for u in range(count)]
        self.dt = self._spread([over(first + u) for u in range(count)])
        self.cs = self._spread(self.cs_lanes)
        self.ecs = jnp.exp(self.cs)
        self.to_end = jnp.exp(self.cs[q - 1:q, :] - self.cs)

    def _spread(self, lanes):
        """The heads' ``(q, 128)`` arrays, each in its head's lanes."""
        if self.count == 1:
            return jnp.concatenate(lanes * (self.p // LANES), axis=1)
        out = lanes[-1]
        for u in range(self.count - 2, -1, -1):
            out = jnp.where(self.masks[u], lanes[u], out)
        return out

    def only(self, v, u: int, dtype):
        """``v (q, span)`` float32 with every head's lanes but head ``u``'s
        zeroed, in ``dtype``: an operand whose product takes one head's
        entries and leaves its result in that head's lanes."""
        if self.count > 1:
            v = jnp.where(self.masks[u], v, 0.0)
        return v.astype(dtype)

    def decay(self, u: int, seen):
        """``L (q, q)`` of head ``u``: ``exp(cs_i - cs_j)`` at and under
        the diagonal, 0 above, masked before the ``exp``."""
        q = seen.shape[0]
        h = HEAD_BLOCK + self.first + u
        down = jnp.concatenate([self.cs_lanes[u]] * (q // LANES), axis=1)
        return jnp.exp(jnp.where(seen, down - self.rows[h:h + 1, :],
                                 -jnp.inf))


def _spans(rows, cols, p: int, wide: int):
    """The visit's spans of lanes: ``(lanes' slice, _Span)``."""
    span = max(p, LANES)
    count = span // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols.shape[0], LANES), 1)
    masks = [(lane >= u * p) & (lane < (u + 1) * p)
             for u in range(count)] if count > 1 else [None]
    return [(slice(s * span, (s + 1) * span),
             _Span(rows, cols, s * count, masks, p))
            for s in range(wide // span)]


def _state_rows(j, wide: int, at=None):
    """The rows of a row's whole state ``(heads x p, n)`` that belong to
    block of heads ``j`` (traced), or to its lanes' slice ``at``."""
    at = at or slice(0, wide)
    return pl.ds(pl.multiple_of(j * wide + at.start, LANES),
                 at.stop - at.start)


def _keep(rows, at, p: int):
    """``exp(cs_last)`` of the heads whose state rows ``at`` holds, a
    ``(rows, 1)`` column: what scales the carried state over a chunk."""
    q = rows.shape[1]
    whole = jnp.exp(rows[HEAD_BLOCK:, q - 1:q])             # (heads, 1)
    return jnp.concatenate(
        [jnp.broadcast_to(whole[h:h + 1, :], (p, 1))
         for h in range(at.start // p, at.stop // p)], axis=0)


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, skip_ref, y_ref, open_ref,
                last_ref, *, p: int):
    q, wide = x_ref.shape[1:]
    c, j = pl.program_id(1), pl.program_id(2)
    mine = _state_rows(j, wide)

    @pl.when(c == 0)
    def _init():
        last_ref[0, mine, :] = jnp.zeros((wide, last_ref.shape[2]),
                                         jnp.float32)

    h0 = last_ref[0, mine, :]
    bm, cm = b_ref[0], c_ref[0]
    dtype = bm.dtype
    # what the backward pass keeps is what the products read: the opening
    # state in the operands' dtype (the carry itself stays float32 here)
    open_ref[0, 0] = h0b = h0.astype(dtype)
    scores = _nt(cm, bm)
    rows = rows_ref[0, 0]
    carried = _nt(cm, h0b)                                 # (q, heads x p)
    seen = _seen(q)
    for at, sp in _spans(rows, _columns(rows), p, wide):
        xf = x_ref[0, :, at].astype(jnp.float32)
        xdt = xf * sp.dt
        y = None
        for u in range(sp.count):
            m = (scores * sp.decay(u, seen)).astype(dtype)
            part = _mm(m, sp.only(xdt, u, dtype))
            y = part if y is None else y + part
        y = y + carried[:, at] * sp.ecs
        y = y + skip_ref[:, at] * xf
        y_ref[0, :, at] = y.astype(y_ref.dtype)
        xw = (xf * (sp.dt * sp.to_end)).astype(dtype)
        last_ref[0, _state_rows(j, wide, at), :] = \
            _keep(rows, at, p) * h0[at] + _tn(xw, bm)


def _terms(z, passes: int):
    """``z`` float32 as ``passes`` 16-bit terms, 8 bits of it each: what a
    product with exact 0/1 weights sums to ``z``'s float32 at three."""
    out = []
    for k in range(passes):
        out.append(z.astype(jnp.bfloat16))
        if k + 1 < passes:
            z = z - out[-1].astype(jnp.float32)
    return out


def _sum_heads(z, ones, passes: int):
    """``z (q, span)`` float32 summed over each head's lanes by the MXU,
    ``-> (q, 128)`` with a head's sum in the lane ``ones (span, 128)`` (16
    bits, a head's rows 1 in its lane) gives it."""
    return sum(_mm(term, ones) for term in _terms(z, passes))


def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, skip_ref, open_ref, dy_ref,
                dlast_ref, ones_ref, dx_ref, db_ref, dc_ref, drows_ref,
                dh_sc, *, p: int, per_group: int):
    q, wide = x_ref.shape[1:]
    c, j = pl.program_id(1), pl.program_id(2)
    mine = _state_rows(j, wide)

    @pl.when(c == 0)
    def _init():
        dh_sc[mine, :] = dlast_ref[0, mine, :]

    dh1, h0b = dh_sc[mine, :], open_ref[0, 0]
    bm, cm = b_ref[0], c_ref[0]
    dtype = bm.dtype
    dh1b, h0 = dh1.astype(dtype), h0b.astype(jnp.float32)
    scores = _nt(cm, bm)
    rows = rows_ref[0, 0]
    carried = _nt(cm, h0b)                                  # C h^T
    dxw_all = _nt(bm, dh1b)                                 # B dh^T
    # sum over a state row's entries of dh x h, a row a lane: what the
    # opening state's decay over the chunk gathers, a head's rows summed
    # with the rest below
    over = jnp.ones((8, h0.shape[1]), jnp.bfloat16)
    gathered = sum(_nt(over, term) for term in _terms(dh1 * h0, 3))[:1]
    seen = _seen(q)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dscores = jnp.zeros((q, q), jnp.float32)
    out = jnp.zeros((q, LANES), jnp.float32)
    xws, dyes = [], []
    for at, sp in _spans(rows, _columns(rows), p, wide):
        xf = x_ref[0, :, at].astype(jnp.float32)
        dyf = dy_ref[0, :, at].astype(jnp.float32)
        xdt32 = xf * sp.dt
        xdt = xdt32.astype(dtype)
        dxdt = yin = None
        for u in range(sp.count):
            decay = sp.decay(u, seen)
            gb = (scores * decay).astype(dtype)
            dy = sp.only(dyf, u, dtype)
            dscores = dscores + _nt(dy, xdt) * decay        # dY (dt x)^T * L
            a, b = _tn(gb, dy), _mm(gb, sp.only(xdt32, u, dtype))
            dxdt, yin = (a, b) if dxdt is None else (dxdt + a, yin + b)
        wt = sp.dt * sp.to_end
        dxw = dxw_all[:, at]
        xw32 = xf * wt
        # d cs: row sums less column sums of W = dG * G, each a sum over a
        # head's entries (``dy . (G dt x)`` and ``dt x . (G^T dy)``), the
        # carried state's decay, the closing state's decays to the end;
        # and on the chunk's last position what that one gathers
        z_cs = dyf * (yin + sp.ecs * carried[:, at]) - \
            xdt.astype(jnp.float32) * dxdt - dxw * xw32
        z_cs = z_cs + jnp.where(
            at_last, (dxw * xw32).sum(axis=0, keepdims=True) +
            sp.ecs[q - 1:q, :] * gathered[:, at], 0.0)
        z_dt = xf * (dxdt + dxw * sp.to_end)
        dx = dxdt * sp.dt + dxw * wt + skip_ref[:, at] * dyf
        dx_ref[0, :, at] = dx.astype(dx_ref.dtype)
        xws.append(xw32.astype(dtype))
        dyes.append((dyf * sp.ecs).astype(dtype))
        out = out + _sum_heads(z_dt, ones_ref[0, at], 2) + \
            _sum_heads(z_cs, ones_ref[1, at], 3) + \
            _sum_heads(dyf * xf, ones_ref[2, at], 2)
    dsb = dscores.astype(dtype)
    xw, dye = (jnp.concatenate(v, axis=1) for v in (xws, dyes))
    dc = _mm(dsb, bm) + _mm(dye, h0b)
    db = _tn(dsb, cm) + _mm(xw, dh1b)
    # a group's blocks of heads follow one another: the first writes the
    # group's block of dB and dC, the others add to it
    first = j % per_group == 0

    @pl.when(first)
    def _write():
        dc_ref[0], db_ref[0] = dc, db

    @pl.when(jnp.logical_not(first))
    def _add():
        dc_ref[0] += dc
        db_ref[0] += db

    dh_sc[mine, :] = _keep(rows, slice(0, wide), p) * dh1 + _tn(dye, cm)
    drows_ref[0, 0] = out.T[:3 * HEAD_BLOCK]


def _rows_of(v, blocks: int):
    """``(b, t, heads)`` -> ``(b, blocks, HEAD_BLOCK, t)``, heads-major."""
    b, t, _ = v.shape
    return v.transpose(0, 2, 1).reshape(b, blocks, HEAD_BLOCK, t)


def _heads_of(v):
    """:func:`_rows_of`, undone."""
    b, blocks, hb, t = v.shape
    return v.reshape(b, blocks * hb, t).transpose(0, 2, 1)


def _specs(q: int, wide: int, n: int, per_group: int, chunk_of):
    """The block specs both kernels share: ``x``'s block (``y``'s, ``dy``'s
    and ``dx``'s too), a group's of ``B`` (``C``'s, and their float32
    gradients' too), the rows' (by their count of rows) and the skip's, for
    grid point ``(row, step, block of heads)`` at chunk ``chunk_of(step)``:
    the blocks of heads innermost, so that a group's blocks follow one
    another (its ``B`` and ``C`` are fetched once, its ``dB`` and ``dC``
    summed in their block)."""
    vm = pltpu.VMEM
    skip_spec = pl.BlockSpec((1, wide), lambda i, c, j: (0, j),
                             memory_space=vm)
    wide_spec = pl.BlockSpec((1, q, wide), lambda i, c, j: (i, chunk_of(c), j),
                             memory_space=vm)
    def group_spec(first: int = 0):
        return pl.BlockSpec((1, q, n), lambda i, c, j:
                            (i, chunk_of(c), first + j // per_group),
                            memory_space=vm)

    def rows_spec(k: int):
        return pl.BlockSpec((1, 1, k * HEAD_BLOCK, q), lambda i, c, j:
                            (i, j, 0, chunk_of(c)), memory_space=vm)
    return wide_spec, group_spec, rows_spec, skip_spec


def _lanes_of(skip, p: int):
    """``skip (heads,)`` -> ``(1, heads x p)``: a head's value in each of
    its lanes."""
    return jnp.repeat(skip, p)[None, :]


def _head_sums(p: int):
    """``(3, HEAD_BLOCK x p, 128)`` 16-bit 0/1 weights: a product with the
    ``k``-th sums each head's ``p`` lanes into lane ``k x HEAD_BLOCK +
    head`` (:func:`_sum_heads`): the three cotangents a head and a position
    land side by side, as their rows leave."""
    head = jnp.arange(HEAD_BLOCK * p)[:, None] // p
    lane = jnp.arange(LANES)[None, :]
    return jnp.stack([lane == k * HEAD_BLOCK + head
                      for k in range(3)]).astype(jnp.bfloat16)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _sizes(xbc, rows, inner: int, groups: int):
    """-> ``(blocks of heads, a block's lanes, N, P)`` of a packed operand
    ``(b, t, inner + 2 x groups x N)`` and its rows."""
    blocks = rows.shape[1]
    wide, n = inner // blocks, (xbc.shape[2] - inner) // (2 * groups)
    return blocks, wide, n, wide // HEAD_BLOCK


@partial(jax.jit, static_argnames=("inner", "groups", "q", "interpret"))
def scan_fwd(xbc, rows, skip, *, inner: int, groups: int, q: int,
             interpret: bool):
    """-> ``(y (b, t, heads x P) and each chunk's opening state (b, t / q,
    heads x P, N) in xbc's dtype, the state behind the last position (b,
    heads x P, N) float32)``.

    ``xbc`` ``(b, t, inner + 2 x groups x N)`` in the compute dtype: ``x``
    (``inner`` = heads x P lanes), ``B`` and ``C`` (``groups x N`` lanes
    each) side by side as the layer's convolution leaves them, each cut from
    the lanes by its block spec (no copy of any is prepared); ``rows``
    float32 ``(b, heads / HEAD_BLOCK, 2 x HEAD_BLOCK, t)``: a block of
    heads' ``dt`` (after the softplus), then their running sums of ``dt A``
    inside each chunk of ``q`` positions; ``skip`` float32 ``(heads,)``;
    ``t`` a multiple of ``q``."""
    b, t, _ = xbc.shape
    blocks, wide, n, p = _sizes(xbc, rows, inner, groups)
    chunks = t // q
    wide_spec, group_spec, rows_spec, skip_spec = _specs(
        q, wide, n, blocks // groups, lambda c: c)
    vm = pltpu.VMEM
    return pl.pallas_call(
        partial(_fwd_kernel, p=p),
        grid=(b, chunks, blocks),
        in_specs=[wide_spec, group_spec(inner // n),
                  group_spec(inner // n + groups), rows_spec(2), skip_spec],
        # a row's whole state stays in VMEM over the row's visits: it is
        # the carry, and leaves once as the state behind the last position
        out_specs=[
            wide_spec,
            pl.BlockSpec((1, 1, wide, n), lambda i, c, j: (i, c, j, 0),
                         memory_space=vm),
            pl.BlockSpec((1, inner, n), lambda i, c, j: (i, 0, 0),
                         memory_space=vm)],
        out_shape=[_out_struct((b, t, inner), xbc.dtype, xbc),
                   _out_struct((b, chunks, inner, n), xbc.dtype, xbc),
                   _out_struct((b, inner, n), jnp.float32, xbc)],
        compiler_params=_PARAMS,
        name=FWD_KERNEL_NAME,
        interpret=interpret,
    )(xbc, xbc, xbc, rows, _lanes_of(skip, p))


@partial(jax.jit, static_argnames=("inner", "groups", "q", "interpret"))
def scan_bwd(xbc, rows, skip, opening, dy, dlast, *, inner: int, groups: int,
             q: int, interpret: bool):
    """-> ``(dx like y, dB, dC float32 (b, t, groups x N), d rows float32
    (b, heads / HEAD_BLOCK, 3 x HEAD_BLOCK, t): a block of heads'
    cotangents a position of dt, of the running sums and of the skip)``:
    the gradients of ``sum(y * dy) + sum(last * dlast)`` (:func:`scan_fwd`)
    from ``scores`` and the decays made again in the chunk and the kept
    ``opening`` states (in xbc's dtype)."""
    b, t, _ = xbc.shape
    blocks, wide, n, p = _sizes(xbc, rows, inner, groups)
    chunks, per_group = t // q, blocks // groups
    back = lambda c: chunks - 1 - c                         # noqa: E731
    wide_spec, group_spec, rows_spec, skip_spec = _specs(q, wide, n,
                                                         per_group, back)
    vm = pltpu.VMEM
    summed = _out_struct((b, t, groups * n), jnp.float32, xbc)
    return pl.pallas_call(
        partial(_bwd_kernel, p=p, per_group=per_group),
        grid=(b, chunks, blocks),
        in_specs=[
            wide_spec, group_spec(inner // n),
            group_spec(inner // n + groups), rows_spec(2), skip_spec,
            pl.BlockSpec((1, 1, wide, n), lambda i, c, j:
                         (i, back(c), j, 0), memory_space=vm),
            wide_spec,
            pl.BlockSpec((1, inner, n), lambda i, c, j: (i, 0, 0),
                         memory_space=vm),
            pl.BlockSpec((3, wide, LANES), lambda i, c, j: (0, 0, 0),
                         memory_space=vm)],
        out_specs=[wide_spec, group_spec(), group_spec(), rows_spec(3)],
        out_shape=[_out_struct(dy.shape, xbc.dtype, xbc), summed, summed,
                   _out_struct((b, blocks, 3 * HEAD_BLOCK, t), jnp.float32,
                               xbc)],
        scratch_shapes=[pltpu.VMEM((inner, n), jnp.float32)],
        compiler_params=_PARAMS,
        name=BWD_KERNEL_NAME,
        interpret=interpret,
    )(xbc, xbc, xbc, rows, _lanes_of(skip, p), opening, dy, dlast,
      _head_sums(p))


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def scan(xbc, dt, cs, skip, inner: int, groups: int, q: int,
         interpret: bool):
    """The scan over whole chunks by the two kernels, differentiable in
    its four operands: ``xbc (b, t, inner + 2 x groups x N)`` (``x``, ``B``
    and ``C`` side by side: :func:`scan_fwd`), ``dt``, ``cs`` float32 ``(b,
    t, heads)`` (``cs`` the running sum of ``dt A`` inside each chunk of
    ``q`` positions), ``skip`` float32 ``(heads,)`` -> ``(y (b, t, inner)
    in xbc's dtype, the state behind the last position (b, inner, N)
    float32)``."""
    return _scan_fwd(xbc, dt, cs, skip, inner, groups, q, interpret)[0]


def _scan_fwd(xbc, dt, cs, skip, inner, groups, q, interpret):
    blocks = dt.shape[2] // HEAD_BLOCK
    rows = jnp.concatenate([_rows_of(dt, blocks), _rows_of(cs, blocks)],
                           axis=2)
    y, opening, last = scan_fwd(xbc, rows, skip, inner=inner, groups=groups,
                                q=q, interpret=interpret)
    # a kernel's results leaving a custom_vjp: named for the layer's
    # checkpoint policy (``plan.py::_KEPT_ALWAYS``), or the kernel runs twice
    y = checkpoint_name(y, "ssm_y")
    opening = checkpoint_name(opening, "ssm_state")
    return (y, last), (xbc, rows, skip, opening)


def _scan_bwd(inner, groups, q, interpret, kept, cts):
    xbc, rows, skip, opening = kept
    dy, dlast = cts
    dx, db, dc, drows = scan_bwd(xbc, rows, skip, opening, dy, dlast,
                                 inner=inner, groups=groups, q=q,
                                 interpret=interpret)
    hb = HEAD_BLOCK
    ddt, dcs, dskip = (_heads_of(drows[:, :, k * hb:(k + 1) * hb])
                       for k in range(3))
    dxbc = jnp.concatenate([dx, db.astype(dx.dtype), dc.astype(dx.dtype)],
                           axis=2)
    return dxbc, ddt, dcs, dskip.sum(axis=(0, 1))


scan.defvjp(_scan_fwd, _scan_bwd)
