"""Flash attention as a Pallas kernel — the hot-op kernel for the
long-context stack (TPU-native extension; the reference predates
transformers, SURVEY.md §6.7, but its identity — a hand-written kernel
for every op family's hot path — is matched here for attention).

Row-block formulation: the grid walks ``(batch*heads, q_blocks)``; each
step holds one q block plus the full K/V for that head in VMEM and
computes its softmax row exactly — the ``(t, t)`` score matrix never
touches HBM (XLA's dense path materializes it twice per layer per step:
~1 GB/layer at b=8, h=8, t=2048, f32).  The saved residual is the
logsumexp row ``lse`` (one f32 per query), from which the backward kernel
reconstructs the probabilities: ``p = exp(s·scale - lse)``.

VMEM budget per grid step is O(block_q·t + t·dh) ≈ 1.5 MB at t=2048 —
fine through t≈8k.  Beyond that the sequence axis should be sharded (ring
attention, znicz_tpu/parallel/ring_attention.py); the two compose: the
ring rotates K/V blocks over ICI while each local block uses dense math,
so per-shard t stays in this kernel's range.

Backward follows the standard flash recipe in one grid pass: dq per
q block; dk/dv accumulated across q blocks into a revisited output block
(Pallas TPU grids execute sequentially, so accumulation over the minor
grid axis is sound).

Grouped-query attention rides the index maps: with ``group`` query heads
to each key/value head the folded q is ``(b*h, t, dh)`` and k, v are
``(b*h/group, t, dh)``; grid row ``i`` reads key/value row ``i // group``
(the query heads of one group are consecutive rows), so a key/value head
is fetched once for its whole group, and the backward kernel's dk/dv
block stays resident over the group's ``group * t/block_q`` consecutive
steps and sums over them.  ``group == 1`` is the program it always was.

A second, KEY/VALUE-BLOCKED form (the ``flash_attention_kvb_*`` kernels,
below the whole-row ones) takes the shapes whose whole key/value head no
longer fits: a q block meets one key/value block at a time and keeps the
running maximum, sum and accumulator in VMEM, so its VMEM is a function of
the block sizes and the head width only, and under a causal mask the
blocks wholly above the diagonal are in no kernel's grid: neither fetched
nor computed.  :func:`form_of` says which form a shape gets; the whole-row
form keeps every shape it took.

Layouts.  The whole-row kernels read FOLDED operands: ``(b, t, h, dh)``
transposed head-major to ``(b*h, t, dh)``, and ``o`` transposed back (a
pass over each of q, k, v, o, and over their four gradients under AD).
The blocked kernels read either.  With a head that is a multiple of 128
(:func:`direct_layout`) they read the layer's own array, viewed as ``(b,
t, h*dh)`` by a reshape that moves nothing: grid row ``i`` is batch ``i //
h`` and head ``i % h``, and a ``(1, block, dh)`` block at index ``(i // h,
q or key/value block, i % h)`` is ``block`` rows of ``dh`` contiguous
entries, a legal TPU block; ``o``, ``dq``, ``dk``, ``dv`` are written
through the same maps, so the output product and the projections'
backward take them as they are and no head-major copy is in the program.
The float32 rows stay the kernels' own, ``lse`` as ``(b*h, t, 1)``, and
``delta = sum(do * o, -1)``, reckoned head by head from the layer's
layout, is the one array still written head-major (``(b, h, t)`` float32).  At 64, 192, 320,
448 such a block's last axis would be neither a multiple of the 128 lanes
nor the whole axis, and the blocked kernels are handed folded operands as
the whole-row ones are.  Kernel bodies, block sizes, visit tables and
names are one set for both layouts.  The direct layout saves the copies
only if the caller's arrays ARE row-major ``(b, t, h*dh)`` on the chip:
XLA keeps them so when they are made whole rows of heads at a time (a
product's result, a row kernel's) and lays them out time-minor, with a
copy before the kernels, when a head is cut and concatenated at a column
that is no multiple of 128 (``parallel/blocks.py::_latent_qkv`` has
the forms that keep it).

A SELECTION (``flash_attention(..., sel=)``: int8 ``(b, t, t)``, one entry a
(query, key) pair, nonzero where the query attends to the key, shared by
all heads; learned sparse attention's per-query choice of keys) reaches the
blocked kernels as one more operand: a ``(block, block)`` tile at (query
block, key/value block) beside q, k and v, in all three passes (the dk/dv
pass, which holds everything transposed, reads the caller's transposed
copy).  A masked-out score is ``-1e30`` as the diagonal's are; the selection
holds the causal cut itself, so the kernels with a selection compute no
mask of their own, and their visit tables stay the causal ones (a tile
below the diagonal in which no pair is selected is still visited).  It is a
static switch: a call without a selection traces the kernels it always
traced, under the names they always had; with one the three kernels are
named ``flash_attention_kvb_sel_*``.  Nothing here holds a ``(heads, t,
t)`` array.

A WINDOW (``flash_attention(..., causal=True, window=W)``: query ``i`` sees
key ``j`` iff ``0 <= i - j < W``) reaches the blocked kernels through their
visit tables, with no operand: :func:`_visits` lists beside the causal rule
only the tiles with an entry inside the band, so a tile below the band costs
no step and no fetch, as one above the diagonal never did, and flags the
tiles the band's lower edge crosses (:data:`_LOW`), in which alone the
second mask is computed (:func:`_band_scores`, from the visit's own two
table entries).  ``W`` need be no multiple of a tile, and may be shorter
than one (the diagonal's tile then takes both cuts).  A static switch as the
selection is: ``window=None`` traces the kernels it always traced over the
tables it always walked; with one the three kernels are named
``flash_attention_kvb_swa_*``.  The whole-row form takes no window: a
windowed call is always blocked.
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: the kernels' names in the lowered program and in device traces — what
#: chip_smoke.py looks for to prove the step compiled them
FWD_KERNEL_NAME = "flash_attention_fwd"
BWD_KERNEL_NAME = "flash_attention_bwd"


def _mask_scores(s, causal: bool, iq: int, block_q: int):
    """Apply the causal mask to one q block's score rows ``(bq, t)``."""
    if not causal:
        return s
    bq, t = s.shape
    qpos = jax.lax.broadcasted_iota(jnp.int32, (bq, t), 0) + iq * block_q
    kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, t), 1)
    return jnp.where(kpos > qpos, jnp.float32(-1e30), s)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                sm_scale: float, block_q: int):
    iq = pl.program_id(1)
    q = q_ref[0]                                       # (bq, dh)
    k = k_ref[0]                                       # (t, dh)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = _mask_scores(s, causal, iq, block_q)
    m = s.max(axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=1, keepdims=True)
    # p rides the MXU at the input dtype (bf16 in production); the
    # accumulator and the 1/l normalization stay f32
    o = jnp.dot(p.astype(v.dtype), v,
                preferred_element_type=jnp.float32) / l
    o_ref[0] = o.astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                        # (bq, 1)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, causal: bool, sm_scale: float,
                block_q: int, group: int = 1):
    iq = pl.program_id(1)
    first = iq == 0
    if group > 1:
        # dk/dv sum over the group's query heads as well as over q blocks
        first = first & (pl.program_id(0) % group == 0)

    @pl.when(first)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    q = q_ref[0]                                       # (bq, dh)
    k = k_ref[0]                                       # (t, dh)
    v = v_ref[0]
    lse = lse_ref[0]                                   # (bq, 1)
    delta = delta_ref[0]                               # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = _mask_scores(s, causal, iq, block_q)
    p = jnp.exp(s - lse)                               # (bq, t)
    # dv += pᵀ @ do (p cast to the MXU input dtype; accumulate f32)
    dv_ref[0] += jax.lax.dot_general(
        p.astype(v.dtype), do_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    # ds = p ⊙ (do @ vᵀ − Δ), already includes the softmax jacobian
    dp = jax.lax.dot_general(do_ref[0], v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * sm_scale
    dsc = ds.astype(q.dtype)
    dq_ref[0] = jnp.dot(dsc, k,
                        preferred_element_type=jnp.float32
                        ).astype(dq_ref.dtype)
    # dk += dsᵀ @ q
    dk_ref[0] += jax.lax.dot_general(
        dsc, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _pick_block_q(t: int) -> int:
    # 128 rows already fill the MXU's systolic dimension; larger q blocks
    # only grow the (block_q, t) score temporaries that dominate the
    # BACKWARD kernel's VMEM working set
    return 128 if t % 128 == 0 else 0


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal: bool, interpret: bool):
    o, _ = _flash_fwd(q, k, v, causal, interpret)
    return o


from znicz_tpu.ops.pallas._elementwise import out_struct as _out_struct


def _group_of(q, k) -> int:
    """Query heads to each key/value head of folded ``q`` and ``k``."""
    if q.shape[0] % k.shape[0]:
        raise ValueError(f"{q.shape[0]} folded query heads do not divide "
                         f"over {k.shape[0]} key/value heads")
    return q.shape[0] // k.shape[0]


def _kv_block(t: int, dh: int, group: int):
    """BlockSpec of one whole key/value head, row ``i // group``."""
    index = (lambda i, j: (i, 0, 0)) if group == 1 else \
        (lambda i, j: (i // group, 0, 0))
    return pl.BlockSpec((1, t, dh), index, memory_space=pltpu.VMEM)


def _call_fwd(q, k, v, causal, interpret):
    bh, t, dh = q.shape
    block_q = _pick_block_q(t)
    group = _group_of(q, k)
    kern = partial(_fwd_kernel, causal=causal,
                   sm_scale=1.0 / float(np.sqrt(dh)), block_q=block_q)
    kv = partial(_kv_block, t, dh, group)
    qspec = pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        grid=(bh, t // block_q),
        in_specs=[qspec, kv(), kv()],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            # lse rides as (bh, t, 1): a 2-D (1, block_q) block is not a
            # legal Mosaic tile (penultimate dim 1 is neither 8-divisible
            # nor the full bh axis) — the trailing singleton makes the
            # last-two block dims (block_q, 1) == (8k-divisible, full dim)
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct((bh, t, dh), q.dtype, q),
            _out_struct((bh, t, 1), jnp.float32, q),
        ],
        name=FWD_KERNEL_NAME,
        interpret=interpret,
    )(q, k, v)


def _flash_fwd(q, k, v, causal, interpret):
    o, lse = _call_fwd(q, k, v, causal, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, interpret, res, do, dlse=None):
    q, k, v, o, lse = res
    bh, t, dh = q.shape
    block_q = _pick_block_q(t)
    group = _group_of(q, k)
    # Δ = rowsum(do ⊙ o) — the lse-side term of the softmax jacobian;
    # shaped (bh, t, 1) like lse for the same Mosaic-tiling reason.
    # When the caller also differentiates through lse (the ring×flash
    # merge), its cotangent folds into the SAME kernel:
    #   ds = p·(dp − Δ)·scale  and  ∂lse/∂s = p·scale
    #   ⇒ ds_total = p·(dp − (Δ − dlse))·scale
    # so Δ' = Δ − dlse and the backward kernel is reused unchanged.
    delta = (do.astype(jnp.float32) *
             o.astype(jnp.float32)).sum(-1, keepdims=True)
    if dlse is not None:
        delta = delta - dlse
    kern = partial(_bwd_kernel, causal=causal,
                   sm_scale=1.0 / float(np.sqrt(dh)), block_q=block_q,
                   group=group)
    kv = partial(_kv_block, t, dh, group)
    qblk3 = lambda: pl.BlockSpec((1, block_q, dh),     # noqa: E731
                                 lambda i, j: (i, j, 0),
                                 memory_space=pltpu.VMEM)
    qblk2 = lambda: pl.BlockSpec((1, block_q, 1),      # noqa: E731
                                 lambda i, j: (i, j, 0),
                                 memory_space=pltpu.VMEM)
    dq, dk, dv = pl.pallas_call(
        kern,
        grid=(bh, t // block_q),
        in_specs=[qblk3(), kv(), kv(), qblk3(), qblk2(), qblk2()],
        # dk/dv revisit the same (bh)-indexed block across the q axis —
        # sequential grid makes the += accumulation exact
        out_specs=[qblk3(), kv(), kv()],
        out_shape=[
            _out_struct((bh, t, dh), q.dtype, q),
            _out_struct(k.shape, jnp.float32, q),
            _out_struct(k.shape, jnp.float32, q),
        ],
        name=BWD_KERNEL_NAME,
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_lse(q, k, v, causal: bool = False,
                        interpret: bool = False):
    """Flash attention over FOLDED per-head tensors ``(b·h, t, dh)``
    returning ``(o, lse)`` with BOTH outputs differentiable — the
    building block for blockwise composition (ring attention merges
    per-block results by lse weight, so lse carries real cotangents).
    Same kernels as :func:`flash_attention`; the lse cotangent folds
    into the backward's Δ term (see :func:`_flash_bwd`)."""
    return _call_fwd(q, k, v, causal, interpret)


def _flash_lse_fwd(q, k, v, causal, interpret):
    o, lse = _call_fwd(q, k, v, causal, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, interpret, res, cts):
    do, dlse = cts
    return _flash_bwd(causal, interpret, res, do, dlse)


flash_attention_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# -- the key/value-blocked form -----------------------------------------------

#: the blocked kernels' names in the lowered program and in device traces
KVB_FWD_KERNEL_NAME = "flash_attention_kvb_fwd"
KVB_DKV_KERNEL_NAME = "flash_attention_kvb_dkv"
KVB_DQ_KERNEL_NAME = "flash_attention_kvb_dq"
#: the same three passes with a selection tile among their operands
KVB_SEL_KERNEL_NAMES = {"fwd": "flash_attention_kvb_sel_fwd",
                        "dkv": "flash_attention_kvb_sel_dkv",
                        "dq": "flash_attention_kvb_sel_dq"}

#: and under a window (a band of the causal triangle), whose tables list
#: fewer tiles and whose bodies mask a second kind of cut tile
KVB_SWA_KERNEL_NAMES = {"fwd": "flash_attention_kvb_swa_fwd",
                        "dkv": "flash_attention_kvb_swa_dkv",
                        "dq": "flash_attention_kvb_swa_dq"}

#: rows of a q block and of a key/value block a pass may take, largest
#: first (:func:`_kvb_block` chooses)
_KVB_BLOCKS = (1024, 512, 256, 128)
#: from this head width on the dk/dv pass keeps 512 rows: its four
#: products a tile keep the MXU busy there, and what a 1,024-row tile
#: computes and masks on the diagonal (a quarter over what causality
#: needs, against an eighth) costs more than its fewer visits save
#: (alone at 2 x 4,096 x 20 x 256: 4.604 ms against 4.638; at heads of 64
#: and 128 the larger tile wins this pass too, 3.792 against 3.977 and
#: 1.958 against 2.136; wider heads are not measured)
_KVB_DKV_MXU_HEAD = 256
#: scoped VMEM asked of the compiler for each blocked kernel
_KVB_VMEM_LIMIT = 32 * 1024 * 1024
#: what a pass holds in VMEM, counted in blocks of ``block`` rows:
#: double-buffered bf16 operand blocks ``(block, dh)`` (forward q, k, v,
#: o; dk/dv q, k, v, do, dk, dv; dq q, k, v, do, dq), f32 accumulators
#: ``(block, dh)``, f32 score tiles ``(block, block)`` (forward ``s``,
#: ``p``; both backward passes ``s``, ``p``, ``dp``, ``ds``) and f32
#: statistic columns ``(block, 1)``, each padded to the 128 lanes (forward
#: ``lse`` twice, ``m``, ``l``; dq ``lse`` and ``delta`` twice; dk/dv
#: reads them as rows, 32 bytes a position, not counted)
_KVB_HOLDS = {"fwd": (4, 1, 2, 4), "dkv": (6, 2, 4, 0), "dq": (5, 1, 4, 4)}
#: the three passes, each a kernel of its own with a tile of its own
_KVB_PASSES = tuple(_KVB_HOLDS)
#: a visit's flags: the first and the last of its run (the visits that
#: share the block the kernel accumulates for), and whether the diagonal
#: cuts the tile (only then is the mask computed); under a window, whether
#: the band's lower edge does (some pair of the tile lies ``window`` or more
#: positions apart: the second mask)
_FIRST, _LAST, _CUT, _LOW = 1, 2, 4, 8
_MASKED = -1e30


def _kvb_vmem(pass_: str, block: int, dh: int, sel: bool = False) -> int:
    """Bytes of VMEM a pass's working set takes at ``block`` rows of a
    head ``dh`` wide (:data:`_KVB_HOLDS`): at 1,024 rows of head 128 the
    forward pass 12.5 MiB, dk/dv 20 MiB, dq 21 MiB; dk/dv at head 512
    32 MiB, the whole limit.  ``sel``: with a selection tile among the
    operands, int8 ``(block, block)`` double-buffered and once more
    widened for the compare (6 bytes an entry: 6 MiB at 1,024 rows)."""
    operands, accumulators, tiles, columns = _KVB_HOLDS[pass_]
    return block * (2 * 2 * dh * operands + 4 * dh * accumulators +
                    4 * block * tiles + 4 * 128 * columns +
                    6 * block * sel)


def _kvb_block(t: int, dh: int, pass_: str, sel: bool = False) -> int:
    """Rows of the (square) tile a pass runs, by the time axis, the head
    width and the pass alone: the largest of :data:`_KVB_BLOCKS` that
    divides ``t`` and whose working set (:func:`_kvb_vmem`) fits
    :data:`_KVB_VMEM_LIMIT`; 0 when none does.  The softmax and the cost
    of a visit, not the MXU, set the pace of every pass but one, so the
    larger tile is the faster (docs/TUNING.md has the sweep, rectangles
    and 2,048 rows among it: all slower); the one is dk/dv at a wide
    head (:data:`_KVB_DKV_MXU_HEAD`), which keeps 512."""
    most = 512 if pass_ == "dkv" and dh >= _KVB_DKV_MXU_HEAD else \
        _KVB_BLOCKS[0]
    return next((b for b in _KVB_BLOCKS if b <= most and t % b == 0 and
                 _kvb_vmem(pass_, b, dh, sel) <= _KVB_VMEM_LIMIT), 0)


def kvb_block_rows(t: int, dh: int, sel: bool = False,
                   window: int | None = None) -> dict[str, int]:
    """``{pass: rows of its tile}`` for the shape, 0 in every pass where
    the shape's form (:func:`form_of`; with a selection or under a window
    always the blocked one, :func:`flash_attention`) is not the
    key/value-blocked one."""
    blocked = sel or window is not None or form_of(t, dh)[0] == "blocked"
    return {p: _kvb_block(t, dh, p, sel) if blocked else 0
            for p in _KVB_PASSES}


def _tile_in_band(i: int, j: int, block: int, window: int) -> bool:
    """Whether the tile (q block ``i``, key/value block ``j <= i``) holds a
    pair fewer than ``window`` positions apart: its nearest pair is its
    first query and its last key (the diagonal's own tile: distance 0)."""
    return i == j or (i - j - 1) * block + 1 < window


def _tile_crosses_band(i: int, j: int, block: int, window: int) -> bool:
    """Whether the tile holds a pair ``window`` or more positions apart (its
    last query and its first key are its farthest): the band's lower edge
    cuts it."""
    return (i - j + 1) * block - 1 >= window


def kvb_window_tiles(t: int, dh: int, window: int) -> dict[str, tuple]:
    """``{pass: (tiles its table lists under the window, tiles the causal
    triangle's table lists)}`` at each pass's own tile
    (:func:`kvb_block_rows`): what a window spares the blocked kernels."""
    return {p: (len(_visits(t, rows, True, p == "dkv", window)[0]),
                len(_visits(t, rows, True, p == "dkv")[0]))
            for p, rows in kvb_block_rows(t, dh, window=window).items()}


@lru_cache(maxsize=None)
def _visits(t: int, block: int, causal: bool, by_kv: bool,
            window: int | None = None):
    """The (q block, key/value block) tiles a pass visits, as three static
    int32 tables ``(q block, kv block, flags)``: under ``causal`` only the
    tiles with an unmasked entry, and under a ``window`` (query ``i`` sees
    key ``j`` iff ``0 <= i - j < window``) of those only the tiles with an
    entry inside the band, the ones its lower edge crosses flagged
    :data:`_LOW`.  Ordered by q block (the forward pass
    and dq, which accumulate over key/value blocks) or ``by_kv`` (dk and
    dv, which accumulate over q blocks); the kernels' grids walk the
    tables, so a tile that is not listed costs no step and no fetch."""
    n = t // block
    pairs = [(i, j) for i in range(n) for j in range(n)
             if not causal or j <= i]
    if window is not None:
        pairs = [ij for ij in pairs if _tile_in_band(*ij, block, window)]
    outer = 1 if by_kv else 0
    pairs.sort(key=lambda ij: (ij[outer], ij[1 - outer]))
    flags = []
    for v, (i, j) in enumerate(pairs):
        first = v == 0 or pairs[v - 1][outer] != pairs[v][outer]
        last = v + 1 == len(pairs) or pairs[v + 1][outer] != pairs[v][outer]
        flags.append(_FIRST * first + _LAST * last +
                     _CUT * (causal and i == j) +
                     _LOW * (window is not None and
                             _tile_crosses_band(i, j, block, window)))
    return (np.asarray([i for i, _ in pairs], np.int32),
            np.asarray([j for _, j in pairs], np.int32),
            np.asarray(flags, np.int32))


def _nt(a, b):
    """``a @ b.T`` in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _either_tile(flags, tile):
    """Run ``tile(cut)`` once, with the mask only where the diagonal cuts
    the tile."""
    cut = (flags & _CUT) != 0
    pl.when(cut)(partial(tile, True))
    pl.when(jnp.logical_not(cut))(partial(tile, False))


def _cut_scores(s, rows_are_q: bool):
    """The diagonal tile's mask: q and key/value blocks are as long, so
    inside the tile position equals index."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0 if rows_are_q else 1)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 if rows_are_q else 0)
    return jnp.where(kpos > qpos, jnp.float32(_MASKED), s)


def _tile_apart(qi_ref, ki_ref, block: int, window: int | None):
    """Positions by which this visit's first query lies behind its first
    key, ``(q block - key/value block) * rows``, from the visit's two table
    entries (read at the kernel's top level: a grid index is not to be had
    inside a ``pl.when``); None without a window, which alone asks."""
    if window is None:
        return None
    v = pl.program_id(1)
    return (qi_ref[v] - ki_ref[v]) * block


def _band_scores(s, rows_are_q: bool, apart, window: int):
    """The second cut, of a tile the band's lower edge crosses: ``-1e30``
    where query and key lie ``window`` or more positions apart
    (:func:`_tile_apart`)."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0 if rows_are_q else 1)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 if rows_are_q else 0)
    return jnp.where(qpos - kpos + apart >= window, jnp.float32(_MASKED), s)


def _selected_scores(s, sel_ref):
    """The tile's scores where the selection tile is nonzero, ``-1e30``
    elsewhere (the causal cut is the selection's own)."""
    return jnp.where(sel_ref[0].astype(jnp.int32) != 0, s,
                     jnp.float32(_MASKED))


def _band_tile(flags, tile):
    """Run ``tile(cut, low)`` once, each of the two masks only where its
    edge cuts the tile (both in one tile only under a window shorter than
    the tile)."""
    edges = flags & (_CUT | _LOW)
    for cut in (False, True):
        for low in (False, True):
            pl.when(edges == _CUT * cut + _LOW * low)(partial(tile, cut, low))


def _tile_of(flags, tile, sel_ref, window=None):
    """Run ``tile`` once: with a selection as it stands (the tile masks by
    its selection operand), under a window by :func:`_band_tile`, else by
    :func:`_either_tile`."""
    if sel_ref is not None:
        tile(False)
    elif window is not None:
        _band_tile(flags, tile)
    else:
        _either_tile(flags, tile)


def _kvb_fwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, *rest,
                    sm_scale: float, sel: bool = False,
                    window: int | None = None):
    sel_ref = rest[0] if sel else None
    o_ref, lse_ref, m_sc, l_sc, acc_sc = rest[sel:]
    flags = fl_ref[pl.program_id(1)]
    apart = _tile_apart(qi_ref, ki_ref, q_ref.shape[1], window)

    @pl.when((flags & _FIRST) != 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _MASKED)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def tile(cut: bool, low: bool = False):
        v = v_ref[0]
        s = _nt(q_ref[0], k_ref[0]) * sm_scale             # (bq, bk)
        if cut:
            s = _cut_scores(s, True)
        if low:
            s = _band_scores(s, True, apart, window)
        if sel:
            s = _selected_scores(s, sel_ref)
        m_prev = m_sc[...]
        # key/value block 0 comes first and every query sees key 0, so
        # the running maximum is a real score from the first visit on.
        # (Under a selection a query's first tiles may hold none of its
        # keys: p is then 1 everywhere and what l and acc gather is wiped
        # by alpha = 0 at its first selected key, which every query has.)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    _tile_of(flags, tile, sel_ref, window)

    @pl.when((flags & _LAST) != 0)
    def _store():
        l = l_sc[...]
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_sc[...] + jnp.log(l)


def _kvb_dkv_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, *rest, sm_scale: float,
                    sel: bool = False,
                    window: int | None = None):
    """dk and dv of one key/value block, summed over the q blocks that see
    it.  Everything is held TRANSPOSED (key/value rows down, queries
    across; ``lse`` and ``delta`` arrive as rows, a selection as the tile
    of its transpose), so that all four products are plain or ``a @ b.T``
    and no score tile is transposed."""
    sel_ref = rest[0] if sel else None
    dk_ref, dv_ref, dk_sc, dv_sc = rest[sel:]
    flags = fl_ref[pl.program_id(1)]
    apart = _tile_apart(qi_ref, ki_ref, q_ref.shape[1], window)

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def tile(cut: bool, low: bool = False):
        q, do = q_ref[0], do_ref[0]
        s = _nt(k_ref[0], q) * sm_scale                    # (bk, bq)
        if cut:
            s = _cut_scores(s, False)
        if low:
            s = _band_scores(s, False, apart, window)
        if sel:
            s = _selected_scores(s, sel_ref)
        p = jnp.exp(s - lse_ref[0])
        dv_sc[...] += jnp.dot(p.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        ds = p * (_nt(v_ref[0], do) - delta_ref[0]) * sm_scale
        dk_sc[...] += jnp.dot(ds.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)

    _tile_of(flags, tile, sel_ref, window)

    @pl.when((flags & _LAST) != 0)
    def _store():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _kvb_dq_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, *rest, sm_scale: float,
                   sel: bool = False,
                   window: int | None = None):
    sel_ref = rest[0] if sel else None
    dq_ref, dq_sc = rest[sel:]
    flags = fl_ref[pl.program_id(1)]
    apart = _tile_apart(qi_ref, ki_ref, q_ref.shape[1], window)

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def tile(cut: bool, low: bool = False):
        k = k_ref[0]
        s = _nt(q_ref[0], k) * sm_scale                    # (bq, bk)
        if cut:
            s = _cut_scores(s, True)
        if low:
            s = _band_scores(s, True, apart, window)
        if sel:
            s = _selected_scores(s, sel_ref)
        p = jnp.exp(s - lse_ref[0])
        ds = p * (_nt(do_ref[0], v_ref[0]) - delta_ref[0]) * sm_scale
        dq_sc[...] += jnp.dot(ds.astype(k.dtype), k,
                              preferred_element_type=jnp.float32)

    _tile_of(flags, tile, sel_ref, window)

    @pl.when((flags & _LAST) != 0)
    def _store():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _kvb_specs(block: int, dh: int, heads: int, sel_heads: int = 0):
    """BlockSpecs over the visit tables: a q-side block, a key/value-side
    block, and the float32 row statistics as a column or as a row.  Grid
    row ``i`` is (batch ``i // heads``, head ``i % heads``).  ``heads ==
    0``: the operands are folded ``(b * h, t, dh)`` and row ``i`` is
    theirs; else they are the layer's ``(b, t, heads * dh)`` and a block
    is head ``i % heads``'s ``dh`` columns of batch ``i // heads``'s rows.
    The statistics are ``(b * h, t, 1)`` or ``(b * h, 1, t)`` either way.
    ``sel_heads`` (the query heads a batch row has; 0: no selection) adds
    the selection's tile ``(b, t, t)`` at (batch ``i // sel_heads``, q
    block, key/value block) as ``sel``, and as ``selT`` the tile at (key/
    value block, q block) of its transpose."""
    vm = pltpu.VMEM
    tile = {} if not sel_heads else {
        "sel": pl.BlockSpec((1, block, block), lambda i, v, qi, ki, fl:
                            (i // sel_heads, qi[v], ki[v]), memory_space=vm),
        "selT": pl.BlockSpec((1, block, block), lambda i, v, qi, ki, fl:
                             (i // sel_heads, ki[v], qi[v]), memory_space=vm)}
    if heads:
        q = lambda i, v, qi, ki, fl: (i // heads, qi[v], i % heads)  # noqa: E731
        kv = lambda i, v, qi, ki, fl: (i // heads, ki[v], i % heads)  # noqa: E731
    else:
        q = lambda i, v, qi, ki, fl: (i, qi[v], 0)          # noqa: E731
        kv = lambda i, v, qi, ki, fl: (i, ki[v], 0)         # noqa: E731
    return {
        "q": pl.BlockSpec((1, block, dh), q, memory_space=vm),
        "kv": pl.BlockSpec((1, block, dh), kv, memory_space=vm),
        "col": pl.BlockSpec((1, block, 1), lambda i, v, qi, ki, fl:
                            (i, qi[v], 0), memory_space=vm),
        "row": pl.BlockSpec((1, 1, block), lambda i, v, qi, ki, fl:
                            (i, 0, qi[v]), memory_space=vm),
        **tile,
    }


def _kvb_dims(q):
    """-> ``(b * h, t, dh, heads)`` of an operand in either layout the
    blocked kernels take: folded ``(b * h, t, dh)`` (``heads`` 0) or the
    layer's ``(b, t, h, dh)``, which they read as ``(b, t, h * dh)``."""
    if q.ndim == 3:
        return (*q.shape, 0)
    b, t, h, dh = q.shape
    return b * h, t, dh, h


def _kvb_delta(o, do, heads: int):
    """``sum(do * o)`` over each head's entries, float32 ``(b * h, t)``
    head-major as the kernels read their rows.  Of the layer's ``(b, t,
    h * dh)`` it is taken head by head, each a sum over that head's own
    ``dh`` columns (the slices start at multiples of 128 lanes): the
    ``(b, h, t)`` result is the one array the direct layout still writes
    head-major, a 128th of an operand.  A folded operand is one head."""
    heads = heads or 1
    dh = o.shape[-1] // heads
    head = lambda x, i: x[..., i * dh:(i + 1) * dh].astype(  # noqa: E731
        jnp.float32)
    return jnp.stack([(head(do, i) * head(o, i)).sum(-1)
                      for i in range(heads)], axis=1)


def _as_rows(x):
    """The layer's ``(b, t, h, dh)`` as the ``(b, t, h * dh)`` the direct
    index maps cut (a view: no element moves); a folded operand as it is."""
    return x.reshape(*x.shape[:2], -1) if x.ndim == 4 else x


def _kvb_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_KVB_VMEM_LIMIT)


# Jitted, so that a program's layers share one trace and one lowering of
# each kernel (as ops/pallas/grouped.py's)
def _kvb_name(pass_: str, masked: bool, window: int | None) -> str:
    """A pass's kernel name: its own, with a selection's tile among its
    operands or under a window (each another program, found by name in a
    device trace)."""
    if masked:
        return KVB_SEL_KERNEL_NAMES[pass_]
    if window is not None:
        return KVB_SWA_KERNEL_NAMES[pass_]
    return {"fwd": KVB_FWD_KERNEL_NAME, "dkv": KVB_DKV_KERNEL_NAME,
            "dq": KVB_DQ_KERNEL_NAME}[pass_]


@partial(jax.jit, static_argnames=("causal", "interpret", "window"))
def _kvb_call_fwd(q, k, v, causal: bool, interpret: bool, sel=None,
                  window: int | None = None):
    """-> ``(o, lse)``: ``o`` in the operands' layout (:func:`_kvb_dims`),
    ``lse`` float32 ``(b * h, t, 1)``.  ``sel``: the selection ``(b, t,
    t)`` int8, or None.  ``window``: query ``i`` sees key ``j`` iff ``0 <=
    i - j < window`` (:func:`_visits`), or None."""
    bh, t, dh, heads = _kvb_dims(q)
    masked = sel is not None
    block = _kvb_block(t, dh, "fwd", masked)
    tables = _visits(t, block, causal, False, window)
    spec = _kvb_specs(block, dh, heads, bh // sel.shape[0] if masked else 0)
    q3 = _as_rows(q)
    o, lse = pl.pallas_call(
        partial(_kvb_fwd_kernel, sm_scale=1.0 / float(np.sqrt(dh)),
                sel=masked, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bh, len(tables[0])),
            in_specs=[spec["q"], spec["kv"], spec["kv"]] +
            ([spec["sel"]] if masked else []),
            out_specs=[spec["q"], spec["col"]],
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, dh), jnp.float32)]),
        out_shape=[_out_struct(q3.shape, q.dtype, q),
                   _out_struct((bh, t, 1), jnp.float32, q)],
        compiler_params=_kvb_params(),
        name=_kvb_name("fwd", masked, window),
        interpret=interpret,
    )(*tables, q3, _as_rows(k), _as_rows(v), *([sel] if masked else []))
    return o.reshape(q.shape), lse


@partial(jax.jit, static_argnames=("causal", "interpret", "window"))
def _kvb_call_bwd(q, k, v, o, lse, do, causal: bool, interpret: bool,
                  sel=None, window: int | None = None):
    bh, t, dh, heads = _kvb_dims(q)
    sm_scale = 1.0 / float(np.sqrt(dh))
    masked = sel is not None
    sel_heads = bh // sel.shape[0] if masked else 0
    q3, k3, v3, do3 = (_as_rows(x) for x in (q, k, v, do))
    delta = _kvb_delta(_as_rows(o), do3, heads)
    # each pass on the tile that is its own (:func:`_kvb_block`)
    block = _kvb_block(t, dh, "dkv", masked)
    spec = _kvb_specs(block, dh, heads, sel_heads)
    by_kv = _visits(t, block, causal, True, window)
    dk, dv = pl.pallas_call(
        partial(_kvb_dkv_kernel, sm_scale=sm_scale, sel=masked,
                window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bh, len(by_kv[0])),
            in_specs=[spec["q"], spec["kv"], spec["kv"], spec["q"],
                      spec["row"], spec["row"]] +
            ([spec["selT"]] if masked else []),
            out_specs=[spec["kv"], spec["kv"]],
            scratch_shapes=[pltpu.VMEM((block, dh), jnp.float32),
                            pltpu.VMEM((block, dh), jnp.float32)]),
        out_shape=[_out_struct(k3.shape, k.dtype, q),
                   _out_struct(v3.shape, v.dtype, q)],
        compiler_params=_kvb_params(),
        name=_kvb_name("dkv", masked, window),
        interpret=interpret,
    )(*by_kv, q3, k3, v3, do3, lse.reshape(bh, 1, t),
      delta.reshape(bh, 1, t),
      # the pass holds its tiles transposed: the one transpose of the
      # selection, int8, made here and dropped after the pass
      *([sel.transpose(0, 2, 1)] if masked else []))
    block = _kvb_block(t, dh, "dq", masked)
    spec = _kvb_specs(block, dh, heads, sel_heads)
    by_q = _visits(t, block, causal, False, window)
    dq = pl.pallas_call(
        partial(_kvb_dq_kernel, sm_scale=sm_scale, sel=masked,
                window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bh, len(by_q[0])),
            in_specs=[spec["q"], spec["kv"], spec["kv"], spec["q"],
                      spec["col"], spec["col"]] +
            ([spec["sel"]] if masked else []),
            out_specs=spec["q"],
            scratch_shapes=[pltpu.VMEM((block, dh), jnp.float32)]),
        out_shape=_out_struct(q3.shape, q.dtype, q),
        compiler_params=_kvb_params(),
        name=_kvb_name("dq", masked, window),
        interpret=interpret,
    )(*by_q, q3, k3, v3, do3, lse, delta.reshape(bh, t, 1),
      *([sel] if masked else []))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_kvb(q, k, v, causal: bool, interpret: bool,
               window: int | None = None):
    """The blocked kernels over operands in either layout
    (:func:`_kvb_dims`); ``o`` and the three gradients come in theirs."""
    return _kvb_call_fwd(q, k, v, causal, interpret, window=window)[0]


def _flash_kvb_fwd(q, k, v, causal, interpret, window):
    o, lse = _kvb_call_fwd(q, k, v, causal, interpret, window=window)
    return o, (q, k, v, o, lse)


def _flash_kvb_bwd(causal, interpret, window, res, do):
    return _kvb_call_bwd(*res, do, causal, interpret, window=window)


_flash_kvb.defvjp(_flash_kvb_fwd, _flash_kvb_bwd)


def _repeat_group(k, v, heads: int):
    """``k`` and ``v`` ``(b, t, kv, dh)`` with each key/value head repeated
    for its group of ``heads / kv`` query heads; folded operands (one row a
    query head already) as they are."""
    group = heads // k.shape[2] if k.ndim == 4 else 1
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash_kvb_sel(q, k, v, sel, interpret: bool):
    """:func:`_flash_kvb` under a selection (causal: the selection's cut
    is the diagonal's or tighter); ``sel`` takes no gradient.  In the
    layer's layout ``k`` and ``v`` come with their own (fewer) heads: the
    group is repeated for the kernels here, in both passes, and what is
    kept for the backward pass is the unrepeated pair."""
    return _kvb_call_fwd(q, *_repeat_group(k, v, q.shape[2]), True,
                         interpret, sel)[0]


def _flash_kvb_sel_fwd(q, k, v, sel, interpret):
    o, lse = _kvb_call_fwd(q, *_repeat_group(k, v, q.shape[2]), True,
                           interpret, sel)
    return o, (q, k, v, o, lse, sel)


def _flash_kvb_sel_bwd(interpret, res, do):
    q, k, v, o, lse, sel = res
    dq, dk, dv = _kvb_call_bwd(q, *_repeat_group(k, v, q.shape[2]), o, lse,
                               do, True, interpret, sel)
    if dk.shape != k.shape:          # a group's heads sum into their own
        b, t, kv, dh = k.shape
        dk, dv = (g.reshape(b, t, kv, -1, dh).sum(3, dtype=jnp.float32)
                  .astype(g.dtype) for g in (dk, dv))
    return dq, dk, dv, None


_flash_kvb_sel.defvjp(_flash_kvb_sel_fwd, _flash_kvb_sel_bwd)


def blocked_unsupported_reason(t: int, dh: int) -> str | None:
    """Why the key/value-blocked form cannot take the shape, or ``None``:
    it needs a time axis its smallest block divides and a head of which
    every pass holds its largest block (:func:`_kvb_vmem`, the chooser's
    formula: VMEM is a function of block, head and pass whatever ``t``;
    dk/dv at head 512 is the limit to the byte, so 512 is the widest);
    key/value heads are as many as query heads (the caller repeats a
    group's)."""
    if t % _KVB_BLOCKS[-1]:
        return (f"t={t} is not a multiple of the {_KVB_BLOCKS[-1]}-row "
                f"key/value block")
    if dh % 64 != 0:
        return f"head_dim={dh} is not a multiple of 64"
    top = _KVB_BLOCKS[0]
    need = max(_kvb_vmem(p, top, dh) for p in _KVB_PASSES)
    if need > _KVB_VMEM_LIMIT:
        return (f"head_dim={dh}: a {top}-row block of it needs "
                f"{need >> 20} MiB of the blocked kernels' "
                f"{_KVB_VMEM_LIMIT >> 20} MiB of VMEM")
    return None


def form_of(t: int, dh: int) -> tuple[str | None, str | None]:
    """THE choice of attention kernel for a shape, by the shape alone:
    ``("rows", None)`` wherever the whole-row form accepts it (every
    shape it ever took keeps its program), ``("blocked", None)`` where
    only the key/value-blocked form does, and ``(None, why)``, both
    refusals in one sentence, where the caller is left with dense
    attention."""
    rows = unsupported_reason(t, dh)
    if rows is None:
        return "rows", None
    blocked = blocked_unsupported_reason(t, dh)
    if blocked is None:
        return "blocked", None
    return None, f"{rows}; key/value-blocked: {blocked}"


def unsupported_reason(t: int, dh: int) -> str | None:
    """Why the whole-row form cannot take the shape (:func:`form_of` then
    asks the key/value-blocked one), or ``None`` when it can:
    q-blockable time axis, lane-sized head dim, and a VMEM budget that
    must cover the BACKWARD kernel (the one actually run under
    value_and_grad): full K/V plus f32 dk/dv accumulator blocks plus the
    three (block_q, t) f32 score temporaries (p, dp, ds)."""
    bq = _pick_block_q(t)
    if bq == 0:
        return f"t={t} is not a multiple of the 128-row q block"
    if dh % 64 != 0:
        return f"head_dim={dh} is not a multiple of 64"
    vmem = 4 * t * dh * 4 + 3 * bq * t * 4
    if vmem > 10 * 1024 * 1024:
        return (f"full K/V at t={t}, head_dim={dh} needs {vmem >> 20} MiB "
                f"of VMEM in the backward kernel (budget 10 MiB); shard "
                f"the seq axis")
    return None


def supported(t: int, dh: int) -> bool:
    """Shapes the whole-row form handles (:func:`unsupported_reason`)."""
    return unsupported_reason(t, dh) is None


def direct_layout(t: int, dh: int, windowed: bool = False) -> bool:
    """Whether the shape's kernels cut their blocks from the layer's own
    ``(b, t, heads * dh)`` and write ``o`` and the gradients there, by the
    shape alone: the blocked form (:func:`form_of`; under a window,
    ``windowed``, wherever the blocked form takes the shape) with a head
    that is a multiple of 128.  A ``(1, block, dh)`` block at index ``(batch, block,
    head)`` is then a legal TPU block (its last axis a multiple of the 128
    lanes); at 64, 192, 320, 448 it is neither that nor the whole axis,
    and the operands are folded head-major as the whole-row form's are."""
    blocked = blocked_unsupported_reason(t, dh) is None if windowed else \
        form_of(t, dh)[0] == "blocked"
    return blocked and dh % 128 == 0


def window_unsupported_reason(t: int, dh: int, window: int) -> str | None:
    """Why no kernel here takes the shape under a window, or ``None``: the
    blocked form alone does (:func:`blocked_unsupported_reason`; its tables
    skip what lies outside the band and its bodies mask the two kinds of cut
    tile, so any window of at least one position will do)."""
    if window < 1:
        return f"window={window}: at least one position (a query sees itself)"
    return blocked_unsupported_reason(t, dh)


def flash_attention(q, k, v, causal: bool = False, *,
                    interpret: bool = False, sel=None,
                    window: int | None = None):
    """Fused attention over per-head tensors ``(b, t, h, dh)`` — same
    contract as ops.attention.attention (``softmax(q·kᵀ/√dh)·v``),
    differentiable via the flash backward kernels, in the form
    :func:`form_of` gives the shape and the layout :func:`direct_layout`
    gives it.  ``k`` and ``v`` may carry fewer heads (``h`` a multiple of
    theirs): grouped-query attention, query head ``j`` reading key/value
    head ``j // group`` (the whole-row form through its index maps, the
    blocked form over repeated heads).  ``sel`` (int8 ``(b, t, t)``,
    nonzero where a query attends to a key, at most the causal triangle,
    every query with a key of its own; no gradient) restricts each
    query's softmax to its selected keys, for all heads alike: always in
    the blocked form (the whole-row kernels take no selection, so
    :func:`blocked_unsupported_reason` alone decides), ``causal``
    required.  ``window``: query ``i`` sees key ``j`` iff ``0 <= i - j <
    window``; always in the blocked form too
    (:func:`window_unsupported_reason`), ``causal`` required, and refused
    beside a selection (which holds whatever cut it likes itself)."""
    b, t, h, dh = q.shape
    if window is not None:
        why = window_unsupported_reason(t, dh, window)
        if not causal:
            why = why or "causal=True is required"
        if sel is not None:
            why = why or "a selection beside it (which holds its own cut)"
        if why:
            raise ValueError(f"flash_attention with a window: {why}")
        form = "blocked"
    elif sel is not None:
        why = blocked_unsupported_reason(t, dh)
        if why or not causal:
            raise ValueError(f"flash_attention with a selection: "
                             f"{why or 'causal=True is required'}")
        form = "blocked"
    else:
        form, why = form_of(t, dh)
    if form is None:
        raise ValueError(
            f"flash_attention cannot take this shape: {why} — gate call "
            f"sites on ops.pallas.attention.form_of() or use the dense "
            f"path")
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        b * x.shape[2], t, -1)
    if form == "rows":
        o = _flash(fold(q), fold(k), fold(v), causal, interpret)
    else:
        direct = dh % 128 == 0     # as direct_layout, the form known
        if sel is not None and direct:
            return _flash_kvb_sel(q, k, v, sel, interpret)
        group = h // k.shape[2]
        if group > 1:
            k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if sel is not None:
            o = _flash_kvb_sel(fold(q), fold(k), fold(v), sel, interpret)
        elif direct:
            return _flash_kvb(q, k, v, causal, interpret, window)
        else:
            o = _flash_kvb(fold(q), fold(k), fold(v), causal, interpret,
                           window)
    return o.reshape(b, h, t, dh).transpose(0, 2, 1, 3)
