"""Learned sparse attention's indexer (DeepSeek-V3.2-Exp's sparse
attention, DSA): which keys a query attends to, and the loss that teaches
the indexer to pick them.

For a query ``t`` and a key ``s <= t`` the index score is ``I[t, s] = sum_j
w[t, j] * relu(qI[t, j] . kI[s])`` over ``hi`` index heads of width ``di``
on ONE index key head; the selection ``S_t`` is the ``top_k`` largest
``I[t, s]`` over ``s <= t`` (every ``s <= t`` while ``t < top_k``), one set
a token for all attention heads; the alignment term is ``L_I = mean over
tokens of KL(p[t, .] || softmax over S_t of I[t, .])`` with ``p`` the mean
over the attention heads of ``softmax over S_t of (q_t . k_s / sqrt(dh))``,
detached.  ``L_I`` is the only thing that trains the indexer, and it trains
nothing else: the caller hands the indexer a detached input, and the
selection carries no gradient.

:func:`index_select_align` computes all three a block of queries at a time
(``lax.scan`` over blocks of :data:`Q_BLOCK` rows), so that no temporary
is a square of ``t``.  Where the kernels below run, nothing a block makes
in HBM is wider than its ``(Q_BLOCK, t)`` float32 rows (the index scores
summed over their heads, the ordered keys, the target, ``dL/dI``: 8 MiB
each at 16,384 positions): no ``(heads, Q_BLOCK, t)`` temporary is left,
of the index heads or of the attention's.  In the ``jax.numpy`` forms the
largest is a block's scores over the keys, ``(heads, Q_BLOCK, t)`` float32:
256 MiB at 32 heads and 16,384 positions.  What leaves is the selection
itself (int8 ``(b, t, t)``, the operand
the flash kernels take: ``ops/pallas/attention.py``), the loss and, under
differentiation, the loss's gradients to ``qI``, ``kI`` and ``w``, made
in the block that made the scores (a ``custom_vjp`` as the chunked
cross-entropy is: the backward pass only scales them).  Causality is used
coarsely: the rows are cut into :data:`GROUPS` groups and a group's blocks
read the keys up to the group's end only (5/8 of the square at four
groups).

The threshold is found by COUNTING, not by sorting: the float32 scores are
mapped to unsigned keys in the same order and the ``top_k``-th largest key
of a row is built two bits a pass, sixteen passes of three compares and
sums over the block (``lax.top_k`` of 2,048 from 16,384 is a sort of the
row on a TPU).  A row's selection is every causal key at or above its
threshold, so keys that tie with the ``top_k``-th, bit for bit in float32,
are all kept (a row then holds a key more than ``top_k``).

Precision: the index products take the operands in the compute dtype
(``qI``, ``kI`` as the caller hands them) and accumulate in float32; ``relu``,
the weighting, the sum over index heads, the keys' order, both softmaxes
and the KL are float32.  The selection is a discrete choice, so a pair
within rounding of a row's threshold may fall on either side of it.  The
alignment pass's own product of ``q`` and ``k`` (the target's scores, a
second time: the flash kernels keep none) leaves the MXU in the compute
dtype, as the head pass's logits do, which halves what a block writes and
reads back.

The target and the index scores each have two implementations of one
algorithm.  Where the step's kernels run (a TPU, or interpreted) and the
shape fits (:func:`align_kernel_refusal`, :func:`index_kernel_refusal`:
one question, :func:`_kernel_refusal`, with each kernel's own reasons), a
block's work is Pallas kernels (``ops/pallas/dsa.py``) whose scores stay
float32 in VMEM from the MXU on, none in HBM, and that visit no key tile
above the block's last query:

- the target, :data:`~znicz_tpu.ops.pallas.dsa.ALIGN_KERNEL_NAME`: two
  sweeps over key tiles, the attention heads' scores from the MXU to the
  ``exp``;
- the index scores, :data:`~znicz_tpu.ops.pallas.dsa.
  INDEX_SCORES_KERNEL_NAME`: ``qI kI^T`` a few index heads at a time,
  ``relu``, the weighting and the sum over the heads into the block's
  ``(Q_BLOCK, tile)`` rows; and their gradients, :data:`~znicz_tpu.ops.
  pallas.dsa.INDEX_GRADS_KERNEL_NAME`: the same product again in the tile
  (the forward pass keeps neither ``s`` nor ``relu(s)``), ``g = dL/dI * w *
  (s > 0)`` rounded to the operands' dtype as the einsums round it, ``g
  kI`` summed over the tiles, ``qI^T g`` a tile at a time into the scan's
  carry (keys-minor, as XLA lays the carry out), and ``dL/dw``.  They read
  a block's ``qI`` as the layer has it, ``(Q_BLOCK, hi x di)``, a head cut
  from the lanes: no copy of ``qI`` is prepared.

Everywhere else the blocked ``jax.numpy`` forms above.  Both give the same
``p`` to rounding (the kernel's scores are not rounded to the compute
dtype) and the same index scores and gradients to the order of their
float32 sums; the threshold, the selection, the KL and ``dL/dI`` are the
same code under either.

Scopes ``<scope>.index`` (the scores, and their gradients'
``transpose(jvp(...))``), ``<scope>.select`` (keys, threshold, the
selection) and ``<scope>.align`` (the attention heads' probabilities, the
KL, its gradient to the scores): siblings of ``<scope>`` by name.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from znicz_tpu.observe import probe as _probe
from znicz_tpu.ops.pallas import dsa as _pdsa

#: query rows a block of the scan holds
Q_BLOCK = 128
#: groups the rows are cut into where the length allows; a group's blocks
#: read the keys up to the group's end
GROUPS = 4


def sortable_keys(x):
    """float32 -> uint32 in the same order (``a < b`` iff ``key(a) <
    key(b)``; every real score's key is above 0)."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest_key(keys, k: int):
    """The ``k``-th largest of each row of ``keys`` ``(rows, n)`` uint32,
    or 0 for a row in which fewer than ``k`` keys are above 0: the largest
    ``T`` with ``count(keys >= T) >= k``, built from the top two bits a
    pass (sixteen passes, each three compares and sums over the row)."""
    steps = jnp.arange(1, 4, dtype=jnp.uint32)

    def two_bits(i, thr):
        shift = (30 - 2 * i).astype(jnp.uint32)
        cands = thr[:, None] | (steps << shift)[None, :]        # (rows, 3)
        counts = (keys[:, None, :] >= cands[:, :, None]).sum(-1)
        # counts fall with the candidate: those that hold are a prefix
        best = (counts >= k).sum(-1).astype(jnp.uint32)
        return thr | (best << shift)

    return lax.fori_loop(0, 16, two_bits,
                         jnp.zeros(keys.shape[0], jnp.uint32))


def _blocks_of(t: int) -> tuple[int, int]:
    """-> ``(rows a block, groups)`` for ``t`` positions."""
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    groups = GROUPS if t % (GROUPS * block) == 0 else 1
    return block, groups


def _kernel_refusal(t: int, interpret: bool, of_shape) -> str | None:
    """Why a pass over ``t`` positions is left to its ``jax.numpy`` form, or
    ``None`` where its kernel runs (``ops/pallas/dsa.py``): where the step's
    kernels run at all (a TPU, or ``interpret``: interpreted), whole blocks
    of :data:`Q_BLOCK` queries, and a shape the kernel's tiles take at every
    group's key extent (``of_shape(block, keys)``, the kernel's own reason,
    for extents that are multiples of ``keys = t / groups``)."""
    if not interpret and jax.default_backend() != "tpu":
        return (f"the backend is {jax.default_backend()} and the step's "
                f"kernels are not interpreted")
    if t % Q_BLOCK:
        return f"t={t} is not a multiple of the {Q_BLOCK}-row block of queries"
    block, groups = _blocks_of(t)
    return of_shape(block, t // groups)


def align_kernel_refusal(t: int, heads: int, kv: int, dh: int,
                         interpret: bool) -> str | None:
    """Why the alignment target of ``t`` positions of ``heads`` heads ``dh``
    wide on ``kv`` key/value heads is left to the ``jax.numpy`` form, or
    ``None`` where the kernel makes it (:func:`_kernel_refusal`)."""
    return _kernel_refusal(t, interpret, lambda block, keys:
                           _pdsa.unsupported_reason(block, keys, heads, kv,
                                                    dh))


def index_kernel_refusal(t: int, hi: int, di: int,
                         interpret: bool) -> str | None:
    """Why the index scores of ``t`` positions of ``hi`` index heads ``di``
    wide and their three gradients are left to the ``jax.numpy`` einsums,
    or ``None`` where the two kernels make them (:func:`_kernel_refusal`)."""
    return _kernel_refusal(t, interpret, lambda block, keys:
                           _pdsa.index_unsupported_reason(block, keys, hi,
                                                          di))


def _one_block(ki, k, top_k: int, scale, weight, grads: bool, scope: str,
               kernel: bool, index_kernel: bool, interpret: bool):
    """The scan body over blocks of queries against the keys ``ki`` ``(tk,
    di)`` and ``k`` ``(tk, kv, dh)``; the carry is ``dL/dkI``, ``(tk, di)``
    or, with ``index_kernel``, keys-minor ``(di, tk)``.  ``kernel``:
    the target by the Pallas kernel, the block's ``q`` then head-major
    ``(kv, grp * bq, dh)``; ``index_kernel``: the index scores and their
    gradients by theirs, the block's ``qi`` (and ``dqi``) then ``(bq, hi *
    di)``, the heads side by side."""
    tk = ki.shape[0]
    f32 = jnp.float32

    def target_numpy(q, sel):
        # a key/value head's group of query heads at a time, as one plain
        # product with the keys along the minor axis, the layout every
        # softmax over keys has: the one product over all heads
        # (``qgjd,kgd->gjqk``) gets, at some widths, a layout of XLA's
        # choosing in which the row statistics cost 27 times the product
        # (11.7 ms a block of 128 at 8,192 keys against 0.2 at 16,384; my
        # chip run, PR 39).  The scores leave the product in the compute
        # dtype (as the head pass's logits do) and are float32 from there on
        bq, kv, grp, _ = q.shape
        rows = jnp.repeat(sel, grp, axis=0)                 # (bq*grp, tk)
        p = jnp.zeros(sel.shape, f32)
        for g in range(kv):
            a = jnp.dot(q[:, g].reshape(bq * grp, -1),
                        k[:, g].T).astype(f32) * scale
            a = jnp.where(rows, a, -jnp.inf)
            e = jnp.exp(a - a.max(-1, keepdims=True))
            p = p + (e / e.sum(-1, keepdims=True)).reshape(
                bq, grp, -1).sum(1)
        return p / (kv * grp)                                # (bq, tk)

    def body(dki, xs):
        # (bq, hi, di) or (bq, hi * di), (bq, hi), (bq, kv, grp, dh) or
        # head-major, (bq,)
        qi, w, q, pos = xs
        causal = jnp.arange(tk)[None, :] <= pos[:, None]
        with _probe.scope(f"{scope}.index"):
            if index_kernel:
                idx = _pdsa.index_scores(qi, ki, w, pos[-1],
                                         interpret=interpret)
            else:
                s = jnp.einsum("qjd,kd->qjk", qi, ki,
                               preferred_element_type=f32)
                r = jnp.maximum(s, 0.0)
                idx = (r * w[:, :, None]).sum(1)                 # (bq, tk)
        with _probe.scope(f"{scope}.select"):
            keys = jnp.where(causal, sortable_keys(idx), jnp.uint32(0))
            sel = (keys >= kth_largest_key(keys, top_k)[:, None]) & causal
        sel8 = sel.astype(jnp.int8)
        with _probe.scope(f"{scope}.align"):
            if kernel:
                p = _pdsa.align_target(q, k, sel8, pos[-1],
                                       sm_scale=float(scale),
                                       interpret=interpret)
            else:
                p = target_numpy(q, sel)
            li = jnp.where(sel, idx, -jnp.inf)
            logq = li - jax.nn.logsumexp(li, axis=-1, keepdims=True)
            kl = jnp.where(p > 0, p * (jnp.log(jnp.maximum(p, 1e-37)) -
                                       logq), 0.0).sum()
            # dL/dI: zero outside the selection (exp(-inf), and p is 0)
            d_idx = (jnp.exp(logq) - p) * weight
        out = (sel8, kl)
        if not grads:
            return dki, out
        with _probe.scope_bwd(f"{scope}.index"):
            if index_kernel:
                dqi, dk_i, dw = _pdsa.index_grads(qi, ki, w, d_idx, pos[-1],
                                                  interpret=interpret)
            else:
                g = (d_idx[:, None, :] * w[:, :, None] *
                     (s > 0)).astype(qi.dtype)
                dqi = jnp.einsum("qjk,kd->qjd", g, ki,
                                 preferred_element_type=f32)
                dk_i = jnp.einsum("qjk,qjd->kd", g, qi,
                                  preferred_element_type=f32)
                dw = (d_idx[:, None, :] * r).sum(-1)
            dki = dki + dk_i
        return dki, out + (dqi, dw)

    return body


def _row(qi, ki, w, q, k, top_k: int, weight, grads: bool, scope: str,
         interpret: bool):
    """One sequence: ``qi (t, hi, di)``, ``ki (t, di)``, ``w (t, hi)``, ``q
    (t, h, dh)``, ``k (t, kv, dh)`` -> ``(sel (t, t) int8, sum of the rows'
    KL, (dqi, dki, dw) or None)``."""
    t, h, dh = q.shape
    kv = k.shape[1]
    block, groups = _blocks_of(t)
    rows = t // groups
    iheads, di = qi.shape[1:]
    kernel = align_kernel_refusal(t, h, kv, dh, interpret) is None
    index_kernel = index_kernel_refusal(t, iheads, di, interpret) is None
    q = q.reshape(t // block, block, kv, h // kv, dh)
    if kernel:
        # the kernel's rows, head-major a block: one pass over q a layer
        with _probe.scope(f"{scope}.align"):
            q = q.transpose(0, 2, 3, 1, 4).reshape(t // block, kv, -1, dh)
    # the index kernels cut a head from the lanes of a block's (bq, hi x di)
    qi_blocks = qi.reshape(t // block, block, iheads * di) if index_kernel \
        else qi.reshape(t // block, block, iheads, di)
    scale = np.float32(1.0 / np.sqrt(dh))
    sels, kl, dqis, dws = [], 0.0, [], []
    dki = jnp.zeros(ki.shape, jnp.float32)
    for g in range(groups):
        lo, hi = g * rows, (g + 1) * rows
        cut = lambda a: a[lo:hi].reshape(rows // block, block,  # noqa: E731
                                         *a.shape[1:])
        body = _one_block(ki[:hi], k[:hi], top_k, scale, weight, grads,
                          scope, kernel, index_kernel, interpret)
        dki_g, out = lax.scan(
            body, jnp.zeros((di, hi) if index_kernel else (hi, di),
                            jnp.float32),
            (qi_blocks[lo // block:hi // block], cut(w),
             q[lo // block:hi // block],
             cut(jnp.arange(t, dtype=jnp.int32))))
        with _probe.scope(f"{scope}.select"):
            sels.append(jnp.pad(out[0].reshape(rows, hi),
                                ((0, 0), (0, t - hi))))
        kl = kl + out[1].sum()
        if grads:
            dki = dki.at[:hi].add(dki_g.T if index_kernel else dki_g)
            dqis.append(out[2])
            dws.append(out[3].reshape(rows, w.shape[1]))
    with _probe.scope(f"{scope}.select"):
        sel = jnp.concatenate(sels) if groups > 1 else sels[0]
    if not grads:
        return sel, kl, None
    return sel, kl, (jnp.concatenate(dqis).reshape(qi.shape).astype(qi.dtype),
                     dki.astype(ki.dtype), jnp.concatenate(dws))


def _forward(qi, ki, w, q, k, top_k: int, scope: str, interpret: bool,
             grads: bool):
    b, t = q.shape[:2]
    weight = np.float32(1.0 / (b * t))
    rows = [_row(qi[i], ki[i], w[i], q[i], k[i], top_k, weight, grads,
                 scope, interpret) for i in range(b)]
    sel = jnp.stack([r[0] for r in rows])
    loss = sum(r[1] for r in rows) * weight
    if not grads:
        return sel, loss, None
    return sel, loss, tuple(jnp.stack([r[2][i] for r in rows])
                            for i in range(3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def index_select_align(qi, ki, w, q, k, top_k: int, scope: str = "dsa",
                       interpret: bool = False):
    """The selection and the alignment loss of one attention layer.

    ``qi`` ``(b, t, hi, di)`` and ``ki`` ``(b, t, di)``: the indexer's
    queries and its one key head, normed and rotated, in the compute
    dtype; ``w`` ``(b, t, hi)`` float32: the index heads' weights, scaled;
    ``q`` ``(b, t, h, dh)``, ``k`` ``(b, t, kv, dh)``: the attention's own
    operands (``kv`` divides ``h``), read for the target and given no
    gradient.  -> ``(sel int8 (b, t, t), L_I float32)``: ``sel[b, t, s]``
    is 1 where query ``t`` attends to key ``s``; ``L_I`` is the mean over
    the ``b * t`` tokens of the KL (module docstring), with gradients to
    ``qi``, ``ki`` and ``w`` only.  ``interpret``: the step runs its Pallas
    kernels interpreted, so the target's kernel too
    (:func:`align_kernel_refusal`)."""
    sel, loss, _ = _forward(qi, ki, w, q, k, top_k, scope, interpret, False)
    return sel, loss


def _isa_fwd(qi, ki, w, q, k, top_k, scope, interpret):
    sel, loss, grads = _forward(qi, ki, w, q, k, top_k, scope, interpret,
                                True)
    return (sel, loss), grads


def _isa_bwd(top_k, scope, interpret, grads, cts):
    ct = cts[1]
    with _probe.scope_bwd(f"{scope}.index"):
        return (*((ct * g.astype(jnp.float32)).astype(g.dtype)
                  for g in grads), None, None)


index_select_align.defvjp(_isa_fwd, _isa_bwd)


def live_tiles(sel, block: int):
    """-> ``(live, visited)`` float32: of the causal ``(block, block)``
    tiles of ``sel`` ``(b, t, t)`` (those a blocked kernel's visit table
    lists), how many hold at least one selected pair, and how many there
    are."""
    b, t, _ = sel.shape
    n = t // block
    # rows of a block first (contiguous), then its columns: no copy of the
    # square in another layout
    any_ = sel.reshape(b, n, block, t).max(2).reshape(b, n, n, block).max(3) \
        != 0
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    return ((any_ & causal).sum().astype(jnp.float32),
            jnp.float32(b * n * (n + 1) // 2))
