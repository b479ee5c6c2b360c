"""How a hidden state becomes a loss: the head pass's forms (dense,
chunked with its gradients made in the pass that makes its logits, chunked
against a vocab-sharded head), the rule that picks among them
(:func:`ce_grad_in_forward`) and the one normalisation they share.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from znicz_tpu.parallel.arch import Arch


def _chunk_token_nll(head, xc, lc):
    """``-log p[label]`` of each token of a chunk, f32, from
    replicated-head logits."""
    logits = (xc @ head).astype(jnp.float32)         # (chunk, vocab)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]


def _ce_chunked(x, labels, w, n_chunks: int):
    """A head pass's operands cut into ``n_chunks`` chunks of tokens; the
    rows that fill the last chunk weigh 0, so they contribute nothing to
    the sum or to a gradient."""
    n_tok, d = x.shape
    chunk = -(-n_tok // n_chunks)
    pad = chunk * n_chunks - n_tok
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        labels, w = jnp.pad(labels, (0, pad)), jnp.pad(w, (0, pad))
    return (x.reshape(n_chunks, chunk, d), labels.reshape(n_chunks, chunk),
            w.reshape(n_chunks, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ce_weighted(x, head, labels, w, n_chunks: int):
    """The chunked cross-entropy against a replicated head: ``x`` ``(n_tok,
    d)``, ``head`` ``(d, vocab)``, ``labels`` ``(n_tok,)``, ``w`` ``(n_tok,)``
    f32 -> ``(sum_i w_i nll_i, nll (n_tok,))``, both f32, ``n_chunks``
    chunks of tokens at a time so that only one chunk's ``(chunk, vocab)``
    logits are ever live (whole they are ~2 GB at the bench shape, and the
    dominant HBM stream of a small-d model).  Per-token numerics are the
    dense path's (row-wise log_softmax); only the cross-token summation
    order differs.  ``n_chunks`` need not divide ``n_tok``: the last chunk
    is filled with rows that weigh 0.

    The sum takes gradients in ``x``, ``head`` AND ``w`` (a looped stack's
    exit distribution).  ``nll`` is a reading for counters and takes NONE:
    the backward rule drops its cotangent, so a caller that differentiates
    through it gets zeros without an error.  Differentiated, the pass
    makes its gradients where it makes its logits
    (:func:`_ce_weighted_fwd`): three products with the vocabulary axis a
    pass, where a checkpointed chunk would run the logits' a second time;
    the transpose only scales them (:func:`_ce_weighted_bwd`)."""
    def chunk(inp):
        xc, lc, wc = inp
        nll = _chunk_token_nll(head, xc, lc)
        return (nll * wc).sum(), nll

    totals, nll = lax.map(chunk, _ce_chunked(x, labels, w, n_chunks))
    return totals.sum(), nll.reshape(-1)[:x.shape[0]]


def _ce_weighted_fwd(x, head, labels, w, n_chunks: int):
    """:func:`_ce_weighted` under differentiation -> its outputs and the
    residuals ``(dx, dhead, nll)``: per chunk the logits (product 1), the
    f32 softmax chain as ``log_softmax`` runs it, ``dlogits = w (softmax -
    onehot)`` cast to the compute dtype (where the transpose of the
    logits' ``astype`` would cast it), ``dx = dlogits head^T`` (product 2)
    and ``dhead += x^T dlogits`` (product 3; the running sum in the head's
    dtype, as a transposed map carries it).  Both gradients are of the
    sum itself: the backward pass scales them by its cotangent."""
    def chunk(dhead, inp):
        xc, lc, wc = inp
        # log_softmax's chain written out for its parts, and the label's
        # logit picked BEFORE log(s) is taken off: picked after, as
        # _chunk_token_nll does (and stays bit for bit the eval pass's old
        # loss), the step compiled for a v5e writes the whole (chunk,
        # vocab) f32 logp for the gather to read (PR 35)
        logits = (xc @ head).astype(jnp.float32)     # (chunk, vocab)
        shifted = logits - logits.max(-1, keepdims=True)
        e = jnp.exp(shifted)
        s = e.sum(-1, keepdims=True)
        picked = jnp.take_along_axis(shifted, lc[:, None], axis=-1)
        nll = (jnp.log(s) - picked)[:, 0]
        hot = lax.broadcasted_iota(jnp.int32, e.shape, 1) == lc[:, None]
        dl = (e * (wc[:, None] / s) - jnp.where(hot, wc[:, None], 0.0)
              ).astype(head.dtype)
        dxc = lax.dot_general(dl, head, (((1,), (1,)), ((), ())))
        dhead = dhead + lax.dot_general(xc, dl, (((0,), (0,)), ((), ())))
        return dhead, ((nll * wc).sum(), nll, dxc)

    # the chunks last to first, the order in which a transposed map sums
    # the head's gradient
    dhead, (totals, nll, dx) = lax.scan(
        chunk, jnp.zeros_like(head), _ce_chunked(x, labels, w, n_chunks),
        reverse=True)
    n_tok, d = x.shape
    nll = nll.reshape(-1)[:n_tok]
    return (totals.sum(), nll), (dx.reshape(-1, d)[:n_tok], dhead, nll)


def _ce_weighted_bwd(n_chunks: int, res, cts):
    """No product and no softmax: the sum is a scalar, so is its cotangent
    (it carries ``1 / n_tokens``, a loss term's weight), and it scales
    the forward rule's gradients in f32, cast once; ``nll`` is the
    gradient with respect to the weights."""
    dx, dhead, nll = res
    ct = cts[0]

    def scaled(g):
        return (ct * g.astype(jnp.float32)).astype(g.dtype)

    return scaled(dx), scaled(dhead), None, ct * nll


_ce_weighted.defvjp(_ce_weighted_fwd, _ce_weighted_bwd)


def _vshard_chunk_nll(head_local, axis_name: str = "model"):
    """-> chunk fn for a VOCAB-SHARDED head (Megatron parallel cross
    entropy, arXiv:1909.08053 §3): each model shard computes its
    ``(chunk, vocab/n)`` logit columns; the stable-softmax max and the
    sum-exp reduce with one pmax + one psum, and the label's logit
    comes from its owning shard via a masked psum — the full-vocab
    logits row never exists on any device."""
    @jax.checkpoint
    def chunk_nll(xc, lc, wc):
        logits = (xc @ head_local).astype(jnp.float32)  # (chunk, v_loc)
        v_loc = logits.shape[-1]
        start = lax.axis_index(axis_name) * v_loc
        # the max shift is gradient-neutral (the lse gradient is the
        # softmax either way).  stop_gradient goes on pmax's INPUT: the
        # zero tangent keeps AD from needing pmax's (missing) JVP rule,
        # and pmax — unlike all_gather — types as model-INVARIANT under
        # the shard_map vma checker, which the P() loss out_spec needs
        m = lax.pmax(lax.stop_gradient(logits.max(-1)), axis_name)
        se = lax.psum(jnp.exp(logits - m[:, None]).sum(-1), axis_name)
        lse = m + jnp.log(se)
        lc_loc = jnp.clip(lc - start, 0, v_loc - 1)
        mine = (lc >= start) & (lc < start + v_loc)
        picked_loc = jnp.take_along_axis(logits, lc_loc[:, None],
                                         axis=-1)[:, 0]
        picked = lax.psum(jnp.where(mine, picked_loc, 0.0), axis_name)
        return (-(picked - lse) * wc).sum()
    return chunk_nll


def _n_chunks(loss_chunks: int | None) -> int:
    """``loss_chunks`` as a count of chunks: 1 when unset."""
    return loss_chunks if loss_chunks and loss_chunks > 1 else 1


def ce_grad_in_forward(loss_chunks: int | None, head_sharded: bool,
                       looped: bool = False) -> bool:
    """Whether a head pass makes its gradients where it makes its logits
    (:func:`_ce_weighted`): a looped stack's always, another's when chunked
    against a replicated head (else AD makes them).  :func:`_ce_from_hidden`
    acts on it, ``transformer.step_choices`` reports it."""
    return looped or (not head_sharded and _n_chunks(loss_chunks) > 1)


def _token_weights(weights, b: int, t: int):
    """``weights`` (anything that broadcasts to ``(b, t)``, or None for
    ones) as ``(b * t,)`` f32."""
    if weights is None:
        return jnp.ones((b * t,), jnp.float32)
    return jnp.broadcast_to(weights, (b, t)).reshape(b * t)


def _ce_token_nll_sum(x, labels, chunk_nll, n_chunks, weights):
    """Σ weights·(-log p[label]) over the local tokens against a
    VOCAB-SHARDED head (:func:`_vshard_chunk_nll`; a replicated head takes
    :func:`_ce_weighted`), ``n_chunks`` tokens-chunks at a time with the
    chunk rematerialized: only one chunk of logits is live (forward AND
    backward, ``jax.checkpoint`` recomputes it in the transpose)."""
    b, t, d = x.shape
    totals = lax.map(
        lambda inp: chunk_nll(*inp),
        _ce_chunked(x.reshape(b * t, d), labels.reshape(b * t),
                    _token_weights(weights, b, t), n_chunks))
    return totals.sum()


def _head_of(ps, arch: Arch):
    """The ``(d, vocab)`` matrix the logits are read against."""
    return ps["emb"].T if arch.tied else ps["head"]


def _ce_from_hidden(x, head, labels, mask, aux_term, loss_chunks,
                    head_sharded, reduce, skip_last: bool = False):
    """Head matmul + masked CE over the hidden states, normalised and
    (``reduce``) summed over the data x seq shards: the tail of
    ``transformer._forward_ce``.  ``skip_last`` leaves each row's last position
    out of the sum and of the count (the seq axis unsharded)."""
    b_l, t_l = labels.shape
    mvec = mask[:, None].astype(jnp.float32) if mask is not None else None
    if skip_last:
        counted = (jnp.arange(t_l) < t_l - 1).astype(jnp.float32)[None, :]
        mvec = counted if mvec is None else mvec * counted
        t_l -= 1                      # positions a row counts from here on
    # every path yields the LOCAL weighted nll sum; normalization below
    # is shared so dense and chunked conventions can never drift.  A
    # vocab-sharded head always routes through its chunk helper (its CE
    # needs the collective-reduced softmax; n_chunks=1 when unchunked).
    n_chunks = _n_chunks(loss_chunks)
    if ce_grad_in_forward(loss_chunks, head_sharded):
        nll, _ = _ce_weighted(
            x.reshape(-1, x.shape[-1]), head, labels.reshape(-1),
            _token_weights(mvec, *labels.shape), n_chunks)
    elif head_sharded:
        nll = _ce_token_nll_sum(x, labels, _vshard_chunk_nll(head),
                                n_chunks, mvec)
    else:
        logits = (x @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None],
                                     axis=-1)[..., 0]
        nll = -picked.sum() if mvec is None else \
            -(picked * jnp.broadcast_to(mvec, picked.shape)).sum()
    return _normalised(nll, mask, b_l, t_l, aux_term, reduce)


def _normalised(nll, mask, b_l: int, t_l: int, aux_term, reduce: bool):
    """The LOCAL sum ``nll`` over this shard's counted tokens (``t_l`` a
    row) -> the mean over all shards' counted tokens in the loss's
    convention (scaled by the shard count; ``reduce``: summed over the
    data x seq shards), plus ``aux_term``."""
    if mask is None:
        local = nll / (b_l * t_l) + aux_term
        if not reduce:
            return local
        # psum-of-local-means; it makes AD emit globally-reduced grads
        # for replicated params; model-sharded params get their local
        # shard's grad
        return lax.psum(local, ("data", "seq"))
    # masked variant, SAME n_shards-scaled convention as the unmasked
    # psum-of-local-means (the caller divides loss and grads by n_shards)
    n_seq = lax.psum(1, "seq")
    n_shards = lax.psum(1, "data") * n_seq
    # the mask is seq-INVARIANT (each seq shard sees the same rows), so
    # its token count reduces over "data" and multiplies by n_seq — a
    # joint psum would mix varying and invarying axis states
    total = lax.psum(mask.astype(jnp.float32).sum() * t_l, "data") * n_seq
    if not reduce:
        # n_shards/total are replicated, so the psum of this local term
        # distributes back to exactly the reduce=True expression
        return n_shards * nll / jnp.maximum(total, 1.0) + aux_term
    return n_shards * lax.psum(nll, ("data", "seq")) / \
        jnp.maximum(total, 1.0) + lax.psum(aux_term, ("data", "seq"))
