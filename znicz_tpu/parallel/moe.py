"""Expert-parallel mixture-of-experts FFN over the ``expert`` mesh axis
(TPU-native extension; the reference has no MoE — SURVEY.md §3.4 EP row).

Three regimes (docs/TUNING.md "MoE"):

- :func:`moe_ffn` — tokens REPLICATED over the expert axis: dense
  masked compute (each device runs its local experts over all tokens,
  one psum combines), exact for top-1 switch routing and GShard
  renormalized top-k, at E_local× arithmetic per token.
- :func:`moe_ffn_dispatch` — tokens SHARDED over the expert axis: the
  all_to_all token-dispatch path (each token computes once, on its
  expert's device; capacity overflow drops, switch semantics).
- :func:`moe_routed_ffn` — this chip's SHARE of an expert-parallel
  layer: told which experts it holds, it routes every token over all
  experts, sorts the (token, choice) pairs by expert, runs grouped
  products over the held experts' groups and scatters back.  No
  capacity and no drop; what absent experts would add is left out (their
  chips add it).  The score function, the selection bias and the
  expert's form are arguments.  Its all-to-all form over an expert axis
  is what would retire the two above (ROADMAP.md).

:func:`load_balance_aux` is the shared switch load-balance regularizer.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from znicz_tpu.observe import probe as _probe


def moe_ffn(x, gate_w, w1_local, b1_local, w2_local, b2_local,
            act, axis_name: str = "expert", top_k: int = 1):
    """x ``(tokens, d)`` replicated over the expert axis; ``gate_w``
    ``(d, n_experts_total)`` replicated; ``w1_local`` ``(e_local, d, ff)``,
    ``w2_local`` ``(e_local, ff, d)`` expert-sharded.  Returns replicated
    ``(tokens, d)`` plus the (replicated) gate distribution for load-
    balancing diagnostics.

    ``top_k=1`` is switch routing (winner scaled by its raw softmax
    prob); ``top_k≥2`` is GShard-style: the k winners' probs are
    RENORMALIZED to sum to 1 and their expert outputs combine
    weighted."""
    my_idx = lax.axis_index(axis_name)
    e_local = w1_local.shape[0]
    scores = x @ gate_w                          # (tokens, E)
    gate_probs = jax.nn.softmax(scores, axis=-1)
    _, choice_k = lax.top_k(scores, top_k)       # (tokens, k)
    gate_k = jnp.take_along_axis(gate_probs, choice_k, axis=1)  # (t, k)
    if top_k > 1:
        gate_k = gate_k / gate_k.sum(axis=-1, keepdims=True)
    # local expert ids: my_idx*e_local .. +e_local
    local_ids = my_idx * e_local + jnp.arange(e_local)
    # (e_local, tokens): this local expert's combined gate weight per
    # token (0 when the token routed elsewhere)
    sel = (choice_k[None, :, :] ==
           local_ids[:, None, None])             # (e_local, t, k)
    wgt = (sel.astype(x.dtype) * gate_k[None, :, :]).sum(-1)
    h = act(jnp.einsum("td,edf->etf", x, w1_local) + b1_local[:, None, :])
    y_e = jnp.einsum("etf,efd->etd", h, w2_local) + b2_local[:, None, :]
    y_local = (y_e * wgt[:, :, None]).sum(axis=0)
    return lax.psum(y_local, axis_name), gate_probs


def router_z_loss(scores):
    """ST-MoE router z-loss (arXiv:2202.08906 eq. 5) over the LOCAL
    tokens: mean of ``logsumexp(scores)²`` — penalizes large router
    logits, whose drift destabilizes bf16 MoE training long before the
    balance aux notices.  f32 regardless of compute dtype."""
    z = jax.nn.logsumexp(scores.astype(jnp.float32), axis=-1)
    return (z * z).mean()


def load_balance_aux(gate_probs):
    """Switch-transformer load-balance auxiliary (arXiv:2101.03961
    eq. 4) over the LOCAL tokens: ``E · Σ_e f_e·P_e`` with ``f`` the
    top-1 routed fraction (argmax-derived — gradients flow through the
    mean gate prob ``P`` only) — minimized (=1) at uniform routing.
    f32 regardless of the compute dtype."""
    n_exp = gate_probs.shape[-1]
    pf = gate_probs.astype(jnp.float32)
    f = jnp.mean(jax.nn.one_hot(pf.argmax(-1), n_exp,
                                dtype=jnp.float32), axis=0)
    return n_exp * (f * pf.mean(axis=0)).sum()


def moe_ffn_dispatch(x, gate_w, w1_local, b1_local, w2_local, b2_local,
                     act, axis_name: str = "expert",
                     capacity_factor: float = 2.0, top_k: int = 1):
    """Token-dispatch MoE FFN for the TOKEN-SHARDED regime (the
    all_to_all optimization :func:`moe_ffn`'s docstring plans): ``x``
    ``(tokens_local, d)`` is sharded over ``axis_name`` (each device
    holds its own tokens AND ``e_local`` experts).  Routed tokens
    travel to their expert's device and back with two ``lax.all_to_all``
    exchanges — each token is computed ONCE, by one expert, instead of
    the dense-masked path's E_local× arithmetic.

    Mesh-TensorFlow dispatch formulation (einsum with a
    ``(tokens, E, capacity)`` one-hot — MXU-friendly, no scatters):
    per-expert buckets have ``capacity = ceil(capacity_factor ·
    tokens_local · top_k / E)`` slots per SOURCE device; a
    (token, choice) pair past its expert's capacity is DROPPED
    (contributes zero output — the standard switch-transformer overflow
    semantics; size ``capacity_factor`` for the expected imbalance, or
    set it ≥ E/top_k for provably lossless routing).  Gradients flow
    through both all_to_alls back to x, the gate, and the owning
    expert's weights.

    ``top_k≥2`` routes each token to its k best experts with
    GShard-renormalized combine weights (same semantics as
    :func:`moe_ffn`); the token then occupies up to k bucket slots and
    ``capacity`` scales by k.  Returns ``(y_local (tokens_local, d),
    gate_probs)`` — both sharded like ``x``."""
    n_dev = lax.psum(1, axis_name)
    tokens, d = x.shape
    e_local = w1_local.shape[0]
    n_experts = n_dev * e_local
    scores = x @ gate_w                          # (t, E)
    gate_probs = jax.nn.softmax(scores, axis=-1)
    _, choice_k = lax.top_k(scores, top_k)       # (t, k)
    gate_k = jnp.take_along_axis(gate_probs, choice_k, axis=1)  # (t, k)
    if top_k > 1:
        gate_k = gate_k / gate_k.sum(axis=-1, keepdims=True)
    capacity = int(np.ceil(capacity_factor * tokens * top_k /
                           n_experts))
    # bucket positions over ALL (token, choice) pairs, token-major with
    # the k choices inner — each pair claims its own slot
    cf = choice_k.reshape(-1)                    # (t·k,)
    onehot = jax.nn.one_hot(cf, n_experts, dtype=jnp.int32)  # (t·k, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                              cf[:, None], axis=1)[:, 0]   # (t·k,) int
    keep = (pos < capacity).astype(x.dtype)
    # (t·k, E, C) slot one-hots -> (t, k, E, C)
    mask_k = (onehot.astype(x.dtype)[:, :, None] *
              jax.nn.one_hot(pos, capacity, dtype=x.dtype)[:, None, :] *
              keep[:, None, None]).reshape(tokens, top_k, n_experts,
                                           capacity)
    # slots are distinct across k, so the binary send mask is the sum
    mask = mask_k.sum(axis=1)                    # (t, E, C) dispatch
    comb = (mask_k * gate_k[:, :, None, None]).sum(axis=1)  # combine
    disp = jnp.einsum("tec,td->ecd", mask, x)    # (E, C, d)
    # -> (n_dev, e_local, C, d); all_to_all swaps the leading device dim
    # so each device receives its OWN experts' buckets from every source
    disp = disp.reshape(n_dev, e_local, capacity, d)
    recv = lax.all_to_all(disp, axis_name, split_axis=0, concat_axis=0)
    # expert compute over (n_src * C) tokens per local expert
    xin = recv.transpose(1, 0, 2, 3).reshape(e_local,
                                             n_dev * capacity, d)
    h = act(jnp.einsum("etd,edf->etf", xin, w1_local) +
            b1_local[:, None, :])
    y = jnp.einsum("etf,efd->etd", h, w2_local) + b2_local[:, None, :]
    y = y.reshape(e_local, n_dev, capacity, d).transpose(1, 0, 2, 3)
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0)
    res = back.reshape(n_experts, capacity, d)   # MY tokens' results
    out = jnp.einsum("tec,ecd->td", comb, res)
    return out, gate_probs


def route_top_k(scores_in, bias, top_k: int, score: str = "sigmoid",
                norm_topk: bool = True, scale: float = 1.0):
    """Router arithmetic of :func:`moe_routed_ffn`, float32: ``scores_in``
    ``(tokens, E)`` logits -> ``(choice (tokens, k) int32, weight
    (tokens, k))``.  ``score`` is ``"sigmoid"`` or ``"softmax"`` of the
    logits; selection is the top k of score plus ``bias`` (``(E,)`` or
    None; it steers selection only and takes no gradient); the weights
    are the selected scores themselves, with ``norm_topk`` divided by
    their sum over ALL k selected (+1e-6), times ``scale``."""
    logits = scores_in.astype(jnp.float32)
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"router score {score!r}: sigmoid or softmax")
    sel = s if bias is None else \
        s + lax.stop_gradient(bias.astype(jnp.float32))
    _, choice = lax.top_k(lax.stop_gradient(sel), top_k)
    w = jnp.take_along_axis(s, choice, axis=1)
    if norm_topk:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return choice, w * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_pairs(x, token_of, slot_of, top_k: int):
    """``x[token_of]``: each token's row once for each of its ``top_k``
    pairs, in the pairs' sorted order.  ``slot_of`` is the inverse
    permutation (pair ``t * top_k + j`` lies at ``slot_of[t * top_k +
    j]``), so the gradient is a gather too: a token's row sums the
    ``top_k`` rows at its pairs' slots, where AD's own transpose would
    scatter-add 4 x ``tokens`` rows (2.8 ms against 0.3 at 32,768 x
    2,048 on a v5e, my chip run, PR 28)."""
    return x[token_of]


def _rows_of_pairs_fwd(x, token_of, slot_of, top_k):
    return x[token_of], slot_of


def _rows_of_pairs_bwd(top_k, slot_of, g):
    return _sum_of_pairs(g, None, slot_of, top_k), None, None


_rows_of_pairs.defvjp(_rows_of_pairs_fwd, _rows_of_pairs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sum_of_pairs(ys, token_of, slot_of, top_k: int):
    """Each token's sum over its ``top_k`` pairs' rows of ``ys`` (sorted
    pair order), accumulated in f32: the inverse of
    :func:`_rows_of_pairs`, a gather both ways."""
    picked = ys[slot_of].reshape(-1, top_k, ys.shape[-1])
    return picked.sum(axis=1, dtype=jnp.float32).astype(ys.dtype)


def _sum_of_pairs_fwd(ys, token_of, slot_of, top_k):
    return _sum_of_pairs(ys, token_of, slot_of, top_k), token_of


def _sum_of_pairs_bwd(top_k, token_of, g):
    return g[token_of], None, None


_sum_of_pairs.defvjp(_sum_of_pairs_fwd, _sum_of_pairs_bwd)


def moe_routed_ffn(x, gate_w, bias, w1, w3, w2, first: int, top_k: int,
                   score: str = "sigmoid", norm_topk: bool = True,
                   scale: float = 1.0, act=jax.nn.silu,
                   scope: str = "moe"):
    """One chip's share of a routed expert layer (module docstring).

    ``x`` ``(tokens, d)``; ``gate_w`` ``(d, E)`` over ALL ``E`` experts
    and ``bias`` ``(E,)`` or None, both float32 (a selection is a
    discrete choice: the router's product runs at the highest
    precision); ``w1``, ``w3`` ``(held, d, f)`` and ``w2`` ``(held, f,
    d)`` are experts ``first .. first + held``, each a gated unit ``w2
    (act(x w1) * (x w3))``.

    The ``tokens * top_k`` pairs are sorted by expert, pairs of absent
    experts last: the sorted buffer is the static worst case (every pair
    held) and the grouped products (``lax.ragged_dot``, on a TPU a
    kernel that walks only the tiles its group sizes cover) never touch
    the tail.  Each pair's result is weighted by its router weight,
    normalised over all ``top_k`` selected experts whether held or not,
    and summed into its token.

    Returns ``(y (tokens, d), stats)``; ``stats`` holds float32 scalars
    ``pairs_held`` (pairs routed to held experts) and
    ``load_max_over_mean`` (the fullest held expert's pairs over the
    held experts' mean).  The work lies under two scopes of the
    program, ``<scope>.route`` (scores, top-k, sort, gather, scatter)
    and ``<scope>.experts`` (the grouped products): siblings by name,
    since an operation counts for its outermost scope."""
    tokens, d = x.shape
    held = w1.shape[0]
    n_pairs = tokens * top_k
    with _probe.scope(f"{scope}.route"):
        logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        choice, weight = route_top_k(logits, bias, top_k, score, norm_topk,
                                     scale)
        local = choice.reshape(n_pairs) - first
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)             # absent: the tail
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros(held + 1, jnp.int32).at[key].add(1)[:held]
        n_held = sizes.sum()
        live = (jnp.arange(n_pairs) < n_held)[:, None]
        token_of = order // top_k
        slot_of = jnp.zeros(n_pairs, order.dtype).at[order].set(
            jnp.arange(n_pairs, dtype=order.dtype))
        # the tail's rows are nobody's, and a grouped product leaves them
        # as it found them (whatever the memory held, NaN included): each
        # result is cut to zeros there BEFORE it meets another factor, so
        # that no gradient is a zero times that
        xs = jnp.where(
            live, _rows_of_pairs(x, token_of, slot_of, top_k), 0)
        ws = weight.reshape(n_pairs)[order]
    with _probe.scope(f"{scope}.experts"):
        def grouped(a, w):
            return jnp.where(live, lax.ragged_dot(a, w, sizes), 0)

        ys = grouped(act(grouped(xs, w1)) * grouped(xs, w3), w2)
    with _probe.scope(f"{scope}.route"):
        ys = ys * ws[:, None].astype(ys.dtype)
        y = _sum_of_pairs(ys, token_of, slot_of, top_k)
        sizes_f = sizes.astype(jnp.float32)
        stats = {"pairs_held": n_held.astype(jnp.float32),
                 "load_max_over_mean":
                     sizes_f.max() / jnp.maximum(sizes_f.mean(), 1e-9)}
    return y, stats
