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
  products over the held experts' groups and sums back into tokens,
  over a buffer sized from the pairs it has counted (the static worst
  case as the fallback).  No capacity and no drop; what absent experts
  would add is left out (their chips add it).  The score function, the
  selection bias and the expert's form (a gated unit of three weights, or a
  plain one of two) are arguments.  Its all-to-all form over an expert
  axis is what would retire the two above (ROADMAP.md).

:func:`load_balance_aux` is the shared switch load-balance regularizer.
"""

from __future__ import annotations

import functools
import logging

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from znicz_tpu.observe import probe as _probe
from znicz_tpu.ops.pallas import grouped as _gmm

_log = logging.getLogger("znicz_tpu.moe")


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
    # the selected scores by a compare over the E columns: a gather of
    # tokens x k scalars and its scatter-add back cost 0.33 + 0.28 ms a
    # layer at 8,192 tokens (my chip run, PR 29); each sum has one term
    picked = choice[..., None] == jnp.arange(s.shape[-1])
    w = jnp.where(picked, s[:, None, :], 0).sum(-1)
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


#: the compact pairs buffer over the mean this share receives
#: (``n_pairs * held / E``).  A trained model's selection bias balances
#: the chips to a fraction of a per cent and biases drawn independently
#: left the fullest share at 1.29 x the mean (26,383-42,307 pairs a step
#: over four layers of 8,192, my chip runs, PR 28): 1.5 keeps both on
#: the compact branch, and a load beyond it is computed whole by the
#: full buffer, slower and never short of a pair.
_COMPACT_SLACK = 1.5
#: rows are rounded up to ``lax.ragged_dot``'s row tile (the v5e's
#: kernel walks rows by 512: its tile table in the compiled step has
#: ``rows / 512 + held - 1`` entries, 39 at 12,288 rows and 79 at 32,768),
#: a multiple of the Pallas kernels' (``ops/pallas/grouped.py::ROW_TILE``)
_ROW_TILE = 512
#: counters that are averaged, not summed, over layers and shards
MEAN_STATS = ("load_max_over_mean", "compact", "tile_fill", "act_zero")


def relu2(h):
    """Squared ReLU, the plain expert's activation (``w2 relu(x w1)^2``)."""
    return jnp.square(jax.nn.relu(h))


def compact_rows(n_pairs: int, held: int, n_experts: int) -> int:
    """Rows of the compact pairs buffer of a share of ``held`` of
    ``n_experts`` experts: a function of static shapes only.  At
    ``n_pairs`` (a chip that holds every expert, or a layer too small to
    gain a tile) there is one buffer and nothing to choose."""
    rows = int(np.ceil(_COMPACT_SLACK * n_pairs * held / n_experts))
    return min(-(-rows // _ROW_TILE) * _ROW_TILE, n_pairs)


@functools.lru_cache(maxsize=None)
def _report_kernel_refusal(shape: tuple, why: str) -> None:
    """The grouped-product kernels were eligible by platform and the
    shape turned them down: say so, once per shape per process."""
    _log.warning("grouped-product kernels refused rows=%d k=%d n=%d "
                 "held=%d: %s; this layer uses lax.ragged_dot", *shape, why)


def _kernels_eligible(interpret: bool) -> bool:
    """Whether the Pallas kernels may run at all: on a TPU, or interpreted."""
    return interpret or jax.default_backend() == "tpu"


def gmm_kernel_refusal(rows: int, k: int, n: int, held: int, dtype,
                       interpret: bool) -> str | None:
    """Why a grouped product of ``rows`` rows with ``(held, k, n)`` weights
    (``k`` and ``n`` in either order) runs on ``lax.ragged_dot`` and not on
    the Pallas kernels (``ops/pallas/grouped.py``), or None: what
    ``transformer.step_choices`` asks, by the deciders :func:`_gmm_kernels`
    asks as the step is traced."""
    if not _kernels_eligible(interpret):
        return "no TPU, and engine.pallas_interpret is not set"
    return _gmm.unsupported_reason(rows, k, n, held, dtype)


def _gmm_kernels(rows: int, k: int, n: int, held: int, dtype):
    """THE choice between the two forms of a grouped product of ``rows``
    rows with ``(held, k, n)`` weights (``k`` and ``n`` in either
    order), by what can be observed, as the flash kernels are picked
    (``transformer.py::_flash_eligible``): the Pallas kernels
    (``ops/pallas/grouped.py``) on a TPU, or interpreted wherever
    ``root.common.engine.pallas_interpret`` is set, at shapes they
    accept; ``lax.ragged_dot`` elsewhere.  -> ``None`` for
    ``lax.ragged_dot``, else the kernels' ``interpret`` argument."""
    from znicz_tpu.core.config import root
    interpret = bool(root.common.engine.get("pallas_interpret", False))
    if not _kernels_eligible(interpret):
        return None
    why = _gmm.unsupported_reason(rows, k, n, held, dtype)
    if why:
        _report_kernel_refusal((rows, k, n, held), why)
        return None
    return interpret


def _row_tile(rows: int, k: int, n: int, held: int, dtype) -> int:
    """Rows to a tile of the grouped product :func:`_gmm_kernels` picks."""
    return _ROW_TILE if _gmm_kernels(rows, k, n, held, dtype) is None \
        else _gmm.ROW_TILE


#: the grouped product's gradient to its weights on the ``lax.ragged_dot``
#: path: each group's rows of the left operand, transposed, times its
#: rows of the result's cotangent (what AD's own transpose of
#: ``lax.ragged_dot`` builds)
_TO_WEIGHTS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=(0,), rhs_group_dimensions=())


def _grouped(a, w, sizes, live):
    """``a``'s rows times their group's weights (cast to ``a``'s dtype),
    zeros on the buffer's tail.  The tail's rows are nobody's, and
    ``lax.ragged_dot`` leaves them as it found them (whatever the memory
    held, NaN included): each result is cut BEFORE it meets another
    factor, so that no gradient is a zero times that.  The kernels write
    the zeros themselves.  Differentiable in both forms (the kernels'
    rules are the two other kernels)."""
    interpret = _gmm_kernels(*a.shape, w.shape[2], w.shape[0], a.dtype)
    if interpret is None:
        return jnp.where(live, lax.ragged_dot(a, w.astype(a.dtype), sizes),
                         0)
    return _gmm.gmm(a, w.astype(a.dtype), sizes, interpret)


def _grouped_to_rows(g, w, sizes, live):
    """:func:`_grouped`'s gradient to its rows, cut like its result: on
    the kernels' path read from ``w`` as it is stored."""
    w = w.astype(g.dtype)
    rows, (held, k, n) = g.shape[0], w.shape
    interpret = _gmm_kernels(rows, k, n, held, g.dtype)
    if interpret is None:
        return jnp.where(live, jax.linear_transpose(
            lambda a: lax.ragged_dot(a, w, sizes),
            jax.ShapeDtypeStruct((rows, k), g.dtype))(g)[0], 0)
    return _gmm.gmm_rows_t(g, w, sizes, interpret=interpret)


def _grouped_to_weights(a, g, sizes, dtype):
    """:func:`_grouped`'s gradient to its weights, in ``dtype`` (float32
    masters: as the products accumulate it, unrounded)."""
    interpret = _gmm_kernels(*a.shape, g.shape[1], sizes.shape[0], a.dtype)
    if interpret is None:
        return lax.ragged_dot_general(a, g, sizes, _TO_WEIGHTS,
                                      preferred_element_type=dtype)
    return _gmm.gmm_weights(a, g, sizes, interpret=interpret).astype(dtype)


def _experts_hidden(hs: tuple, act):
    """The experts' hidden rows from their up-projections' results: the
    gated unit's ``act(h1) * h3``, or of the plain form ``act(h1)``."""
    return act(hs[0]) * hs[1] if len(hs) == 2 else act(hs[0])


def _zeroed(hs: tuple, live):
    """Of the plain form's live hidden entries, how many its activation (a
    squared ReLU) zeroes, float32; None of the gated unit (no leaf: its
    program is the one it was)."""
    if len(hs) == 2:
        return None
    return jnp.where(live, hs[0] <= 0, False).sum(dtype=jnp.float32)


def _experts(xs, ws: tuple, sizes, live, act):
    """The grouped products of the pairs' rows ``xs`` through the experts'
    weights ``ws`` (``(w1, w3, w2)`` of the gated unit: three products;
    ``(w1, w2)`` of the plain form: two) -> ``(ys, the up-projections'
    results, :func:`_zeroed`)``."""
    hs = tuple(_grouped(xs, w, sizes, live) for w in ws[:-1])
    ys = _grouped(_experts_hidden(hs, act), ws[-1], sizes, live)
    return ys, hs, _zeroed(hs, live)


def _pairs_full(x, weight, ws: tuple, order, slot_of, sizes, top_k: int,
                act, scope: str):
    """The pairs stage over the static worst case: all ``tokens *
    top_k`` sorted pairs, whatever their number (``sizes.sum()``) that
    held experts receive.  Dispatch and combine are gathers both ways
    (:func:`_rows_of_pairs`, :func:`_sum_of_pairs`).  -> ``(y,
    :func:`_zeroed`)``."""
    n_pairs = order.shape[0]
    with _probe.scope(f"{scope}.route"):
        live = (jnp.arange(n_pairs) < sizes.sum())[:, None]
        token_of = order // top_k
        xs = jnp.where(
            live, _rows_of_pairs(x, token_of, slot_of, top_k), 0)
        wp = weight[order]
    with _probe.scope(f"{scope}.experts"):
        ys, _, zeroed = _experts(xs, ws, sizes, live, act)
    with _probe.scope(f"{scope}.route"):
        ys = ys * wp[:, None].astype(ys.dtype)
        return _sum_of_pairs(ys, token_of, slot_of, top_k), zeroed


def _compact_index(order, sizes, rows: int, top_k: int):
    """-> ``(order_c, token_c, n_held, live)`` of the first ``rows``
    sorted pairs: every pair of a held expert is among them when
    ``sizes.sum() <= rows`` (held pairs sort first)."""
    order_c, n_held = order[:rows], sizes.sum()
    return order_c, order_c // top_k, n_held, \
        (jnp.arange(rows) < n_held)[:, None]


def _sum_of_slots(rows_of, slot_of, n_held, top_k: int):
    """Each token's sum over its live pairs' rows of ``rows_of`` (the
    pairs' first sorted slots), accumulated in f32.  One gather a
    choice, token-major, and a dead pair reads row 0: what costs is the
    rows read at random, and those are the live pairs only (0.38 ms
    against 1.43 for a scatter-add of the same 12,288 rows and 1.82 for
    the full buffer's gather, 8,192 tokens x 2,048, my chip run,
    PR 29)."""
    slots = slot_of.reshape(-1, top_k)
    acc = jnp.zeros((slots.shape[0], rows_of.shape[-1]), jnp.float32)
    for j in range(top_k):
        held = slots[:, j] < n_held
        acc = acc + jnp.where(
            held[:, None], rows_of[jnp.where(held, slots[:, j], 0)], 0)
    return acc.astype(rows_of.dtype)


def _compact_fwd(x, weight, ws: tuple, order, slot_of, sizes, rows: int,
                 top_k: int, act, scope: str):
    """The pairs stage over the first ``rows`` sorted pairs -> ``((y,
    :func:`_zeroed`), the up-projections' results)``: the same arithmetic
    for every live pair as :func:`_pairs_full`, and the products its
    backward needs."""
    with _probe.scope(f"{scope}.route"):
        order_c, token_c, n_held, live = _compact_index(order, sizes, rows,
                                                        top_k)
        xs = jnp.where(live, x[token_c], 0)
        wp = weight[order_c]
    with _probe.scope(f"{scope}.experts"):
        ys, hs, zeroed = _experts(xs, ws, sizes, live, act)
    with _probe.scope(f"{scope}.route"):
        ys = ys * wp[:, None].astype(ys.dtype)
        return (_sum_of_slots(ys, slot_of, n_held, top_k), zeroed), hs


def _compact_bwd(x, weight, ws: tuple, order, slot_of, sizes, hs: tuple, g,
                 rows: int, top_k: int, act, scope: str, dtypes: tuple):
    """:func:`_compact_fwd`'s gradients to ``(x, weight, ws)`` from the
    up-projections' results ``hs`` and a second gather of the rows: for
    each of the experts' weights a grouped product to the rows and one to
    the weights (each the transpose AD itself would take: six of the gated
    unit, four of the plain form), none computed twice, and nothing kept at
    the full buffer's size.  The weights' gradients leave their products
    in ``dtypes``, their masters' (float32: unrounded)."""
    def to_rows(g_out, w):
        return _grouped_to_rows(g_out, w, sizes, live)

    def to_weights(a, g_out, dtype):
        return _grouped_to_weights(a, g_out, sizes, dtype)

    with _probe.scope_bwd(f"{scope}.route"):
        order_c, token_c, n_held, live = _compact_index(order, sizes, rows,
                                                        top_k)
        xs = jnp.where(live, x[token_c], 0)
        wp = weight[order_c][:, None]
        gy = g[token_c]
    with _probe.scope_bwd(f"{scope}.experts"):
        hh, hh_vjp = jax.vjp(lambda *h: _experts_hidden(h, act), *hs)
        # ys = (hh w2) * wp: the product's own cotangent gives both the
        # weights' (its rows against hh's) and hh's (times wp)
        t = to_rows(gy, ws[-1])
        d_down = to_weights(hh * wp.astype(hh.dtype), gy, dtypes[-1])
        d_hs = hh_vjp(t * wp.astype(t.dtype))
        d_ups = tuple(to_weights(xs, d_h, dtype)
                      for d_h, dtype in zip(d_hs, dtypes))
        d_xs = to_rows(d_hs[0], ws[0])
        for d_h, w in zip(d_hs[1:], ws[1:-1]):
            d_xs = d_xs + to_rows(d_h, w)
    with _probe.scope_bwd(f"{scope}.route"):
        d_wp = (t.astype(jnp.float32) * hh.astype(jnp.float32)).sum(-1)
        d_weight = jnp.zeros(weight.shape, weight.dtype).at[order_c].set(
            d_wp.astype(weight.dtype), unique_indices=True)
        d_x = _sum_of_slots(d_xs, slot_of, n_held, top_k)
    return d_x, d_weight, (*d_ups, d_down)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _pairs_either(x, weight, ws: tuple, order, slot_of, sizes, rows: int,
                  top_k: int, act, scope: str):
    """The pairs stage at the size the layer observes: over ``rows``
    pairs when the held experts' pairs (``sizes.sum()``) fit, over all
    of them otherwise -> ``(y, :func:`_zeroed`)``.  One rule for both
    passes, so that AD does not
    make each branch of the choice write the other's residuals as zeros:
    the compact branch keeps its up-projections' results
    (:func:`_compact_bwd`),
    the full one keeps nothing and takes :func:`_pairs_full` again in
    the backward pass.  The experts' weights are cast to ``x``'s dtype
    once, before the choice, and both passes of either branch read that
    cast: a grouped product and its gradient to the rows take a weight
    in the same layout (ops/pallas/grouped.py), so the backward pass
    makes no second pass over the masters."""
    return _pairs_either_fwd(x, weight, ws, order, slot_of, sizes, rows,
                             top_k, act, scope)[0]


def _pairs_either_fwd(x, weight, ws, order, slot_of, sizes, rows, top_k,
                      act, scope):
    with _probe.scope(f"{scope}.experts"):
        cast = tuple(w.astype(x.dtype) for w in ws)
    args = (x, weight, cast, order, slot_of, sizes)

    def full(*a):
        blank = jnp.zeros((rows, ws[0].shape[-1]), x.dtype)
        return _pairs_full(*a, top_k, act, scope), (blank,) * (len(ws) - 1)

    with _probe.scope(f"{scope}.route"):
        fits = sizes.sum() <= rows
    out, kept = lax.cond(
        fits, lambda *a: _compact_fwd(*a, rows, top_k, act, scope), full,
        *args)
    # a kernel's output that a checkpointed layer keeps as it keeps every
    # other's (``plan._loop_saves``): out of the ``cond`` it needs a name
    return out, (args, checkpoint_name(kept, "moe_up"), ws)


def _pairs_either_bwd(rows, top_k, act, scope, res, g):
    args, kept, masters = res       # the masters: their gradients' dtypes
    dtypes = tuple(w.dtype for w in masters)

    def full(x, weight, ws, order, slot_of, sizes, hs, g):
        d_x, d_weight, d_ws = jax.vjp(
            lambda *a: _pairs_full(*a, order, slot_of, sizes, top_k, act,
                                   scope)[0],
            x, weight, ws)[1](g)
        return d_x, d_weight, tuple(d.astype(t)
                                    for d, t in zip(d_ws, dtypes))

    with _probe.scope_bwd(f"{scope}.route"):
        fits = args[-1].sum() <= rows           # sizes: as the forward chose
    grads = lax.cond(
        fits, lambda *a: _compact_bwd(*a, rows, top_k, act, scope, dtypes),
        full, *args, kept, g[0])                # the count takes no gradient
    return (*grads, None, None, None)


_pairs_either.defvjp(_pairs_either_fwd, _pairs_either_bwd)


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
    (act(x w1) * (x w3))`` or, with ``w3`` None, the plain ``w2 act(x w1)``
    (``act`` :func:`relu2`: the squared-ReLU expert), in ``x``'s dtype or
    as float32 masters: the
    products run in ``x``'s dtype either way, and masters take their
    gradients as the products accumulate them, unrounded.

    The ``tokens * top_k`` pairs are sorted by expert, pairs of absent
    experts last, and the pairs stage (gather, the grouped products: three
    of the gated unit, two of the plain form,
    the weights, the sum into tokens, and their backward) runs over a
    buffer sized from what the layer observes: the first
    :func:`compact_rows` sorted pairs when the held experts' pairs fit
    there, all ``tokens * top_k`` (the static worst case, every pair
    held) when they do not, chosen on the device by the count.  No
    capacity and no drop at any load; a share of all ``E`` experts has
    the one buffer.  The grouped products (:func:`_gmm_kernels`: on a
    TPU the Pallas kernels of ``ops/pallas/grouped.py``, elsewhere
    ``lax.ragged_dot``) walk only the row tiles their group sizes cover
    and never read a buffer's tail.  Each pair's result is weighted by
    its router weight, normalised over all ``top_k`` selected experts
    whether held or not, and summed into its token.

    Returns ``(y (tokens, d), stats)``; ``stats`` holds float32 scalars
    ``pairs_held`` (pairs routed to held experts), ``load_max_over_mean``
    (the fullest held expert's pairs over the held experts' mean),
    ``compact`` (1.0 when the compact buffer carried the layer),
    ``tile_fill`` (held pairs over the row-slots the grouped products
    visit: a row tile that two groups share is visited twice; reckoned
    with the row tile of the form that ran) and, of the plain form alone,
    ``act_zero`` (over the held pairs, the share of the experts' hidden
    entries that ``act`` zeroes: ``x w1 <= 0``).  The
    work lies under two scopes of the program, ``<scope>.route``
    (scores, top-k, sort, gather, scatter) and ``<scope>.experts`` (the
    grouped products): siblings by name, since an operation counts for
    its outermost scope."""
    tokens, d = x.shape
    held = w1.shape[0]
    n_pairs = tokens * top_k
    rows = compact_rows(n_pairs, held, gate_w.shape[1])
    with _probe.scope(f"{scope}.route"):
        logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        choice, weight = route_top_k(logits, bias, top_k, score, norm_topk,
                                     scale)
        local = choice.reshape(n_pairs) - first
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)             # absent: the tail
        order = jnp.argsort(key, stable=True)
        slot_of = jnp.argsort(order)                   # its inverse
        sizes = (key[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)
        n_held = sizes.sum()
        # named for a checkpointed layer's policy (``plan._loop_saves``; a
        # name is no operation): kept, no sort runs twice
        weight, order, slot_of, sizes = checkpoint_name(
            (weight.reshape(n_pairs), order, slot_of, sizes), "moe_route")
    ws = (w1, w2) if w3 is None else (w1, w3, w2)
    stage = (x, weight, ws, order, slot_of, sizes)
    if rows < n_pairs:
        y, zeroed = _pairs_either(*stage, rows, top_k, act, scope)
    else:                                   # one buffer, nothing to choose
        y, zeroed = _pairs_full(*stage, top_k, act, scope)
    with _probe.scope(f"{scope}.route"):
        sizes_f = sizes.astype(jnp.float32)
        compact = (n_held <= rows) & (rows < n_pairs)
        fill_c, fill_f = (_gmm.tile_fill(sizes, _row_tile(
            r, d, w1.shape[2], held, x.dtype)) for r in (rows, n_pairs))
        stats = {"pairs_held": n_held.astype(jnp.float32),
                 "load_max_over_mean":
                     sizes_f.max() / jnp.maximum(sizes_f.mean(), 1e-9),
                 "compact": compact.astype(jnp.float32),
                 "tile_fill": jnp.where(compact, fill_c, fill_f)}
        if zeroed is not None:
            stats["act_zero"] = lax.stop_gradient(zeroed) / jnp.maximum(
                stats["pairs_held"] * w1.shape[2], 1.0)
    return y, stats
