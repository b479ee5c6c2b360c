"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): linear attention
whose state follows the delta rule under a decay a KEY CHANNEL, as the
``solar_open2`` family's linear-attention layers have it, in the chunked
form.

The layer, on the normed residual stream ``u (b, t, d)``, with ``H`` heads of
``K`` entries (keys and values alike) and a low rank ``R``:

    [q~ | k~ | v] = silu(conv(u W_in))          d -> 3 H K; depthwise, causal,
                                                 no bias, zeros before the
                                                 sequence
    q = q~ / ||q~|| K^-1/2,  k = k~ / ||k~||    a head's K entries, eps 1e-6
    g = -exp(A_log) softplus((u W_f1) W_f2 + dt_bias)
                                                 the log-decay, a key channel
                                                 (A_log a head); alpha = e^g
    beta = 2 sigmoid(u W_b)                      a head (1 sigmoid without
                                                 negative eigenvalues)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                              S (K, K) a head, S_0 = 0
    out = (RMSNorm_K(o; gain) * sigmoid((u W_g1) W_g2 + b_g)) W_out
                                                 the norm over a head's K
                                                 entries first, then the gate

**The rule** (:func:`delta`) never walks the positions.  In chunks of ``C``
positions, ``G`` the running sum of ``g`` inside a chunk and ``S`` the state
that opens it:

1. the keys' scores under the decays, ``A[i, j] = sum_c k_ic k_jc exp(G_ic -
   G_jc)`` (``j < i``), and the queries' against the keys, ``j <= i``
   (:func:`_scores`).  The decay sits INSIDE the contraction over the
   channels, and ``exp(G_i) exp(-G_j)`` over a whole chunk overflows under a
   strong decay, so a chunk is cut in halves again and again: a block of
   rows in a second half against the columns of its first half takes both
   factors against the running sum at the first half's last position, which
   lies between ``j`` and ``i``, ``exp(G_i - G_edge) exp(G_edge - G_j)``,
   each the ``exp`` of a number that is at most 0; the diagonal sub-blocks
   of :data:`DIRECT` positions are made directly, a channel at a time.
   Nothing ``(C, C, K)`` is written;
2. the delta rule couples a chunk's positions: ``T = (I + strict_lower(
   Diag(beta) A))^-1 Diag(beta)``, a unit-triangular inverse a chunk and
   head, by the same halves (``[[a, 0], [c, b]]^-1 = [[a^-1, 0], [-b^-1 c
   a^-1, b^-1]]``: block forward substitution), then ``W = T (K * exp(G))``,
   ``U = T V`` (:func:`_prepared`);
3. the carry from chunk to chunk, ``S' = Diag(exp(G_last)) S + (K *
   exp(G_last - G))^T (U - W S)`` (``t / C`` steps of a ``lax.scan``; the one
   part that is sequential);
4. a position's output, ``(Q * exp(G)) S + tril(QK scores) (U - W S)``.

Float32: the log-decays, their running sums, every decay factor, ``beta``,
the L2 norms, the triangular inverse (its products at the highest
precision) and the carried state.  Products run on the operands' dtype
(bfloat16 in a training step) and accumulate in float32, as ``ssm.py``'s
do: a key or query times its decay factor is rounded to that dtype for the
product, and so are ``T``, ``W``, ``U - W S`` and the state where a product
reads them.  ``C`` is a tile, a power of two: it changes no value beyond
rounding, and a row it does not divide is filled with positions of ``g = 0``,
``beta = 0`` (the state passes them unchanged).

**Two forms of one algorithm** (:func:`delta` asks one question,
:func:`delta_kernel_refusal`, of what it can observe; no switch chooses).
Where the step's kernels run (a TPU, or ``engine.pallas_interpret``) and the
shape fits (a head of exactly 128 entries, keys and values alike: one lane
tile; ``C`` a power of two from 16 to 128; the heads whole stacks of ``128 /
C``; a visit inside the kernels' VMEM), steps 1 to 4 are two Pallas kernels
behind a ``jax.custom_vjp`` (``ops/pallas/kda_delta.py``: ``kda_delta_fwd``,
``kda_delta_bwd``): a visit holds one chunk of a block of heads, ``q``, ``k``
and ``v`` cut by lanes from the convolution's own ``(b, t, 3 H K)`` result
(the L2 norms of a head's ``q`` and ``k`` taken in the kernels: :func:`mixer`
hands the array over whole, :func:`_packed`) and ``g`` from its ``(b, t, H
K)``, the float32 state of a row's heads stays in VMEM over the row's chunks, and the
scores, the inverse, ``W``, ``U``, ``K e^(G_last - G)`` and every head-major
copy never reach HBM in any pass.  There the halves go all the way down (no
direct sub-blocks: a level is one 128-row product), the inverse's float32
products run as three 16-bit passes on two-term operands, the running sums are
made inside the levels from ``g`` itself, and ``U - W S`` is ``T (V - (K
e^G) S)``, the same number.  Everywhere else, and as the tests' second
opinion, the ``jax.numpy`` form below.  A refusal is logged once a shape
with its reason.

**What the backward pass keeps**, in either form: the operands and each
chunk's opening state (named ``kda_state``, cast as the outputs' products
read it: ``(b, H, t / C, K, V)`` here, ``(b, t / C, H V, K)`` transposed from
the kernel, which writes it as its products read it).  The scores, the
inverse, ``W`` and ``U`` are made again from the operands when the gradients
are, and never stored: by the backward kernel in VMEM (``dN = -M^T dM M^T``;
the decays' cotangent a channel-wise product of each level's operand with
its cotangent, walked back through the running sums), by the three
``jax.checkpoint`` parts below through HBM (:func:`_prepared` (1, 2),
:func:`_carried` (3) and :func:`_outputs` (1 for the queries, 4); the
gradients are those of the chunked form as written, no hand-written rule).
The operands themselves come from the kept convolution result by
elementwise work and two small products.  The kernel names its output
``kda_y`` and the layer's own checkpoint (``transformer.py::_block_fn``)
keeps it as it keeps every kernel's, so the forward kernel runs once a
step.  The input projection is named ``kda_in`` for ``plan.py::
checkpoint_plan`` to keep or refuse and the heads' output ``kda_y``.

**The convolution** in front is the state-space layer's without a bias over
the projection's ``3 H K`` lanes: where ``ssm.conv_kernel_refusal`` says None
the two Pallas kernels of ``ops/pallas/ssm_conv.py`` read the projection
itself (a bias row of zeros), else ``ssm._conv``; a refusal is logged once a
shape, as the state-space layer's is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from znicz_tpu.observe import probe as _probe
from znicz_tpu.parallel import ssm

#: the mixer's leaves that stay in the master dtype in a step's forward: the
#: decay's bias and rate enter float32 chains
F32_LEAVES = ("kda_dt_b", "kda_a_log")

#: positions of a diagonal sub-block of the scores, made directly (a
#: channel at a time, no factoring of the decay); a power of two
DIRECT = 4

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def l2_normed(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, the statistic in
    float32, in ``x``'s dtype."""
    xf = x.astype(_F32)
    return (xf * lax.rsqrt((xf * xf).sum(-1, keepdims=True) + eps)
            ).astype(x.dtype)


def _halves(a, h: int):
    """``(..., C, d)`` in segments of ``2 h`` positions -> ``(their first
    halves, their second halves)``, each ``(..., C / 2h, h, d)``."""
    seg = a.reshape(*a.shape[:-2], a.shape[-2] // (2 * h), 2 * h, a.shape[-1])
    return seg[..., :h, :], seg[..., h:, :]


def _paired(m):
    """Blocks ``(..., 2 s, h, h)`` -> ``(the even ones, the odd ones)``."""
    pair = m.reshape(*m.shape[:-3], m.shape[-3] // 2, 2, *m.shape[-2:])
    return pair[..., 0, :, :], pair[..., 1, :, :]


def _joined(a, b, off):
    """``[[a, 0], [off, b]]`` of blocks ``(..., h, h)``."""
    return jnp.concatenate(
        [jnp.concatenate([a, jnp.zeros_like(off)], axis=-1),
         jnp.concatenate([off, b], axis=-1)], axis=-2)


def _merged(diag, offs, lower_left=lambda a, b, off: off):
    """A lower-triangular matrix from its parts by halves: ``diag (..., n,
    s, s)`` the diagonal sub-blocks, ``offs[l] (..., n / 2^(l+1), h, h)``
    (``h = s 2^l``) the block under the diagonal of each pair of level
    ``l`` -> ``(..., n s, n s)``; ``lower_left(a, b, off)`` gives a pair's
    lower-left block from the pair's merged diagonal blocks and ``off``."""
    m = diag
    for off in offs:
        a, b = _paired(m)
        m = _joined(a, b, lower_left(a, b, off))
    return m[..., 0, :, :]


def _scores(rows, cols, gs):
    """Step 1: the parts (:func:`_merged`'s ``diag, offs``) of ``sum_c
    rows_ic cols_jc exp(gs_ic - gs_jc)`` over ``j <= i``, float32; ``rows``,
    ``cols`` ``(..., C, K)``, ``gs (..., C, K)`` the running sums of the
    log-decays (float32, falling along ``C``).  Every factor is the ``exp``
    of a number that is at most 0."""
    n, width = cols.shape[-2:]
    lead = cols.shape[:-2]
    s = min(DIRECT, n)

    def blocks(a):
        return a.reshape(*lead, n // s, s, width)

    gb = blocks(gs)
    seen = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])[..., None]
    # masked before the exp: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(seen, gb[..., :, None, :] - gb[..., None, :, :],
                              -jnp.inf))                     # (.., s, s, K)
    diag = (blocks(rows).astype(_F32)[..., :, None, :] *
            blocks(cols).astype(_F32)[..., None, :, :] * decay).sum(-1)
    offs, h = [], s
    while h < n:
        g_lo, g_hi = _halves(gs, h)
        edge = g_lo[..., -1:, :]       # lies between every j and every i
        left = (_halves(rows, h)[1].astype(_F32) *
                jnp.exp(g_hi - edge)).astype(rows.dtype)
        right = (_halves(cols, h)[0].astype(_F32) *
                 jnp.exp(edge - g_lo)).astype(cols.dtype)
        offs.append(jnp.einsum("...id,...jd->...ij", left, right,
                               preferred_element_type=_F32))
        h *= 2
    return diag, offs


def _small_inverse(n):
    """``(I + n)^-1`` of strictly lower-triangular blocks ``n (..., s, s)``
    of a few positions, by forward substitution, unrolled."""
    s = n.shape[-1]
    eye = jnp.eye(s, dtype=n.dtype)
    rows = []
    for i in range(s):
        row = jnp.broadcast_to(eye[i], n.shape[:-2] + (s,))
        for j in range(i):
            row = row - n[..., i, j, None] * rows[j]
        rows.append(row)
    return jnp.stack(rows, axis=-2)


@jax.checkpoint
def _prepared(k, v, g, beta):
    """Steps 1 and 2 on chunked, head-major operands ``k (b, H, c, C, K)``,
    ``v (b, H, c, C, V)``, ``g`` as ``k`` float32, ``beta (b, H, c, C)``
    float32 -> what the carry and the outputs read: ``(exp(G_last) (b, H, c,
    K)`` float32, ``W`` in ``k``'s dtype, ``U`` float32, ``K * exp(G_last -
    G)`` in ``k``'s dtype``)``."""
    n = k.shape[-2]
    gs = jnp.cumsum(g, axis=-2)
    diag, offs = _scores(k, k, gs)
    s = diag.shape[-1]
    strict = jnp.arange(s)[:, None] > jnp.arange(s)[None, :]
    diag = jnp.where(strict, diag, 0.0) * \
        beta.reshape(*beta.shape[:-1], n // s, s)[..., None]
    offs = [off * beta.reshape(*beta.shape[:-1], -1, 2 * off.shape[-1])[
        ..., off.shape[-1]:, None] for off in offs]
    # the unit-triangular inverse, float32 throughout: a pair's lower-left
    # block is -b^-1 c a^-1 of its two inverted diagonal blocks
    inverse = _merged(
        _small_inverse(diag), offs, lambda a, b, c: -jnp.einsum(
            "...ij,...jk,...kl->...il", b, c, a, precision=_HIGHEST))
    t = (inverse * beta[..., None, :]).astype(k.dtype)
    kf = k.astype(_F32)
    w = jnp.einsum("...ij,...jd->...id", t,
                   (kf * jnp.exp(gs)).astype(k.dtype),
                   preferred_element_type=_F32).astype(k.dtype)
    u = jnp.einsum("...ij,...jd->...id", t, v, preferred_element_type=_F32)
    # k against the decays from its position to the chunk's end
    kd = (kf * jnp.exp(gs[..., -1:, :] - gs)).astype(k.dtype)
    return jnp.exp(gs[..., -1, :]), w, u, kd


@jax.checkpoint
def _carried(whole, w, u, kd):
    """Step 3 -> ``(each chunk's opening state (b, H, c, K, V) as the
    outputs' products read it, in ``w``'s dtype, the state behind the last
    position (b, H, K, V) float32)``; the carry itself is float32."""
    def carry(s, inp):
        keep, w_c, u_c, kd_c = inp
        opens = s.astype(w_c.dtype)
        new = u_c - jnp.einsum("bhik,bhkv->bhiv", w_c, opens,
                               preferred_element_type=_F32)
        return keep[..., None] * s + jnp.einsum(
            "bhik,bhiv->bhkv", kd_c, new.astype(kd_c.dtype),
            preferred_element_type=_F32), opens

    zero = jnp.zeros(w.shape[:2] + (w.shape[-1], u.shape[-1]), _F32)
    last, opening = lax.scan(carry, zero, tuple(
        jnp.moveaxis(a, 2, 0) for a in (whole, w, u, kd)))
    return jnp.moveaxis(opening, 0, 2), last


def _chunk_states(k, v, g, beta):
    """Steps 1 to 3 -> ``(W, U, each chunk's opening state, the state behind
    the last position)``."""
    whole, w, u, kd = _prepared(k, v, g, beta)
    return (w, u) + _carried(whole, w, u, kd)


@jax.checkpoint
def _outputs(q, k, g, w, u, opening):
    """Steps 1 (the queries' scores) and 4 on each chunk's ``opening`` state
    ``(b, H, c, K, V)`` in ``k``'s dtype -> ``o (b, H, c, C, V)`` in ``k``'s
    dtype."""
    gs = jnp.cumsum(g, axis=-2)
    new = u - jnp.einsum("...ik,...kv->...iv", w, opening,
                         preferred_element_type=_F32)
    seen = _merged(*_scores(q, k, gs)).astype(k.dtype)         # j <= i
    qg = (q.astype(_F32) * jnp.exp(gs)).astype(q.dtype)
    o = jnp.einsum("...ik,...kv->...iv", qg, opening,
                   preferred_element_type=_F32) + \
        jnp.einsum("...ij,...jv->...iv", seen, new.astype(k.dtype),
                   preferred_element_type=_F32)
    return o.astype(k.dtype)


def delta_kernel_refusal(t: int, heads: int, head_dim: int, chunk: int,
                         itemsize: int, interpret: bool) -> str | None:
    """Why the rule over rows of ``t`` positions of ``heads`` heads of
    ``head_dim`` entries (keys and values alike) of ``itemsize`` bytes in
    chunks of ``chunk`` runs in its ``jax.numpy`` form, or ``None`` where the
    kernels of ``ops/pallas/kda_delta.py`` run it: where the step's kernels
    run at all (a TPU, or ``interpret``: interpreted) and the shape is one
    they take (``kda_delta.unsupported_reason``: the kernel's own reasons;
    ``t`` is filled to whole chunks in either form).  What :func:`delta`
    asks as the step is traced and ``transformer.step_choices`` before."""
    from znicz_tpu.ops.pallas import kda_delta as _pdelta
    return ssm._backend_refusal(interpret) or _pdelta.unsupported_reason(
        int(chunk), heads, head_dim, itemsize)


def delta(q, k, v, g, beta, chunk: int):
    """The rule: ``q``, ``k`` ``(b, t, H, K)``, ``v (b, t, H, V)`` in one
    dtype, ``g (b, t, H, K)`` float32 (the log-decays, at most 0), ``beta (b,
    t, H)`` float32 -> ``(o (b, t, H, V) in that dtype, the state behind the
    last position (b, H, K, V) float32)``, in chunks of ``chunk`` positions
    (a power of two): by the two kernels where :func:`delta_kernel_refusal`
    says None, else as written below."""
    b, t, heads, _ = q.shape
    fill = -t % chunk
    if fill:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, fill)) +
                                    ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    interpret = None
    if v.shape == q.shape:
        interpret = ssm._kernels_or_none(
            "delta rule", delta_kernel_refusal, "t heads head_dim chunk",
            (t, heads, q.shape[-1], int(chunk)), itemsize=q.dtype.itemsize)
    if interpret is not None:
        from znicz_tpu.ops.pallas import kda_delta as _pdelta
        # the operands as the layer has them, a head a lane tile: the
        # kernels cut their blocks from the lanes
        o, last = _pdelta.delta(
            *(a.reshape(b, t + fill, -1) for a in (q, k, v, g)), beta,
            int(chunk), interpret)
        return o.reshape(q.shape)[:, :t], last

    def chunked(a):                      # head-major: (b, H, c, C, ...)
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape(b, heads, -1, chunk, *a.shape[3:])

    q, k, v, g, beta = (chunked(a) for a in (q, k, v, g, beta))
    w, u, opening, last = _chunk_states(k, v, g, beta)
    opening = checkpoint_name(opening, "kda_state")
    o = _outputs(q, k, g, w, u, opening)
    o = jnp.moveaxis(o.reshape(b, heads, t + fill, -1), 1, 2)
    return o[:, :t], last


def _packed(qkv, g, beta, chunk: int, interpret: bool):
    """The rule by the kernels on the layer's own ``qkv (b, t, 3 H K)``,
    ``g (b, t, H K)`` float32 and ``beta (b, t, H)`` float32, the L2 norms
    of q and k (:func:`l2_normed`'s, the queries times ``K^-1/2``) taken in
    the kernels -> ``(o (b, t, H V), the state behind the last position)``;
    a row the chunk does not divide is filled as :func:`delta` fills it."""
    from znicz_tpu.ops.pallas import kda_delta as _pdelta
    t = qkv.shape[1]
    fill = -t % chunk
    if fill:
        qkv, g, beta = (jnp.pad(a, ((0, 0), (0, fill), (0, 0)))
                        for a in (qkv, g, beta))
    o, last = _pdelta.delta_packed(qkv, g, beta, chunk, 1e-6, interpret)
    return o[:, :t], last


def mixer(u, p, heads: int, head_dim: int, chunk: int, neg_eigval: bool,
          eps: float, scope: str):
    """The layer on the normed stream ``u (b, t, d)`` -> ``(out (b, t, d),
    stats)``.  Scopes: ``scope`` and, opened inside it, ``scope.in`` (the ``q
    | k | v`` product), ``scope.gate`` (the gate's pair, the head norm, the
    multiply) and ``scope.out`` (the output product); ``scope.conv`` (the
    convolutions and their ``silu``) and ``scope.delta`` (the L2 norms, the
    log-decays and ``beta``, every step of the rule), siblings by name.
    ``stats`` (of this layer; a step sums them over its layers):
    ``kda_decay``, the mean over positions, heads and channels of
    ``exp(g)``; ``kda_beta``, the mean of ``beta``; ``kda_state_rms``, the
    RMS of the state behind the last position (a row's, mean over the
    rows); ``kda_layers``, 1.  None depends on ``chunk``."""
    b, t, _ = u.shape
    inner = heads * head_dim
    taps = p["kda_conv_k"]
    conv_kernels = ssm._kernels_or_none(
        "convolution", ssm.conv_kernel_refusal, "t start width taps",
        (t, 0, 3 * inner, taps.shape[0]))
    with _probe.scope(scope), _probe.scope(f"{scope}.in"):
        proj = checkpoint_name(u @ p["kda_in"], "kda_in")
    with _probe.scope(f"{scope}.conv"):
        qkv = ssm._conv_silu(proj, taps, jnp.zeros((3 * inner,), _F32), 0,
                             conv_kernels)
    with _probe.scope(f"{scope}.delta"):
        # the log-decays: float32 from the second product on, a head's rate
        # on each of its channels
        pre = jnp.einsum("btr,rn->btn", u @ p["kda_f1"], p["kda_f2"],
                         preferred_element_type=_F32) + \
            p["kda_dt_b"].astype(_F32)
        g = -jnp.repeat(jnp.exp(p["kda_a_log"].astype(_F32)), head_dim) * \
            jax.nn.softplus(pre)
        beta = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bth", u, p["kda_b"], preferred_element_type=_F32))
        if neg_eigval:
            # I - beta k k^T then has its eigenvalue along k in (-1, 1)
            beta = beta * 2.0
        kernels = ssm._kernels_or_none(
            "delta rule", delta_kernel_refusal, "t heads head_dim chunk",
            (t, heads, head_dim, int(chunk)), itemsize=qkv.dtype.itemsize)
        if kernels is not None:
            # q | k | v as the convolution left them: the kernels cut the
            # three from the lanes and take the L2 norms a head themselves
            o, last = _packed(qkv, g, beta, int(chunk), kernels)
        else:
            q, k, v = (qkv[..., i * inner:(i + 1) * inner].reshape(
                b, t, heads, head_dim) for i in range(3))
            q, k = l2_normed(q) * (head_dim ** -0.5), l2_normed(k)
            o, last = delta(q, k, v, g.reshape(b, t, heads, head_dim), beta,
                            chunk)
        # named (b, t, inner) wide, as the state-space layer's ``ssm_y`` is
        o = checkpoint_name(o.reshape(b, t, inner), "kda_y")
        last = lax.stop_gradient(last)
        stats = {"kda_decay": lax.stop_gradient(jnp.exp(g)).mean(),
                 "kda_beta": lax.stop_gradient(beta).mean(),
                 "kda_state_rms":
                     jnp.sqrt((last * last).mean((1, 2, 3))).mean(),
                 "kda_layers": jnp.ones((), _F32)}
    with _probe.scope(scope):
        with _probe.scope(f"{scope}.gate"):
            of = o.astype(_F32).reshape(b, t, heads, head_dim)
            normed = (of * lax.rsqrt((of * of).mean(-1, keepdims=True) + eps)
                      ).astype(u.dtype) * p["kda_norm_g"]
            gate = jax.nn.sigmoid((u @ p["kda_g1"]) @ p["kda_g2"] +
                                  p["kda_g_b"])
            gated = normed.reshape(b, t, inner) * gate
        with _probe.scope(f"{scope}.out"):
            return gated @ p["kda_out"], stats
