"""The Mamba-2 mixer (a state-space layer; Dao & Gu, arXiv:2405.21060), as
the ``granitemoehybrid`` family's ``mamba`` layers (one group) and the
``nemotron_h`` family's ``M`` layers (eight) have it, with its scan in the
chunked form.

The layer, on the normed residual stream ``u (b, t, d)``, with ``H`` heads
of ``P`` entries, a state of ``N`` entries a head entry and ``G`` groups of
``H / G`` heads (head ``h`` reads the ``B`` and ``C`` of group ``h // (H /
G)``):

    [z | xBC | dt] = u W_in                      d -> H P + (H P + 2 G N) + H
    xBC = silu(conv(xBC) + b)                    depthwise, causal, zeros
                                                 before the sequence
    [x | B | C] = xBC                            x as (H, P); B, C as (G, N)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)             a head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  h_0 = 0      (P, N) a head
    y_t = h_t C_t + D x_t
    out = RMSNorm(y * silu(z); g) W_out          over each group's H P / G
                                                 entries, gate first

**The scan** (:func:`ssd`) never walks the positions.  In chunks of ``Q``
positions, with ``cs`` the running sum of ``dt A`` inside a chunk:

1. inside a chunk the quadratic form: ``scores = C B^T`` once a chunk and
   a GROUP (not a head), masked by each head's decays ``L[i, j] = exp(cs_i
   - cs_j)`` (``j <= i``), times ``dt x``;
2. a chunk's closing state ``S_c = sum_j exp(cs_last - cs_j) dt_j x_j
   B_j^T``, each head against its group's ``B``;
3. the carry from chunk to chunk, ``h_{c+1} = exp(cs_last) h_c + S_c``
   (``t / Q`` steps of a ``lax.scan``; the one part that is sequential);
4. the carried state's contribution to each position, ``exp(cs_i) h_c
   C_i``, each head against its group's ``C``.

With one group ``B`` and ``C`` carry no group axis and the program is the
one it was before groups were written (:func:`_heads`).

Products run on the operands' dtype (bfloat16 in a training step) and
accumulate in float32; the softplus, the log-decays and their running sums,
every decay factor and the carried state are float32.  Every decay factor
is an ``exp`` of a sum that is at most 0, so none overflows.  ``Q`` changes
no value: a row that ``Q`` does not divide is filled with positions of ``dt
= 0`` (the state passes them unchanged).

**Two forms of one algorithm** (:func:`ssd` asks one question,
:func:`scan_kernel_refusal`, of what it can observe; no switch chooses).
Where the step's kernels run (a TPU, or ``engine.pallas_interpret``) and the
shape fits (``Q`` a multiple of 128, a group of whole blocks of 8 heads, 8
heads and ``N`` whole lane tiles, a visit inside the kernels' VMEM), steps
1 to 4 are two Pallas kernels behind a ``jax.custom_vjp``
(``ops/pallas/ssd.py``: ``ssd_scan_fwd``, ``ssd_scan_bwd``): a visit holds
one chunk of one block of heads, the carry runs inside the kernel over the
chunk axis, and nothing with two chunk-length axes is written to HBM in any
pass; only the running sums ``cs`` (and, under differentiation, their
reverse sums) stay ``jax.numpy``.  Everywhere else, and as the tests'
second opinion, the ``jax.numpy`` form below: :func:`_chunk_states` (2, 3)
and :func:`_chunk_outputs` (1, 4), each a ``jax.checkpoint``.  A refusal is
logged once a shape with its reason.

**The convolution** in front of the scan has two forms too, chosen the
same way (:func:`conv_kernel_refusal`): where the kernels run and the cut
of the projection's lanes is whole lane tiles, ``t`` whole row tiles and the
taps within the rows fetched in front of a tile, two Pallas kernels behind a
``jax.custom_vjp`` (``ops/pallas/ssm_conv.py``: ``ssm_conv_fwd``,
``ssm_conv_bwd``) read the ``xBC`` lanes of the projection itself, do the
float32 sum, the bias, the ``silu`` and the cast in VMEM and write the ``x |
B | C`` array the scan's kernels cut their blocks from; the backward kernel
makes the sum and the sigmoid again and sums the taps' and the bias's
gradients in VMEM.  Where they run, ``[z | xBC]`` and ``dt`` come from two
products, so that the array the kernels cut is whole lane tiles wide (the
64 lanes of ``dt`` behind it make every reader of it slow on a TPU).
Everywhere else :func:`_conv`, the ``jax.numpy`` form, on one product.

**The gate and the gated norm** behind the scan have two forms too, chosen
the same way (:func:`gate_kernel_refusal`): where the kernels run, there
are SEVERAL groups of whole lane tiles each and ``t`` is whole row tiles, two
Pallas kernels behind a ``jax.custom_vjp`` (``ops/pallas/ssm_gate.py``:
``ssm_gate_fwd``, ``ssm_gate_bwd``) read the scan's result as its kernel
wrote it and ``z`` from the projection's own lanes, a tile of rows of one
group's lanes a visit, take the statistic over the lanes the visit holds and
write the operand of the output product; the backward kernel makes the
product, the statistic and the sigmoid again and sums the gain's gradient in
VMEM.  The group-wise view ``(tokens x groups, H P / G)`` of the ``jax.numpy``
form below, which is no bitcast on a TPU's tiles and moves three or four
``(b, t, H P)`` arrays a layer and a pass, does not exist there.  With ONE
group that form has no such view and stands at its traffic's least as XLA
compiles it, so one group is refused with that sentence (the kernels
themselves take any group of whole lane tiles; ``PERF.md`` section 6, PR 49,
has both forms' times alone at both cells' shapes).

**What the backward pass keeps**, in either form: the operands and each
chunk's opening state (named ``ssm_state``: ``(b, t / Q, H, P, N)``
float32 here, ``(b, t / Q, H x P, N)`` in the operands' dtype from the
kernel, which writes it as its products read it).  The ``(H, Q, Q)`` decay
and score matrices of every chunk
(0.5 GB a layer at 8,192 positions) are made again from the operands when
the gradients are, and never stored: by the backward kernel in VMEM, by the
two ``jax.checkpoint`` halves through HBM.  The operands themselves
come from the input projection and the convolution.  The convolution's
kernel names its result (``ssm_conv``, the scan's ``x | B | C``, in the
compute dtype) and the layer's own checkpoint (``transformer.py::
_block_fn``) keeps it as it keeps every kernel's, so that kernel runs once;
the ``jax.numpy`` form is made again.  The projection's result is named
here (``ssm_in``) for ``plan.py::checkpoint_plan`` to keep or refuse; where
it is refused the product is made again and both convolution kernels read
that.  Of the gate's kernels NOTHING is kept: the output product stands
inside their ``custom_vjp``, whose backward rule reads the operands alone
(``ssm_y``, the projection, the gain, the weight) and takes the gated rows
for the weight's gradient from the backward kernel, which writes them again
beside ``dy`` and ``dz`` (one more write of ``(b, t, H P)`` in 16 bits
against 0.125 GiB a layer kept; the layer's ``sub_out`` is kept, so no
recomputation wants the forward kernel either).  The mixer's leaves and
their shapes are ``params.py``'s (``_ssm_leaf_shapes``).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from znicz_tpu.observe import probe as _probe
from znicz_tpu.ops.pallas import (ssd as _pssd, ssm_conv as _pconv,
                                  ssm_gate as _pgate)

_log = logging.getLogger("znicz_tpu.transformer")

#: the mixer's leaves that stay in the master dtype in a step's forward:
#: the step size's bias, the decay rate and the skip enter float32 chains
F32_LEAVES = ("ssm_dt_b", "ssm_a_log", "ssm_d")


def _chunked(a, q: int):
    """``(b, t, ...) -> (b, t / q, q, ...)``."""
    return a.reshape(a.shape[0], a.shape[1] // q, q, *a.shape[2:])


def _running(dt, a, q: int):
    """``cs (b, c, H, q)`` float32: the running sum inside each chunk of the
    log-decays ``dt A`` (each at most 0)."""
    return jnp.cumsum(_chunked(dt * a, q).transpose(0, 1, 3, 2), axis=-1)


def _heads(v, groups: int, axis: int):
    """``v`` with its head axis cut into ``(groups, heads a group)`` for a
    product against a group's ``B`` or ``C``; as it is with one group."""
    if groups == 1:
        return v
    return v.reshape(*v.shape[:axis], groups, v.shape[axis] // groups,
                     *v.shape[axis + 1:])


def _flat_heads(v, groups: int, axis: int):
    """:func:`_heads`, undone."""
    if groups == 1:
        return v
    return v.reshape(*v.shape[:axis], -1, *v.shape[axis + 2:])


def _letters(groups: int) -> tuple:
    """``(a group's axis, the heads' axes)`` of the einsums below."""
    return ("", "h") if groups == 1 else ("g", "gr")


@jax.checkpoint
def _chunk_states(x, dt, a, bm):
    """Steps 2 and 3 on chunked operands ``x (b, c, q, H, P)``, ``dt (b, c *
    q, H)`` float32, ``a (H,)``, ``bm (b, c, q, N)`` (``(b, c, q, G, N)`` of
    several groups) -> ``(each chunk's opening state (b, c, H, P, N), the
    state behind the last position (b, H, P, N))``, float32."""
    q = x.shape[2]
    groups = bm.shape[3] if bm.ndim == 5 else 1
    g, h = _letters(groups)
    cs = _running(dt, a, q)
    to_end = jnp.exp(cs[..., -1:] - cs).transpose(0, 1, 3, 2)   # (b, c, q, H)
    xw = (x.astype(jnp.float32) *
          (_chunked(dt, q) * to_end)[..., None]).astype(x.dtype)
    closing = _flat_heads(jnp.einsum(
        f"bcq{h}p,bcq{g}n->cb{h}pn", _heads(xw, groups, 3), bm,
        preferred_element_type=jnp.float32), groups, 2)
    whole = jnp.exp(cs[..., -1]).transpose(1, 0, 2)             # (c, b, H)

    def carry(h, inp):
        keep, s = inp
        return keep[..., None, None] * h + s, h

    last, opening = lax.scan(carry, jnp.zeros_like(closing[0]),
                             (whole, closing))
    return opening.transpose(1, 0, 2, 3, 4), last


@jax.checkpoint
def _chunk_outputs(x, dt, a, bm, cm, skip, opening):
    """Steps 1 and 4 and the skip -> ``y (b, c, q, H, P)`` in ``x``'s
    dtype."""
    q = x.shape[2]
    groups = bm.shape[3] if bm.ndim == 5 else 1
    g, h = _letters(groups)
    cs = _running(dt, a, q)
    scores = jnp.einsum(f"bci{g}n,bcj{g}n->bc{g}ij", cm, bm,
                        preferred_element_type=jnp.float32)
    seen = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    # masked before the exp: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))                     # (b, c, H, i, j)
    xf = x.astype(jnp.float32)
    xdt = (xf * _chunked(dt, q)[..., None]).astype(x.dtype)
    # a group's scores serve each of its heads, under the head's own decays
    y = jnp.einsum(f"bc{h}ij,bcj{h}p->bci{h}p",
                   (scores[..., None, :, :] *
                    _heads(decay, groups, 2)).astype(x.dtype),
                   _heads(xdt, groups, 3),
                   preferred_element_type=jnp.float32)
    carried = jnp.einsum(f"bci{g}n,bc{h}pn->bci{h}p", cm,
                         _heads(opening.astype(x.dtype), groups, 2),
                         preferred_element_type=jnp.float32)
    y = _flat_heads(y, groups, 3) + _flat_heads(carried, groups, 3) * \
        jnp.exp(cs).transpose(0, 1, 3, 2)[..., None]
    return (y + skip[:, None] * xf).astype(x.dtype)


def _kernels_eligible(interpret: bool) -> bool:
    """Whether the Pallas kernels may run at all: on a TPU, or interpreted."""
    return interpret or jax.default_backend() == "tpu"


def _backend_refusal(interpret: bool) -> str | None:
    """Why none of the step's kernels runs here, or ``None`` where they
    may."""
    if _kernels_eligible(interpret):
        return None
    return (f"the backend is {jax.default_backend()} and the step's "
            f"kernels are not interpreted")


def scan_kernel_refusal(t: int, heads: int, head_dim: int, state: int,
                        groups: int, chunk: int, itemsize: int,
                        interpret: bool) -> str | None:
    """Why the scan of rows of ``t`` positions in chunks of ``chunk`` (the
    whole row where it is shorter) runs in its ``jax.numpy`` form, or
    ``None`` where the kernels of ``ops/pallas/ssd.py`` run it: where the
    step's kernels run at all (a TPU, or ``interpret``: interpreted) and
    the shape is one they take (``ssd.unsupported_reason``: the kernel's own
    reasons).  What :func:`ssd` asks as the step is traced and
    ``transformer.step_choices`` before."""
    return _backend_refusal(interpret) or _pssd.unsupported_reason(
        min(int(chunk), t), heads, groups, head_dim, state, itemsize)


def conv_kernel_refusal(t: int, start: int, width: int, taps: int,
                        interpret: bool) -> str | None:
    """Why the convolution of the lanes ``[start, start + width)`` of the
    projection over rows of ``t`` positions with ``taps`` taps runs in its
    ``jax.numpy`` form (:func:`_conv`), or ``None`` where the kernels of
    ``ops/pallas/ssm_conv.py`` run it: where the step's kernels run at all
    and the shape is one they take (``ssm_conv.unsupported_reason``).  What
    :func:`mixer` asks as the step is traced and ``transformer.
    step_choices`` before."""
    return _backend_refusal(interpret) or \
        _pconv.unsupported_reason(t, start, width, taps)


def gate_kernel_refusal(t: int, inner: int, groups: int, start: int,
                        itemsize: int, interpret: bool) -> str | None:
    """Why the gate and the gated norm of ``inner`` entries of ``itemsize``
    bytes in ``groups`` groups, ``z`` the lanes ``[start, start + inner)``
    of the projection, over rows of ``t`` positions run in their
    ``jax.numpy`` form (the closing lines of :func:`mixer`), or ``None``
    where the kernels of ``ops/pallas/ssm_gate.py`` run them: where the
    step's kernels run at all, the shape is one they take
    (``ssm_gate.unsupported_reason``) and there are several groups.  What
    :func:`mixer` asks as the step is traced and ``transformer.
    step_choices`` before."""
    if groups == 1 and _kernels_eligible(interpret):
        # measured, PERF.md section 6 (PR 49): the kernels take a group of
        # any whole lane tiles, all 4,096 of one too
        return ("one group: the statistic over a whole row has no "
                "group-wise view, and the compiled jax.numpy form already "
                "stands at its traffic's least there")
    return _backend_refusal(interpret) or \
        _pgate.unsupported_reason(t, inner, groups, start, itemsize)


@functools.lru_cache(maxsize=None)
def _report_refusal(what: str, shape: str, why: str, level: int) -> None:
    """Say once a shape and a process that the layer's ``what`` left its
    kernels."""
    _log.log(level, "state-space %s kernels refused %s: %s; this layer's "
             "%s runs in jax.numpy", what, shape, why, what)


def _kernels_or_none(what: str, refusal, names: str, shape: tuple, **more):
    """THE choice between the two forms of the layer's ``what``, by what
    can be observed (``refusal`` of ``shape``, whose entries ``names``
    names, and ``more``) -> ``None`` for ``jax.numpy``, else the kernels'
    ``interpret`` argument."""
    from znicz_tpu.core.config import root
    interpret = bool(root.common.engine.get("pallas_interpret", False))
    why = refusal(*shape, **more, interpret=interpret)
    if why:
        # a shape the kernels turn down where they could run is news; a
        # backend without them is not
        said = " ".join(f"{k}={v}" for k, v in zip(names.split(), shape))
        _report_refusal(what, said, why, logging.WARNING
                        if _kernels_eligible(interpret) else logging.INFO)
        return None
    return interpret


def ssd(x, dt, a, bm, cm, skip, chunk: int):
    """The scan: ``x (b, t, H, P)``, ``dt (b, t, H)`` float32 (after the
    softplus), ``a (H,)`` float32 (negative), ``bm``, ``cm`` ``(b, t, N)``
    of one group or ``(b, t, G, N)`` of ``G`` (``H / G`` heads each, in
    order), ``skip (H,)`` float32 -> ``(y (b, t, H, P) in x's dtype, the
    state behind the last position (b, H, P, N) float32)``, in chunks of
    ``chunk`` positions (the whole row where it is shorter)."""
    b, t, heads, p = x.shape
    groups = bm.shape[2] if bm.ndim == 4 else 1
    q = min(int(chunk), t)
    fill = -t % q
    if fill:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, fill)) +
                                 ((0, 0),) * (v.ndim - 2))
                         for v in (x, dt, bm, cm))
    interpret = _kernels_or_none(
        "scan", scan_kernel_refusal, "t heads head_dim state groups chunk",
        (t, heads, p, bm.shape[-1], groups, int(chunk)),
        itemsize=x.dtype.itemsize)
    if interpret is not None:
        cs = jnp.cumsum(_chunked(dt * a, q), axis=2).reshape(dt.shape)
        # x | B | C side by side, as the mixer cut them from the
        # convolution's result: XLA folds the cuts and this back into that
        # array, and the kernels cut their blocks from its lanes
        xbc = jnp.concatenate([v.reshape(b, t + fill, -1)
                               for v in (x, bm, cm)], axis=-1)
        y, last = _pssd.scan(xbc, dt, cs, skip, heads * p, groups, q,
                             interpret)
        return (y.reshape(x.shape)[:, :t],
                last.reshape(b, heads, p, bm.shape[-1]))
    x, bm, cm = (_chunked(v, q) for v in (x, bm, cm))
    opening, last = _chunk_states(x, dt, a, bm)
    opening = checkpoint_name(opening, "ssm_state")
    y = _chunk_outputs(x, dt, a, bm, cm, skip, opening)
    return y.reshape(b, t + fill, *y.shape[3:])[:, :t], last


def _conv(v, taps, bias):
    """Depthwise causal convolution over time with zeros before the
    sequence, ``c_t = sum_j k_j v_{t - taps + 1 + j} + bias``, in float32."""
    n, t = taps.shape[0], v.shape[1]
    vp = jnp.pad(v.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    kf = taps.astype(jnp.float32)
    return sum(kf[j] * vp[:, j:j + t] for j in range(n)) + \
        bias.astype(jnp.float32)


def _conv_silu(proj, taps, bias, start: int, interpret):
    """``silu(conv(proj[..., start:start + channels]) + bias)`` in ``proj``'s
    dtype, ``channels`` the taps' ``(taps, channels)``: the scan's ``x | B |
    C``, by the kernels of ``ops/pallas/ssm_conv.py`` (``interpret``: what
    :func:`conv_kernel_refusal` left of them), by :func:`_conv` where that
    is None."""
    if interpret is None:
        cut = proj[..., start:start + taps.shape[1]]
        return jax.nn.silu(_conv(cut, taps, bias)).astype(proj.dtype)
    coef = jnp.concatenate([taps.astype(jnp.float32),
                            bias.astype(jnp.float32)[None]], axis=0)
    return _pconv.conv(proj, coef, start, interpret)


def mixer(u, p, heads: int, head_dim: int, state: int, chunk: int,
          eps: float, scope: str, groups: int = 1):
    """The layer on the normed stream ``u (b, t, d)`` -> ``(out (b, t, d),
    stats)``.  Scopes: ``scope`` and, opened inside it, ``scope.in`` (the
    input products and the cut of ``z``), ``scope.gate`` (the gate and the
    gated norm) and ``scope.out`` (the output product); ``scope.conv`` and
    ``scope.scan`` (softplus, decays, the four steps, the skip), siblings
    by name.  ``stats`` (of
    this layer; a step sums them over its layers): ``ssm_decay``, the mean
    over positions and heads of ``exp(dt A)``; ``ssm_state_rms``, the RMS
    of the state behind the last position (a row's, mean over the rows);
    ``ssm_layers``, 1.  Neither
    depends on ``chunk``."""
    b, t, _ = u.shape
    inner, bc = heads * head_dim, groups * state
    conv_kernels = _kernels_or_none(
        "convolution", conv_kernel_refusal, "t start width taps",
        (t, inner, inner + 2 * bc, p["ssm_conv_k"].shape[0]))
    gate_kernels = _kernels_or_none(
        "gate", gate_kernel_refusal, "t inner groups start",
        (t, inner, groups, 0), itemsize=u.dtype.itemsize)
    with _probe.scope(scope), _probe.scope(f"{scope}.in"):
        w_in, wide = p["ssm_in"], 2 * inner + 2 * bc
        if conv_kernels is None:
            proj = checkpoint_name(u @ w_in, "ssm_in")
            dt = proj[..., wide:]
        else:
            # z | xBC, whole lane tiles wide, and dt by a product of its
            # own: the convolution's kernels cut their blocks from the
            # projection's own lanes, and an array whose last axis is no
            # multiple of 128 (8,512 and 10,304 in the two cells: 66.5 and
            # 80.5 tiles) is read at 0.4 of the rate on a v5e, by a
            # kernel's block fetch and by XLA's own slices alike (0.70
            # against 0.29 ms for 4,352 lanes of 8,192 rows; my chip run,
            # PR 47)
            proj = checkpoint_name(u @ w_in[:, :wide], "ssm_in")
            dt = checkpoint_name(u @ w_in[:, wide:], "ssm_in")
        # cut here, in front of the convolution's, as the projection's
        # cotangents are summed in the order of its uses (the gate's
        # kernels cut their own and leave this one dead)
        z = proj[..., :inner]
    with _probe.scope(f"{scope}.conv"):
        xbc = _conv_silu(proj, p["ssm_conv_k"], p["ssm_conv_b"], inner,
                         conv_kernels)
    with _probe.scope(f"{scope}.scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             p["ssm_dt_b"].astype(jnp.float32))
        a = -jnp.exp(p["ssm_a_log"].astype(jnp.float32))
        x = xbc[..., :inner].reshape(b, t, heads, head_dim)
        bm, cm = xbc[..., inner:inner + bc], xbc[..., inner + bc:]
        if groups > 1:
            bm, cm = (v.reshape(b, t, groups, state) for v in (bm, cm))
        y, last = ssd(x, dt, a, bm, cm, p["ssm_d"].astype(jnp.float32),
                      chunk)
        # named (b, t, inner) wide, as the gate reads it: a saved array
        # whose last axis is one head is laid out time-minor, and then
        # copied on either side of the scan's kernels
        y = checkpoint_name(y.reshape(b, t, inner), "ssm_y")
        last = lax.stop_gradient(last)
        stats = {"ssm_decay": lax.stop_gradient(jnp.exp(dt * a)).mean(),
                 "ssm_state_rms":
                     jnp.sqrt((last * last).mean((1, 2, 3))).mean(),
                 "ssm_layers": jnp.ones((), jnp.float32)}
    parts = (f"{scope}.gate", f"{scope}.out")
    with _probe.scope(scope):
        if gate_kernels is not None:
            # z by a block spec on the projection's first lanes
            return _pgate.gate_out(
                y, proj, p["ssm_g"].reshape(1, inner).astype(u.dtype),
                p["ssm_out"], 0, groups, float(eps), gate_kernels,
                parts), stats
        with _probe.scope(parts[0]):
            gated = y.astype(jnp.float32) * \
                jax.nn.silu(z.astype(jnp.float32))
            # the statistic over each group's entries (the gain lies a
            # group a row, ``params._ssm_leaf_shapes``); over all of them
            # with one group.  A row a (token, group): a statistic ``(b, t,
            # groups)`` wide is laid out time-minor and takes the gated
            # product with it
            gated = gated.reshape(b * t * groups, inner // groups)
            gated = gated * lax.rsqrt(
                (gated * gated).mean(-1, keepdims=True) + eps)
            gated = gated.astype(u.dtype).reshape(b, t, groups, -1) * \
                p["ssm_g"].reshape(groups, -1)
        with _probe.scope(parts[1]):
            return gated.reshape(b, t, inner) @ p["ssm_out"], stats
