"""What a layer DOES on local shards: :func:`_block` and each sub-layer
kind it is made of (the attention core and what chooses it, latent
attention, the indexer's selection, the short convolution, the state-space
layer, the feed-forwards), the norms and the rotary embedding, and
:class:`_Run`, what a step build fixes beside the architecture.
"""

from __future__ import annotations

import dataclasses
import functools
import logging

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from znicz_tpu.observe import probe as _probe
from znicz_tpu.parallel import dsa, kda, ssm, tp
from znicz_tpu.parallel.arch import Arch
from znicz_tpu.parallel.moe import (load_balance_aux, moe_ffn,
                                    moe_routed_ffn, relu2, router_z_loss)
from znicz_tpu.parallel.ring_attention import (ring_attention,
                                               ring_flash_attention)


def _layer_norm(x, g, b, eps=1e-5):
    # stats in f32 regardless of the compute dtype (bf16 mean/var loses
    # ~3 decimal digits); the normalized result returns to x.dtype so the
    # surrounding matmuls stay on the MXU's bf16 path
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = ((xf - mu) / jnp.sqrt(var + eps)).astype(x.dtype)
    return y * g + b


_log = logging.getLogger("znicz_tpu.transformer")


@functools.lru_cache(maxsize=None)
def _report_flash_choice(t: int, dh: int, why: str | None,
                         direct: bool, align: str | None = None) -> None:
    """What a shape that was eligible for a flash kernel by platform and
    mesh got, said once per shape per process: a refusal with its reason
    (the dense ``ring_attention`` path that takes over materializes the
    score matrix, which is a different program, not a detail), or the
    layout its kernels read (``attention.direct_layout``: the layer's
    own, or operands folded head-major around them) and, of the
    key/value-blocked form, the rows of each pass's tile
    (``attention.kvb_block_rows``).  ``align``: of a layer with an
    indexer, what makes its index scores and its alignment target
    (:func:`_dsa_choice`)."""
    if why:
        _log.warning("flash attention refused t=%d head_dim=%d: %s; this "
                     "step uses dense ring_attention", t, dh, why)
        return
    from znicz_tpu.ops.pallas import attention as pattn
    layout = "the layer's (batch, t, heads x head_dim) layout" if direct \
        else ("operands folded head-major (batch x heads, t, head_dim): "
              "eight transposes a layer")
    tiles = pattn.kvb_block_rows(t, dh)
    blocked = "; key/value-blocked, tiles of %s rows" % " / ".join(
        f"{rows} ({name})" for name, rows in tiles.items()) \
        if any(tiles.values()) else ""
    _log.info("flash attention t=%d head_dim=%d: kernels read %s%s%s",
              t, dh, layout, blocked, f"; {align}" if align else "")


def _dsa_choice(t: int, heads: int, kv: int, dh: int, hi: int, di: int,
                interpret: bool) -> str:
    """What makes a layer's index scores with their gradients and its
    alignment target, in words for the step's one INFO line a shape
    (``dsa.index_kernel_refusal``, ``dsa.align_kernel_refusal``)."""
    from znicz_tpu.ops.pallas import dsa as pdsa
    said = []
    for what, why, names in (
            ("the index scores and their gradients",
             dsa.index_kernel_refusal(t, hi, di, interpret),
             (pdsa.INDEX_SCORES_KERNEL_NAME, pdsa.INDEX_GRADS_KERNEL_NAME)),
            ("the alignment target",
             dsa.align_kernel_refusal(t, heads, kv, dh, interpret),
             (pdsa.ALIGN_KERNEL_NAME,))):
        said.append(f"{what} by the jax.numpy form ({why})" if why else
                    f"{what} by kernel{'s' * (len(names) > 1)} "
                    f"{' and '.join(names)}")
    return "; ".join(said)


@dataclasses.dataclass(frozen=True)
class _Run:
    """What a step build fixes beside the architecture: the local head
    counts, the attention core, the regularizer weights.  ``use_flash``,
    ``interpret`` and ``use_ring_flash`` are captured together at
    step-build time so one config snapshot governs all three
    flash-related decisions (kernel choice, interpreter, vma mode)."""

    heads_local: int
    kv_heads_local: int
    use_flash: bool = False
    interpret: bool = False
    use_ring_flash: bool = False
    moe_aux_weight: float = 0.0
    moe_zloss_weight: float = 0.0
    #: bytes of device memory the backend reports (``plan._memory_limit``),
    #: None where it reports none: what ``plan.checkpoint_plan`` divides
    hbm_limit: int | None = None


def _rms_norm(x, g, eps):
    # the statistic in f32, as _layer_norm's
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return y.astype(x.dtype) * g


def _norm(x, p, which: str, arch: Arch):
    if arch.norm == "rms":
        return _rms_norm(x, p[which + "_g"], arch.eps)
    return _layer_norm(x, p[which + "_g"], p[which + "_b"], arch.eps)


def _sub_out(y, p, which: str, arch: Arch):
    """A sub-layer's output on its way to the residual sum: named for the
    recomputation policies (``transformer._block_fn``; a name is no operation),
    through the sandwich's second norm where the stack has one, and times
    ``arch.residual_mult`` where that is not 1."""
    y = checkpoint_name(y, "sub_out")
    if arch.sandwich:
        y = _norm(y, p, which, arch)
    return y if arch.residual_mult == 1.0 else y * arch.residual_mult


def _rope_angles(t: int, dh: int, theta: float):
    """``cos`` and ``sin`` ``(t, dh / 2)`` of the rotary angles of
    positions 0 .. t-1 over a rotated width ``dh``, float32."""
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, theta: float, interleaved: bool = False):
    """Rotary embedding over the whole head of ``x (b, t, h, dh)``,
    rotate-half form, positions from 0 (the seq axis is unsharded
    wherever this runs), in f32.  ``interleaved``: the pairs are the
    neighbours ``(2i, 2i + 1)``; they are first brought to the halves'
    order (evens, then odds), in which the result stays, as the
    DeepSeek-V3 family's code leaves it: queries and keys are permuted
    alike, so their products are those of rotating in place."""
    t, dh = x.shape[1], x.shape[-1]
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    cos, sin = _rope_angles(t, dh, theta)
    cos = jnp.concatenate([cos] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([sin] * 2, axis=-1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., dh // 2:], xf[..., :dh // 2]], axis=-1)
    return (xf * cos + half * sin).astype(x.dtype)


def _block(x, p, arch: Arch, run: _Run, index: int = 0):
    """Layer ``index`` of ``arch`` on local shards (``n_layers``: the MTP
    module's): its mixer, then its feed-forward, each reading a norm of
    the residual stream and adding to it; a layer of one sub-layer
    (``"none"`` for the other) runs that one behind its one norm.  ->
    ``(x, aux, stats)``: the
    regularizer term (pre-weighted) and the layer's counters (the routed
    layer's, and :func:`_block_attn`'s of a layer that ran a flash
    kernel or has an indexer, whose alignment term ``aux`` carries).
    Scopes: ``block<index>.attn`` (with ``.attn.latent`` beside it for
    what latent attention does before the kernel, ``.attn.index``,
    ``.attn.select`` and ``.attn.align`` for an indexer), ``.sconv`` or
    ``.ssm`` (with ``.ssm.conv`` and ``.ssm.scan`` beside it) or ``.kda``
    (with ``.kda.conv`` and ``.kda.delta`` beside it), then
    ``block<index>.mlp`` or ``.moe`` (with ``.moe.route``,
    ``.moe.experts`` and ``.moe.shared`` beside it)."""
    mixer, ffn = arch.kinds(index)
    stats: dict = {}
    if mixer == "sconv":
        with _probe.scope(f"block{index}.sconv"):
            x = _block_sconv(x, p, arch, run)
    elif mixer == "mamba":
        x, stats = _block_ssm(x, p, arch, f"block{index}.ssm")
    elif mixer == "kda":
        x, stats = _block_kda(x, p, arch, f"block{index}.kda")
    elif mixer != "none":
        x, stats = _block_attn(x, p, arch, run, f"block{index}.attn", index)
    if ffn == "moe_routed":
        x, aux, routed = _block_routed(x, p, arch, f"block{index}.moe")
        stats = {**stats, **routed}
    elif ffn != "none":
        with _probe.scope(f"block{index}.mlp"):
            x, aux = _block_mlp(x, p, arch, ffn, run)
    else:
        aux = jnp.zeros((), jnp.float32)
    if "loss_index" in stats:
        # an indexer's alignment term joins the loss as a regularizer does
        aux = aux + stats["loss_index"]
    return x, aux, stats


def _block_ssm(x, p, arch: Arch, scope: str):
    """A state-space layer (``ssm.mixer``) on the normed stream; the norm
    and the residual sum lie under ``scope``.  -> ``(x, stats)``."""
    with _probe.scope(scope):
        u = _norm(x, p, "ln1", arch)
    y, stats = ssm.mixer(u, p, arch.ssm_heads, arch.ssm_head_dim,
                         arch.ssm_state, arch.ssm_chunk, arch.eps, scope,
                         arch.ssm_groups)
    with _probe.scope(scope):
        return x + _sub_out(y, p, "ln1o", arch), stats


def _block_kda(x, p, arch: Arch, scope: str):
    """A delta-rule linear-attention layer (``kda.mixer``) on the normed
    stream; the norm and the residual sum lie under ``scope``.  -> ``(x,
    stats)``."""
    with _probe.scope(scope):
        u = _norm(x, p, "ln1", arch)
    y, stats = kda.mixer(u, p, arch.kda_heads, arch.kda_head_dim,
                         arch.kda_chunk, arch.kda_neg_eigval, arch.eps, scope)
    with _probe.scope(scope):
        return x + _sub_out(y, p, "ln1o", arch), stats


def _plain_qkv(h, p, arch: Arch, run: _Run, rotates: bool,
               windowed: bool = False):
    """Queries, keys and values ``(b, t, heads, head_dim)`` of plain or
    grouped-query attention: three projections, the score scale where it is
    not the kernels' own (``arch.attn_mult``: q takes ``attn_mult *
    sqrt(head_dim)``, exact where that is a power of two), the optional
    QK-norm, and where the layer ``rotates`` (``arch.rotates``) the rotary
    embedding over the whole head: rotate-half, by
    the in-place row kernel where :func:`_rows_rope` says so (a head of 128:
    the whole head is the kernel's tail), else :func:`_rotate`'s f32 chain of
    array operations.  What no kernel wrote is named ``attn_qkv`` for the
    looped stack's recomputation policy, which keeps every kernel's output
    anyway (``plan._loop_saves``; a name is no operation)."""
    b, t_loc, _ = h.shape

    def heads_of(w, n):
        y = h @ w                                    # (b, t_loc, d_local)
        return y.reshape(b, t_loc, n, -1)

    q = heads_of(p["wq"], run.heads_local)
    if arch.attn_mult is not None:
        q = q * (arch.attn_mult * float(np.sqrt(arch.head_dim)))
    k = heads_of(p["wk"], run.kv_heads_local)
    v = checkpoint_name(heads_of(p["wv"], run.kv_heads_local), "attn_qkv")
    if arch.qk_norm:
        q = _rms_norm(q, p["q_g"], arch.eps)
        k = _rms_norm(k, p["k_g"], arch.eps)
    if rotates and _rows_rope(t_loc, arch, run, windowed):
        from znicz_tpu.ops.pallas import rope as prope
        cos, sin = _rope_angles(t_loc, arch.head_dim, arch.rope_theta)
        return tuple(prope.rope_tail(
            a.reshape(b, t_loc, -1), cos, sin, a.shape[2],
            run.interpret).reshape(a.shape) for a in (q, k)) + (v,)
    if rotates:
        q, k = _rotate(q, arch.rope_theta), _rotate(k, arch.rope_theta)
    return checkpoint_name(q, "attn_qkv"), checkpoint_name(k, "attn_qkv"), v


def _rows_rope(t: int, arch: Arch, run: _Run,
               windowed: bool = False) -> bool:
    """Whether the rotated columns of every head (latent attention's
    ``rope_dim`` tail; of plain attention the whole head) are rotated as
    whole rows of heads by the in-place kernel (``ops/pallas/rope.py``):
    where the flash kernels read the layer's own layout
    (``attention.direct_layout``) and the kernel takes the shape.
    Elsewhere the head is cut and concatenated, and the flash kernels fold
    or copy it anyway."""
    from znicz_tpu.ops.pallas import attention as pattn, rope as prope
    dh = arch.head_dim
    return run.use_flash and pattn.direct_layout(t, dh, windowed) and \
        prope.unsupported_reason(t, dh, arch.rope_dim or dh) is None


def _latent_q(c_q, wq_b, arch: Arch, run: _Run):
    """Latent attention's queries ``(b, t, heads, nope + rope)`` from the
    normed query latent: ``[q_nope | q_pe] = c_q wq_b`` a head, ``q_pe``
    rotated.  Where :func:`_rows_rope` says so the product's ``(b, t,
    heads * head_dim)`` result is rotated in place (the weight's columns
    permuted first so that a head's rotary pairs lie in halves order, the
    order :func:`_rotate` leaves them in): no array op cuts a head."""
    b, t, _ = c_q.shape
    heads, nope, rope = arch.heads, arch.nope_dim, arch.rope_dim
    if not _rows_rope(t, arch, run):
        q = (c_q @ wq_b).reshape(b, t, heads, nope + rope)
        return jnp.concatenate([q[..., :nope], _rotate(
            q[..., nope:], arch.rope_theta, arch.rope_interleaved)], axis=-1)
    from znicz_tpu.ops.pallas import rope as prope
    if arch.rope_interleaved:
        w = wq_b.reshape(-1, heads, nope + rope)
        wq_b = lax.optimization_barrier(jnp.concatenate(
            [w[..., :nope], w[..., nope::2], w[..., nope + 1::2]],
            axis=-1).reshape(wq_b.shape))
    cos, sin = _rope_angles(t, rope, arch.rope_theta)
    return prope.rope_tail(c_q @ wq_b, cos, sin, heads,
                           run.interpret).reshape(b, t, heads, nope + rope)


def _latent_qkv(h, p, arch: Arch, run: _Run):
    """Latent attention's queries, keys and values ``(b, t, heads,
    head_dim)``: ``c_q = RMSNorm(h wq_a)``, ``[q_nope | q_pe] = c_q
    wq_b`` a head; ``[c_kv | k_pe] = h wkv_a``, ``c_kv = RMSNorm(c_kv)``,
    ``[k_nope | v] = c_kv wkv_b`` a head; ``q_pe`` and the ONE ``k_pe``
    all heads share are rotated; ``q = [q_nope | q_pe]``, ``k = [k_nope |
    k_pe]``.  Each of the three is made whole rows of heads at a time (a
    product's result, or rotated in place), never cut inside a head and
    concatenated: XLA then keeps ``(b, t, heads * head_dim)`` row-major,
    the layout the flash kernels read (``attention.direct_layout``), where
    a cut at column ``nope`` makes it lay the array out time-minor and
    copy it for the kernels and back for their gradients."""
    b, t, _ = h.shape
    heads, nope, rope = arch.heads, arch.nope_dim, arch.rope_dim
    c_q = _rms_norm(h @ p["wq_a"], p["q_a_g"], arch.eps)
    q = _latent_q(c_q, p["wq_b"], arch, run)
    kv_a = h @ p["wkv_a"]
    c_kv = _rms_norm(kv_a[..., :arch.kv_lora], p["kv_a_g"], arch.eps)
    k_pe = _rotate(kv_a[..., arch.kv_lora:].reshape(b, t, 1, rope),
                   arch.rope_theta, arch.rope_interleaved)
    # keys and values each come out of a product of their own, whole rows
    # of ``heads`` heads: a head's ``[k_nope | k_pe]`` is ``[c_kv | k_pe]``
    # times ``[its k_nope columns | zeros]`` over ``[zeros | identity]``,
    # so the MXU places the one rotated key in every head (exactly: ones
    # and zeros) where a concatenation with its broadcast would cut the
    # head's row at a column that is no multiple of the 128 lanes
    wkv = p["wkv_b"].reshape(arch.kv_lora, heads, nope + arch.head_dim)
    place = jnp.pad(jnp.eye(rope, dtype=wkv.dtype), ((0, 0), (nope, 0)))
    wk = jnp.concatenate([
        jnp.pad(wkv[..., :nope], ((0, 0), (0, 0), (0, rope))),
        jnp.broadcast_to(place[:, None], (rope, heads, nope + rope))])
    wk, wv = lax.optimization_barrier((
        wk.reshape(arch.kv_lora + rope, -1),
        wkv[..., nope:].reshape(arch.kv_lora, -1)))
    k = jnp.concatenate([c_kv, k_pe.reshape(b, t, rope)], axis=-1) @ wk
    v = c_kv @ wv
    return q, k.reshape(b, t, heads, -1), v.reshape(b, t, heads, -1)


def flash_refusal(t: int, dh: int, run: _Run, selected: bool,
                  window: int | None = None) -> str | None:
    """Why a layer of ``t`` local positions and a head of ``dh`` gets no
    flash kernel where ``run`` lets it try one, or None: asked by the trace
    (:func:`_block_attn`) and by ``transformer.step_choices``.  ``selected``:
    an indexer's selection goes with it, which the blocked form alone takes;
    so it is with a ``window`` on the scores (a sharded ``seq`` axis never
    meets one: ``transformer._check_tp`` refuses the mesh by mechanism)."""
    from znicz_tpu.ops.pallas import attention as pattn
    if run.use_flash and window is not None:
        return pattn.window_unsupported_reason(t, dh, window)
    if run.use_flash and selected:
        return pattn.blocked_unsupported_reason(t, dh)
    if run.use_flash:
        return pattn.form_of(t, dh)[1]
    if run.use_ring_flash:             # the ring merges whole-row blocks
        return pattn.unsupported_reason(t, dh)
    return None


@functools.lru_cache(maxsize=None)
def _report_window_choice(t: int, dh: int, window: int, why: str | None,
                          blocked: bool) -> None:
    """What runs a window layer's attention and why, said once a shape a
    process: the blocked kernels with the tiles each pass's table lists of
    the causal triangle's, or dense attention with the band as a mask."""
    from znicz_tpu.ops.pallas import attention as pattn
    if blocked:
        tiles = pattn.kvb_window_tiles(t, dh, window)
        rows = pattn.kvb_block_rows(t, dh, window=window)
        form = ("the key/value-blocked kernels under the window (" +
                ", ".join(f"{p} {listed} of {whole} tiles of {rows[p]} rows"
                          for p, (listed, whole) in tiles.items()) +
                "; a tile outside the band costs no step and no fetch)")
    else:
        form = ("dense attention with the band as a mask on the (t, t) "
                "scores (" + (why or "no flash kernel on this platform or "
                              "mesh") + ")")
    _log.info("attention t=%d head_dim=%d window=%d: %s", t, dh, window, form)


def _block_attn(x, p, arch: Arch, run: _Run, scope: str, index: int = 0):
    """Attention with tp-sharded heads: ring attention over the seq axis;
    with the seq axis unsharded, ``run.use_flash`` swaps the core for a
    Pallas flash kernel (ops/pallas/attention.py) — same math, no (t, t)
    score matrix in HBM — in the form the shape gets
    (``attention.form_of``: whole-row, key/value-blocked, or refused, and
    then the dense core with one logged line).  Fewer key/value heads
    than query heads go to the flash kernels as they are and to the dense
    core repeated.  The norm, the kernel, the output product and the
    residual sum lie under ``scope``; what latent attention does before
    the kernel under ``scope.latent``, a sibling by name.  -> ``(x,
    stats)``: a layer that ran a flash kernel counts ``attn_flash`` 1 and
    ``attn_direct`` 1 or 0 (``attention.direct_layout``), constants of
    the traced step whose sums over layers give the unit its
    ``znicz_lm_attn_direct_layout_share``.  A layer with an indexer
    (``arch.index_top_k``) hands its kernels the selection
    (:func:`_select_keys`, the three scopes ``scope.index``, ``.select``,
    ``.align``) and adds ``loss_index`` (the alignment term, a local mean
    as a regularizer's is) and the selection's counts.  Layer ``index``
    rotates q and k or not (``arch.rotates``) and may have a window on its
    scores (``arch.window_of``): the blocked kernels then run under
    ``scope.swa`` (the dense core masks the band where no kernel takes the
    shape), and the layer counts ``attn_window`` 1 and
    ``attn_window_tiles`` / ``attn_causal_tiles``, the tiles its three
    passes' tables list and those the causal triangle alone would (equal
    where the window ran as a mask).  A gated output (``arch.attn_gate``):
    ``o * sigmoid(h wg)`` in front of the output product, under
    ``scope.gate``."""
    from znicz_tpu.ops.pallas import attention as pattn
    window = arch.window_of(index)
    with _probe.scope(scope):
        h = _norm(x, p, "ln1", arch)
    b, t_loc, _ = h.shape
    if "wkv_a" in p:
        with _probe.scope(f"{scope}.latent"):
            q, k, v = _latent_qkv(h, p, arch, run)
    else:
        with _probe.scope(scope):
            q, k, v = _plain_qkv(h, p, arch, run, arch.rotates(index),
                                 window is not None)
    sel, picked = None, {}
    if arch.index_top_k:
        sel, picked = _select_keys(h, q, k, p, arch, run, scope)
    dh = q.shape[-1]
    why = flash_refusal(t_loc, dh, run, sel is not None, window)
    eligible = run.use_flash or run.use_ring_flash
    flash = eligible and not why
    direct = bool(flash and run.use_flash and pattn.direct_layout(
        t_loc, dh, window is not None))
    if eligible:
        _report_flash_choice(
            t_loc, dh, why, direct, None if sel is None else
            _dsa_choice(t_loc, q.shape[2], k.shape[2], dh,
                        arch.index_heads, arch.index_dim, run.interpret))
    if window is not None:
        _report_window_choice(t_loc, dh, window, why,
                              bool(run.use_flash and not why))
    with _probe.scope(scope if window is None else f"{scope}.swa"):
        if run.use_flash and not why:
            o = pattn.flash_attention(q, k, v, causal=True,
                                      interpret=run.interpret, sel=sel,
                                      window=window)
        elif sel is not None:
            o = _selected_attention_dense(q, k, v, sel)
        else:
            group = q.shape[2] // k.shape[2]
            if group > 1:
                k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
            if run.use_ring_flash and not why:
                o = ring_flash_attention(q, k, v, "seq", causal=True,
                                         interpret=run.interpret)
            else:
                o = ring_attention(q, k, v, "seq", causal=True,
                                   window=window)
        o = o.reshape(b, t_loc, -1)                  # (b, t_loc, d_local)
    if arch.attn_gate:
        with _probe.scope(f"{scope}.gate"):
            o = o * jax.nn.sigmoid(h @ p["wg"])
    with _probe.scope(scope):
        # a layer that ran a flash kernel counts itself, and once more if
        # its kernels read the layer's layout: known as the step is traced
        stats = {"attn_flash": jnp.ones((), jnp.float32),
                 "attn_direct": jnp.full((), float(direct), jnp.float32)} \
            if flash else {}
        if window is not None:
            stats.update(_window_counts(t_loc, dh, window, bool(
                run.use_flash and not why)))
        y = tp.row_parallel(o, p["wo"], None, "model")
        return x + _sub_out(y, p, "ln1o", arch), {**stats, **picked}


def _window_counts(t: int, dh: int, window: int, blocked: bool) -> dict:
    """A window layer's constants of the traced step: itself, the tiles the
    three passes' visit tables list under the window and those the causal
    triangle's tables would (the same number where the window ran as a mask
    on dense scores: one tile, the whole square)."""
    from znicz_tpu.ops.pallas import attention as pattn
    tiles = pattn.kvb_window_tiles(t, dh, window).values() if blocked \
        else [(1, 1)]
    listed, whole = (float(sum(n)) for n in zip(*tiles))
    return {"attn_window": jnp.ones((), jnp.float32),
            "attn_window_tiles": jnp.full((), listed, jnp.float32),
            "attn_causal_tiles": jnp.full((), whole, jnp.float32)}


def _select_keys(h, q, k, p, arch: Arch, run: _Run, scope: str):
    """A layer's indexer (``parallel/dsa.py``) over a DETACHED copy of the
    layer's normed input ``h`` and of the attention's own ``q`` and ``k``:
    ``qI = h wiq`` (``index_heads`` of ``index_dim``), ``kI = LayerNorm(h
    wik)`` (one head), both rotated over the whole index head with the
    layer's theta, ``w = h wiw * index_heads^-0.5 * index_dim^-0.5`` in
    float32.  -> ``(sel int8 (b, t, t), stats)``: the selection the
    attention kernels take, and ``loss_index`` (the alignment term, which
    alone reaches the indexer's five leaves and reaches nothing else),
    ``dsa_selected`` / ``dsa_pairs`` (selected and causal pairs) and
    ``dsa_live_tiles`` / ``dsa_tiles`` (of the tiles the blocked forward
    kernel visits, those that hold a selected pair, and all of them)."""
    from znicz_tpu.ops.pallas import attention as pattn
    b, t, _ = h.shape
    hi, di = arch.index_heads, arch.index_dim
    with _probe.scope(f"{scope}.index"):
        hd = lax.stop_gradient(h)
        qi = _rotate((hd @ p["wiq"]).reshape(b, t, hi, di), arch.rope_theta)
        ki = _rotate(_layer_norm(hd @ p["wik"], p["ik_g"], p["ik_b"],
                                 arch.eps)[:, :, None], arch.rope_theta)
        w = (hd @ p["wiw"]).astype(jnp.float32) * np.float32(
            1.0 / np.sqrt(hi * di))
    sel, term = dsa.index_select_align(
        qi, ki[:, :, 0], w, lax.stop_gradient(q), lax.stop_gradient(k),
        arch.index_top_k, scope, run.interpret)
    with _probe.scope(f"{scope}.select"):
        block = pattn.kvb_block_rows(t, q.shape[-1], True)["fwd"] or t
        live, tiles = dsa.live_tiles(sel, block)
        stats = {"loss_index": term,
                 "dsa_selected": (sel != 0).sum(dtype=jnp.float32),
                 "dsa_pairs": jnp.float32(b * t * (t + 1) // 2),
                 "dsa_live_tiles": live, "dsa_tiles": tiles}
    return sel, stats


def _selected_attention_dense(q, k, v, sel):
    """Attention over a selection with the scores materialised ``(b,
    heads, t, t)``: what a layer with an indexer falls back to where no
    flash kernel takes it (small shapes off the TPU; the kernels take any
    ``t`` their block divides)."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
    a = jax.nn.softmax(jnp.where(sel[:, None] != 0, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", a.astype(v.dtype), v)


def sconv_kernel_refusal(t: int, d: int, taps: int, bias: bool,
                         interpret: bool) -> str | None:
    """Why the gates and taps of a gated short convolution ``d`` wide over
    rows of ``t`` positions run in their ``jax.numpy`` form
    (:func:`_sconv_gate`), or ``None`` where the kernels of ``ops/pallas/
    sconv.py`` run them: where the step's kernels run at all (a TPU, or
    ``interpret``: interpreted) and the shape is one they take
    (``sconv.unsupported_reason``: cuts of whole lane tiles, rows of whole
    halo tiles, taps within the halo, no convolution bias).  What
    :func:`_block_sconv` asks as the step is traced and ``transformer.
    step_choices`` before."""
    from znicz_tpu.ops.pallas import sconv as psconv
    return ssm._backend_refusal(interpret) or \
        psconv.unsupported_reason(t, d, taps, bias)


@functools.lru_cache(maxsize=None)
def _report_sconv_refusal(shape: tuple, why: str, level: int) -> None:
    """Say once a shape and a process that the layer left its kernels."""
    _log.log(level, "gated short convolution kernels refused t=%d d=%d "
             "taps=%d bias=%s: %s; this layer's gates and taps run in "
             "jax.numpy", *shape, why)


def _sconv_gate(proj, taps, bias=None):
    """The ``jax.numpy`` form of ``C * conv(B * X)`` on ``[B, C, X] =
    split3(proj)``: ``z = B * X`` cast to float32, the taps in float32
    (``c_t = sum_j k_j z_{t-taps+1+j}``, zeros before the sequence), the
    bias where the layer has one, one cast back."""
    gate_b, gate_c, xin = jnp.split(proj, 3, axis=-1)
    z = (gate_b * xin).astype(jnp.float32)
    n, t = taps.shape[0], proj.shape[1]
    zp = jnp.pad(z, ((0, 0), (n - 1, 0), (0, 0)))
    kf = taps.astype(jnp.float32)
    c = sum(kf[j] * zp[:, j:j + t] for j in range(n))
    if bias is not None:
        c = c + bias.astype(jnp.float32)
    return gate_c * c.astype(proj.dtype)


def _block_sconv(x, p, arch: Arch, run: _Run):
    """Gated short convolution: ``[B, C, X] = split3(u W_in)``; ``z = B *
    X``; a depthwise causal convolution over time, ``conv_taps`` taps a
    channel, zeros before the sequence starts (``c_t = sum_j k_j
    z_{t-taps+1+j}``, accumulated in f32); ``out = (C * c) W_out``.

    The gates and the taps between the two products run by the two Pallas
    kernels of ``ops/pallas/sconv.py`` (``sconv_gate_fwd`` /
    ``sconv_gate_bwd`` behind one ``custom_vjp``) wherever
    :func:`sconv_kernel_refusal` says None, picked by what can be observed
    and by no switch: they read the three cuts from the projection itself,
    keep ``u W_in`` and the taps for the backward pass and nothing else, and
    write its cotangent whole.  Elsewhere (off the TPU and not interpreted,
    a width that is no whole lane tiles, rows that are no multiple of 16, a
    convolution bias) :func:`_sconv_gate`, the ``jax.numpy`` form, which
    keeps the three cuts and the sum; a refusal is logged once a shape.
    Either way operands in the compute dtype, ``z``, the taps and the sum
    float32; the kernels keep everything between their operands and their
    results float32 (one rounding each of ``y``, ``dB``, ``dC``, ``dX``),
    where that form as written rounds ``z``, the sum and two cotangents on
    the way.  The two products, the norm and the residual sum are XLA's in
    both."""
    u = _norm(x, p, "ln1", arch)
    proj, taps, bias = u @ p["w_in"], p["conv_k"], p.get("conv_b")
    shape = (u.shape[1], u.shape[2], taps.shape[0], bias is not None)
    why = sconv_kernel_refusal(*shape, run.interpret)
    if why:
        # a shape the kernels turn down where they could run is news; a
        # backend without them is not
        _report_sconv_refusal(shape, why, logging.WARNING if
                              ssm._kernels_eligible(run.interpret) else
                              logging.INFO)
        y = _sconv_gate(proj, taps, bias)
    else:
        from znicz_tpu.ops.pallas import sconv as psconv
        y = psconv.gate(proj, taps.astype(jnp.float32), run.interpret)
    return x + y @ p["w_out"]


def _glu(m, w1, w3, w2):
    """Bias-free SwiGLU.  Its two wide products are named for the
    recomputation policy (``glu_wide``: kept where ``plan.checkpoint_plan``
    finds room; a name is no operation)."""
    return (jax.nn.silu(checkpoint_name(m @ w1, "glu_wide")) *
            checkpoint_name(m @ w3, "glu_wide")) @ w2


def _block_mlp(x, p, arch: Arch, ffn: str, run: _Run):
    m = _norm(x, p, "ln2", arch)
    if ffn == "moe_dense":
        # expert-parallel MoE FFN over the model axis (the block's FFN
        # capacity scales with experts instead of Megatron-splitting ff)
        d = m.shape[-1]
        m2d = m.reshape(-1, d)
        y2d, probs = moe_ffn(m2d, p["gate"], p["ew1"],
                             p["eb1"], p["ew2"], p["eb2"],
                             jax.nn.gelu, axis_name="model",
                             top_k=arch.top_k)
        x = x + y2d.reshape(m.shape)
        # regularizers pre-weighted here (weights are static floats), so
        # the accumulator upstream stays a single scalar.  The z-loss's
        # scores GEMM is identical to moe_ffn's internal one — XLA CSEs
        # them under jit
        aux = run.moe_aux_weight * load_balance_aux(probs)
        if run.moe_zloss_weight:
            aux = aux + run.moe_zloss_weight * router_z_loss(
                m2d @ p["gate"])
        return x, aux
    if ffn == "glu":
        y = _glu(m, p["w1"], p["w3"], p["w2"])
        return x + _sub_out(y, p, "ln2o", arch), jnp.zeros((), jnp.float32)
    x = x + tp.mlp(m, p["w1"], p["b1"], p["w2"], p["b2"],
                   jax.nn.gelu, "model")
    return x, jnp.zeros((), jnp.float32)


def _relu2_mlp(m, w1, w2):
    """The plain squared-ReLU unit ``relu(m w1)^2 w2``.  Its wide product
    is named for the recomputation policy as :func:`_glu`'s two are
    (``glu_wide``: a feed-forward unit's wide products)."""
    return relu2(checkpoint_name(m @ w1, "glu_wide")) @ w2


def _block_routed(x, p, arch: Arch, scope: str):
    """This chip's share of a routed expert layer
    (:func:`moe.moe_routed_ffn`); the norm and the residual sum lie
    under ``scope``, the layer's two parts under ``scope.route`` and
    ``scope.experts``, and the shared expert, which every chip computes
    alike for every token, under ``scope.shared``.  The experts, routed
    and shared, are of ``arch.expert_form``.  In a sandwich-normed stack
    the two parts' sum passes the layer's fourth norm (``ln2o``) before
    the residual sum."""
    gated = arch.expert_form == "glu"
    # behind a second norm (or a residual multiplier) the shared and the
    # routed experts' sum is ONE sub-layer's output; without either the
    # shared expert joins the stream first, as it always did
    joined = arch.sandwich or arch.residual_mult != 1.0
    shared = None
    with _probe.scope(scope):
        m = _norm(x, p, "ln2", arch)
    if "sw1" in p:
        with _probe.scope(f"{scope}.shared"):
            shared = _glu(m, p["sw1"], p["sw3"], p["sw2"]) if gated else \
                _relu2_mlp(m, p["sw1"], p["sw2"])
            if not joined:
                x = x + shared
    y, stats = moe_routed_ffn(
        m.reshape(-1, m.shape[-1]), p["gate"], p.get("ebias"), p["ew1"],
        p.get("ew3"), p["ew2"], first=arch.experts_first, top_k=arch.top_k,
        score=arch.score, norm_topk=arch.norm_topk,
        scale=arch.routed_scale, act=jax.nn.silu if gated else relu2,
        scope=scope)
    with _probe.scope(scope):
        y = y.reshape(m.shape)
        if joined:
            y = _sub_out(y if shared is None else y + shared, p, "ln2o",
                         arch)
        return x + y, jnp.zeros((), jnp.float32), stats
