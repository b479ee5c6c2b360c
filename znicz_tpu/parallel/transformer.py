"""The language model's train step, built: one ``shard_map``-ped program
over a ``(data, seq, model)`` mesh (batch over ``data``; time over ``seq``
by exact ring attention; heads and the MLP's width over ``model``) from
what the modules beside this one own: ``arch`` (the description),
``params``, ``blocks`` (the layers), ``head`` and ``plan``.  Here: the step
build's snapshot of config and mesh (:func:`_run_of`), the stacks and
forward passes, the three ``make_*`` builders, and :func:`step_choices`,
which says what a step's trace will choose by asking what the trace asks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from znicz_tpu.observe import probe as _probe
from znicz_tpu.parallel import dsa, kda, qcomm, ssm, zero
# ``arch_from_config`` and ``_FAMILIES`` are not called below: the
# benchmark's builders take the description's reader from the module they
# take ``make_train_step`` from
from znicz_tpu.parallel.arch import (Arch, _FAMILIES,  # noqa: F401
                                     _default_compute_dtype,
                                     arch_from_config, as_arch, routed_block)
from znicz_tpu.parallel.blocks import (_Run, _block, _rms_norm,
                                       flash_refusal, sconv_kernel_refusal)
from znicz_tpu.parallel.compat import quantized_psum, shard_map
from znicz_tpu.parallel.head import (_ce_from_hidden, _ce_weighted, _head_of,
                                     _n_chunks, _normalised,
                                     ce_grad_in_forward)
from znicz_tpu.parallel.moe import (MEAN_STATS, compact_rows,
                                    gmm_kernel_refusal)
from znicz_tpu.parallel.params import (_shape_leaves, _spec_leaves,
                                       param_shapes, param_specs,
                                       shard_params_specs)
from znicz_tpu.parallel.plan import (_memory_limit, _recomputes_by_policy,
                                     _report_plan, _saves, checkpoint_plan)


def _flash_eligible(mesh: Mesh, interpret: bool) -> bool:
    """Use the Pallas flash kernel when the seq axis is unsharded (the
    ring handles sharded time) on a TPU; per-shape limits are checked at
    trace time (ops.pallas.attention.unsupported_reason — a refusal is
    logged, ``blocks._report_flash_choice``).
    ``root.common.engine.flash_attention`` (default True) turns it off;
    ``interpret`` (the pallas_interpret flag, captured once at step-build
    time) forces it ON for the Pallas interpreter — but only on a
    SINGLETON mesh, because interpret mode needs ``check_vma=False``
    whose altered psum transposition is only harmless at axis size 1."""
    from znicz_tpu.core.config import root
    if not bool(root.common.engine.get("flash_attention", True)):
        return False
    if mesh.shape.get("seq", 1) != 1:
        return False
    if interpret:
        return all(s == 1 for s in mesh.shape.values())
    return jax.default_backend() == "tpu"


def _ring_flash_eligible(mesh: Mesh, interpret: bool) -> bool:
    """Flash-in-ring (ring_flash_attention) for a SHARDED seq axis: the
    kernel runs per ring step on (t_loc × t_loc) blocks and results
    merge by lse weight.  Same ``flash_attention`` flag; compiled TPU
    backends only — interpret mode must be opted into explicitly
    (``engine.ring_flash_interpret``, used by the parity tests; the
    vma checker those runs would trip is disabled by the
    parallel/compat.py shard_map shim)."""
    from znicz_tpu.core.config import root
    if not bool(root.common.engine.get("flash_attention", True)):
        return False
    if mesh.shape.get("seq", 1) == 1:
        return False
    if interpret:
        return bool(root.common.engine.get("ring_flash_interpret", False))
    return jax.default_backend() == "tpu"


def _check_tp(mesh: Mesh, arch: Arch,
              vocab_sharded: int | None = None) -> tuple:
    """-> local ``(heads, key/value heads)`` on this mesh, or a refusal.
    The layer kinds beyond the GPT-shaped block run where the ``seq``
    and ``model`` axes are 1, and refuse any other mesh by name."""
    tp_size = mesh.shape["model"]
    extra = arch.mechanisms()
    if extra and (tp_size != 1 or mesh.shape.get("seq", 1) != 1):
        raise ValueError(
            f"{', '.join(extra)}: no sharding over the seq or model axis "
            f"is written for these (mesh {dict(mesh.shape)}); run them on "
            f"a mesh whose seq and model axes are 1")
    if extra and vocab_sharded is not None:
        raise ValueError(f"head_sharded with {', '.join(extra)}")
    heads, d = arch.heads, arch.d
    if heads % tp_size or d % tp_size:
        raise ValueError(f"tp={tp_size} must divide heads={heads} "
                         f"and d={d}")
    # the MoE FFN shards the EXPERT dim, never ff; the dense FFN
    # Megatron-splits ff
    if "moe_dense" in arch.ffns:
        if arch.n_experts % tp_size:
            raise ValueError(f"n_experts={arch.n_experts} must divide by "
                             f"tp={tp_size}")
    elif arch.ff % tp_size:
        raise ValueError(f"tp={tp_size} must divide ff={arch.ff}")
    if vocab_sharded is not None and vocab_sharded % tp_size:
        raise ValueError(f"head_sharded needs vocab={vocab_sharded} "
                         f"divisible by tp={tp_size}")
    return heads // tp_size, arch.kv_heads // tp_size


def _cast_params(ps, arch: Arch, cdt):
    """The forward's view of the params in the compute dtype.  A routed
    layer's leaves stay in the master dtype: its router (``gate``,
    ``ebias``) makes a discrete choice, and :func:`moe.moe_routed_ffn`
    takes its product at the highest precision; its experts are cast
    where the layer's pairs stage uses them, on whichever side of its
    choice of buffer, and take their gradients from there in the master
    dtype (a cast out here would stand alone on both sides of that
    choice: 12 ms of the step, my chip run, PR 29).  The exit gate of a
    looped stack stays in the master dtype too, and a state-space layer's
    step-size bias, decay rates and skip (``ssm.F32_LEAVES``: they enter
    float32 chains), and a delta-rule layer's decay bias and rates
    (``kda.F32_LEAVES``).  A looped stack reads this one cast in every loop
    step."""
    out = jax.tree.map(lambda w: w.astype(cdt), ps)
    layers = list(zip(ps["blocks"], out["blocks"]))
    if arch.mtp:
        layers.append((ps["mtp"]["block"], out["mtp"]["block"]))
    for master, cast in layers:
        if routed_block(master):
            for k in ("gate", "ebias", "ew1", "ew3", "ew2"):
                if k in master:
                    cast[k] = master[k]
        if "ssm_a_log" in master:
            cast.update({k: master[k] for k in ssm.F32_LEAVES})
        if "kda_a_log" in master:
            cast.update({k: master[k] for k in kda.F32_LEAVES})
    if arch.exit_gate:                     # its product is taken in f32
        out.update({k: ps[k] for k in ("exit_w", "exit_b")})
    return out


#: prefixes of the stats that come in the loss's own convention (reduced
#: and scaled as the loss is): the loss's named terms (``loss_main``,
#: ``loss_mtp`` of a stack with an MTP module, ``loss_index`` of one with
#: an indexer) and a looped stack's means
#: over tokens (``loop_exit_step_mean``, ``loop_exit_entropy``,
#: ``loop_loss_step<r>``)
_TERM_PREFIXES = ("loss_", "loop_")


def _sum_stats(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in {**a, **b}}


def _block_fn(arch: Arch, kept: tuple = ()):
    """:func:`_block`, or its checkpointed form.  A looped stack holds
    ``loop_steps`` times the activations its weights suggest, and a layer of
    a stack with state-space layers has wide arrays of 8.5 ``d`` a token:
    both always recompute, the cheapest things first (``plan._loop_saves``),
    keeping the names ``kept`` beside the policy's own: what
    :func:`checkpoint_plan` found room for.  Any other stack recomputes
    nothing."""
    if not _recomputes_by_policy(arch):
        return _block
    return jax.checkpoint(_block, policy=_saves(kept),
                          static_argnums=(2, 3, 4))


def _stack(x, blocks, arch: Arch, run: _Run, blk):
    """One pass through the layers -> ``(x, aux_term, stats)``: the MoE
    regularizer term and the layers' counters, each summed over them."""
    # regularizer weights apply inside _block (per-block pre-weighted)
    aux_term = jnp.zeros((), jnp.float32)
    stats: dict = {}
    for i, p in enumerate(blocks):
        x, aux, st = blk(x, p, arch, run, i)
        aux_term = aux_term + aux
        stats = _sum_stats(stats, st)
    return x, aux_term, stats


def _looped(ps, x, arch: Arch, run: _Run, blk, each=None, carry=()):
    """The stack run ``arch.loop_steps`` times over the same weights:
    ``h_r = RMSNorm_f(Stack(h_{r-1}))``, the one final norm closing every
    loop step and its result fed into the next.  ONE body of ``n_layers``
    layers under ``lax.scan`` and not ``loop_steps`` copies of them: the
    program stays the size of the unlooped model's (and so its compile
    time), every loop step reads the one cast of the weights, and each
    weight's gradient is summed over its uses in the scan's carry.  The
    scopes are the same in every loop step, so a scope's time is the sum
    over them.  ``each(carry, h_r, r) -> (carry, out)`` reads a loop
    step's output as it is made (``r`` from 0, traced).
    -> ``(h_last, aux_term, stats, carry, outs stacked over the steps)``."""
    @jax.checkpoint            # its f32 chain is recomputed, not stacked
    def close(x, g):
        with _probe.scope("ce"):
            return _rms_norm(x, g, arch.eps)

    def body(state, r):
        x, carry = state
        x, aux, st = _stack(x, ps["blocks"], arch, run, blk)
        x = close(x, ps["norm_g"])
        out = None
        if each is not None:
            carry, out = each(carry, x, r)
        return (x, carry), (aux, st, out)

    (x, carry), (aux, stats, outs) = lax.scan(
        body, (x, carry), jnp.arange(arch.loop_steps))
    return x, aux.sum(), {k: v.sum(0) for k, v in stats.items()}, carry, outs


def _embedded(ps, tokens, arch: Arch, cdt):
    """-> ``(the params cast once for the step, the tokens' embeddings
    (b_l, t_l, d))``, times ``arch.embed_mult`` where that is not 1."""
    ps = _cast_params(ps, arch, cdt)
    with _probe.scope("embed"):
        x = ps["emb"][tokens]
        return ps, x if arch.embed_mult == 1.0 else x * arch.embed_mult


def _forward_hidden(ps, tokens, arch: Arch, run: _Run, cdt,
                    kept: tuple = ()):
    """Embedding + block stack — the ONE pre-head forward body, shared
    by the CE loss (:func:`_forward_ce`) and the full-pass logits oracle
    (:func:`make_logits_fn`, the generative serving plane's correctness
    anchor).  Returns ``(x, aux_term, ps_cast, stats)`` — the hidden
    states (through the final norm where the stack has one, and divided
    by ``arch.logits_div``; of a looped stack the last loop step's), the
    summed MoE regularizer term, the
    compute-dtype-cast params (so the caller's head matmul uses the same
    precision policy) and the routed layers' counters summed over the
    layers."""
    ps, x = _embedded(ps, tokens, arch, cdt)
    blk = _block_fn(arch, kept)
    if arch.loop_steps > 1:
        x, aux_term, stats, _, _ = _looped(ps, x, arch, run, blk)
        return x, aux_term, ps, stats
    x, aux_term, stats = _stack(x, ps["blocks"], arch, run, blk)
    if arch.final_norm:
        with _probe.scope("ce"):
            x = _rms_norm(x, ps["norm_g"], arch.eps)
    if arch.logits_div != 1.0:
        # the logits divided: the hidden state is, once, in front of the
        # head pass (exact where the divisor is a power of two)
        with _probe.scope("ce"):
            x = x * (1.0 / arch.logits_div)
    return x, aux_term, ps, stats


def _mean_stats(stats: dict, arch: Arch) -> dict:
    """The routed layers' summed counters with ``moe.MEAN_STATS`` turned
    into their mean over the routed layers (a state-space layer's readings
    stay sums beside their own count, ``ssm_layers``: each kind's mean is
    over its own layers, whatever else the stack holds)."""
    return {k: v / arch.routed_layers() if k in MEAN_STATS else v
            for k, v in stats.items()}


def _mtp_hidden(ps, x, nxt, arch: Arch, run: _Run, blk):
    """The MTP module (depth 1, DeepSeek-V3's form) over the main stack's
    output ``x`` (through its final norm) and the NEXT tokens ``nxt``
    (the main loss's labels): ``h' = [RMSNorm_e(Emb(nxt)) | RMSNorm_h(x)]
    proj``, one more layer (index ``n_layers``, its own weights), its own
    norm; the caller reads it against the model's head for the
    second-next token.  -> ``(hidden, aux, stats)``.  Scopes ``mtp.proj``
    and ``mtp.ce`` around the layer's own."""
    m = ps["mtp"]
    with _probe.scope("mtp.proj"):
        e = _rms_norm(ps["emb"][nxt], m["enorm_g"], arch.eps)
        h = _rms_norm(x, m["hnorm_g"], arch.eps)
        y = jnp.concatenate([e, h], axis=-1) @ m["proj"]
    y, aux, stats = blk(y, m["block"], arch, run, arch.n_layers)
    with _probe.scope("mtp.ce"):
        return _rms_norm(y, m["norm_g"], arch.eps), aux, stats


def _forward_ce(ps, tokens, labels, mask, arch: Arch, run: _Run, cdt,
                loss_chunks: int | None = None,
                head_sharded: bool = False,
                reduce: bool = True):
    """The ONE forward + CE-loss body (shared by the train step's loss_fn
    and the eval pass, so their numerics can never drift); -> ``(loss,
    stats)``, the routed layers' counters beside the loss.  ``mask`` is a
    per-row validity mask or None; masked rows (the loader's padded tail)
    contribute neither loss nor — through AD — gradients, the framework's
    padding contract (loader/base.py).  ``run.moe_aux_weight`` scales the
    MoE blocks' summed load-balance aux into the loss (local-mean
    convention, same psum as the CE term; PADDED rows do count toward
    the routing statistics — the aux is a regularizer, not a metric).

    ``reduce=False`` returns the LOCAL loss term whose exact
    ``psum(..., ("data", "seq"))`` equals the ``reduce=True`` value
    (the replicated normalizers — shard counts, the masked token total —
    still reduce exactly inside).  The quantized-collective train step
    uses it to differentiate a local loss and route the gradient
    reduction through the explicit quantized psum instead of AD's
    psum transpose.

    With an MTP module (``arch.mtp``) the loss is ``CE(main; next token)
    + mtp_weight * CE(module; second-next token)``, the second over the
    positions that have a second-next token, and ``stats`` carries both
    terms (``loss_main``, ``loss_mtp``, in the loss's own convention).
    With an indexer on the attention layers (``arch.index_top_k``) the loss
    is ``CE + L_I``, the alignment term summed over the layers with weight
    1, and ``stats`` carries ``loss_index``.  A looped stack's loss is
    :func:`_forward_loop_ce`'s."""
    kept = _report_plan(*_plan_of(arch, run, tokens.size, cdt, loss_chunks))
    if arch.loop_steps > 1:
        return _forward_loop_ce(ps, tokens, labels, mask, arch, run, cdt,
                                _block_fn(arch, kept), loss_chunks, reduce)
    x, aux_term, ps, stats = _forward_hidden(ps, tokens, arch, run, cdt, kept)
    head = _head_of(ps, arch)
    with _probe.scope("ce"):
        loss = _ce_from_hidden(x, head, labels, mask, aux_term, loss_chunks,
                               head_sharded, reduce)
    if arch.mtp:
        # position i reads token i+1 (its label) and predicts token i+2,
        # the next position's label; the last position has none
        y, aux, st = _mtp_hidden(ps, x, labels, arch, run,
                                 _block_fn(arch, kept))
        with _probe.scope("mtp.ce"):
            second = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
            mtp = _ce_from_hidden(y, head, second, mask, aux, loss_chunks,
                                  head_sharded, reduce, skip_last=True)
        stats = {**_sum_stats(stats, st), "loss_main": loss, "loss_mtp": mtp}
        loss = loss + arch.mtp_weight * mtp
    if arch.index_top_k:
        # summed over the layers and inside ``loss`` already (it came as
        # the regularizer term does); here as the loss's named term
        term = stats["loss_index"]
        stats["loss_index"] = lax.psum(term, ("data", "seq")) if reduce \
            else term
    return loss, _mean_stats(stats, arch)


def _forward_loop_ce(ps, tokens, labels, mask, arch: Arch, run: _Run, cdt,
                     blk, loss_chunks: int | None, reduce: bool):
    """Forward and loss of a looped stack with an exit gate (Ouro's
    LoopLM, arXiv:2510.25741), token by token over the ``R = loop_steps``
    outputs ``h_r`` of :func:`_looped`: ``g_r = h_r w_g + b_g``, ``lam_r =
    sigmoid(g_r)``, ``S_0 = 1``; for ``r < R``: ``p_r = lam_r S_{r-1}``,
    ``S_r = S_{r-1} (1 - lam_r)``; ``p_R = S_{R-1}`` (the last gate is
    read by nothing, the distribution sums to one).  ``L = mean over
    tokens of [sum_r p_r nll_r - beta H(p)]`` with ``nll_r`` the
    next-token cross-entropy of ``h_r`` against the one head and ``H(p) =
    -sum_r p_r log p_r``; gradients flow through ``p`` into the gate and
    the stack.  The gate's product, the sigmoids, the products ``S_r``,
    the entropy and the weighting are f32 (scope ``loop.exit``).  The
    ``R`` head passes (scope ``ce``) run after the loop as ONE call of
    :func:`_ce_weighted` over the stacked ``h_r`` with the weights ``p_r``
    (masked rows 0): differentiated, it makes ``dL/dh_r``, the head's
    gradient (one running sum over all ``R x loss_chunks`` chunks) and,
    through the weights, the gate's where it makes the logits, three
    products a chunk, and its per-token ``nll_r`` serve the counters
    (readings: no gradient passes through them).  ``loss_chunks`` need not
    divide a loop step's tokens any more: the call fills its last chunk
    with rows of weight 0.
    -> ``(loss, stats)``: ``loop_exit_step_mean`` (the mean of ``sum_r r
    p_r``, 1..R), ``loop_exit_entropy`` (of ``H(p)``, nats) and
    ``loop_loss_step<r>`` (each loop step's own mean cross-entropy), in
    the loss's convention, beside the layers' counters."""
    ps, x = _embedded(ps, tokens, arch, cdt)
    head = _head_of(ps, arch)
    steps, beta = arch.loop_steps, jnp.float32(arch.exit_beta)
    b_l, t_l = labels.shape
    counted = jnp.ones((b_l, 1), jnp.float32) if mask is None else \
        mask[:, None].astype(jnp.float32)

    @jax.checkpoint            # the f32 copy of h is recomputed, not stacked
    def gate(h, w, b):
        return jnp.einsum("btd,do->bt", h.astype(jnp.float32), w,
                          precision=lax.Precision.HIGHEST) + b[0]

    def exit_step(alive, h, r):           # alive: S_{r-1} (b, t)
        with _probe.scope("loop.exit"):
            lam = jnp.where(r == steps - 1, 1.0, jax.nn.sigmoid(
                gate(h, ps["exit_w"], ps["exit_b"])))
            # stacked as the rows the head passes read: stacked (b, t, d),
            # XLA lays the stack, and with it the whole loop's residual
            # stream, out time-minor for the gate's reduction
            return alive * (1.0 - lam), (h.reshape(-1, h.shape[-1]),
                                         lam * alive)

    _, aux_term, stats, _, (hs, p) = _looped(
        ps, x, arch, run, blk, exit_step, jnp.ones((b_l, t_l), jnp.float32))
    n_tok = steps * b_l * t_l
    with _probe.scope("ce"):
        # inside the loop the scan would stack each pass's residuals (the
        # head's gradient among them, 201 MB a loop step at 49,152 ids)
        term, nll = _ce_weighted(
            hs.reshape(n_tok, -1), head, jnp.tile(labels.reshape(-1), steps),
            (p * counted).reshape(n_tok), steps * _n_chunks(loss_chunks))
        nll = nll.reshape(p.shape)
    with _probe.scope("loop.exit"):
        # 0 at p = 0, and a finite gradient there
        plogp = p * jnp.log(jnp.maximum(p, 1e-30))
        total = term + beta * (plogp * counted).sum()
        sums = lax.stop_gradient(jnp.stack(
            [(a * counted).sum((1, 2)) for a in (nll, p, plogp)], axis=1))

        def mean(s):
            return _normalised(s, mask, b_l, t_l, 0.0, reduce)

        loss = _normalised(total, mask, b_l, t_l, aux_term, reduce)
        stats["loop_exit_step_mean"] = mean(
            (sums[:, 1] * jnp.arange(1, steps + 1, dtype=jnp.float32)).sum())
        stats["loop_exit_entropy"] = mean(-sums[:, 2].sum())
        for r in range(steps):
            stats[f"loop_loss_step{r + 1}"] = mean(sums[r, 0])
    return loss, stats


def _run_of(mesh: Mesh, arch: Arch, vocab_sharded: int | None = None,
            moe_aux_weight: float = 0.0,
            moe_zloss_weight: float = 0.0) -> _Run:
    """The step build's snapshot: local head counts on this mesh (or a
    refusal) and the attention core the config, the mesh and the stack
    allow: a stack without an attention layer has no flash kernel to try,
    so ``use_flash`` alone says "has an attention layer and may try one"
    to the trace and to :func:`step_choices`."""
    heads_local, kv_local = _check_tp(mesh, arch, vocab_sharded)
    from znicz_tpu.core.config import root as root_cfg
    interp = bool(root_cfg.common.engine.get("pallas_interpret", False))
    attends = bool({"attention", "latent"} & set(arch.mixers))
    return _Run(heads_local, kv_local,
                use_flash=attends and _flash_eligible(mesh, interp),
                interpret=interp,
                use_ring_flash=_ring_flash_eligible(mesh, interp),
                moe_aux_weight=float(moe_aux_weight),
                moe_zloss_weight=float(moe_zloss_weight),
                hbm_limit=_memory_limit(mesh))


def _plan_of(arch: Arch, run: _Run, tokens: int, cdt,
             loss_chunks: int | None) -> tuple:
    """:func:`checkpoint_plan`'s arguments for ``tokens`` LOCAL tokens in
    the dtype ``cdt``, for the trace and for :func:`step_choices` alike."""
    return arch, tokens, jnp.dtype(cdt).itemsize, run.hbm_limit, loss_chunks


def step_choices(mesh: Mesh, arch: Arch, batch: int, t: int,
                 loss_chunks: int | None = None,
                 head_sharded: bool = False) -> dict:
    """What the trace of a train step of ``batch`` rows of ``t`` positions
    on ``mesh`` will choose from its shapes, known before it is traced: the
    pure deciders the trace asks, behind the same :func:`_run_of`.  Keyed as
    the unit's attributes are (``units/lm.py``):

    - ``ce_grad_in_forward_share``: 1.0 where the head passes make their
      gradients where they make their logits (``head.ce_grad_in_forward``);
    - ``attn_kvb_block_rows``: ``{pass: rows}`` of the key/value-blocked
      flash kernels' tiles (``attention.kvb_block_rows``), 0 in every pass
      where the attention layers run another form or no flash kernel
      (``blocks.flash_refusal``) or there are none;
    - ``checkpoint_kept_bytes``: ``plan.checkpoint_plan`` by the memory the
      mesh's first device reports (0: refused; empty: no layer is
      checkpointed by that policy);
    - ``dsa_index_kernel_share`` / ``dsa_align_kernel_share``: of the layers
      with an indexer, the share whose index scores with their gradients,
      and whose alignment target, the kernels of ``ops/pallas/dsa.py`` make
      (all or none: ``dsa.index_kernel_refusal``,
      ``dsa.align_kernel_refusal``); None without an indexer;
    - ``moe_gmm_kernel_share``: of the routed expert layers, the share whose
      grouped products the kernels of ``ops/pallas/grouped.py`` make (all or
      none, in the compact and the full pairs buffer alike:
      ``moe.gmm_kernel_refusal``; the rest ``lax.ragged_dot``); None
      without a routed layer;
    - ``ssm_scan_kernel_share``: of the state-space layers, the share whose
      scan the kernels of ``ops/pallas/ssd.py`` run (all or none:
      ``ssm.scan_kernel_refusal``; the rest ``ssm.py``'s ``jax.numpy``
      form); None without a state-space layer;
    - ``ssm_conv_kernel_share``: of the state-space layers, the share whose
      convolution, bias and ``silu`` the kernels of ``ops/pallas/
      ssm_conv.py`` run (all or none: ``ssm.conv_kernel_refusal``; the rest
      ``ssm._conv``); None without a state-space layer;
    - ``ssm_gate_kernel_share``: of the state-space layers, the share whose
      gate, gated group norm and output product the kernels of ``ops/pallas/
      ssm_gate.py`` run (all or none: ``ssm.gate_kernel_refusal``, which
      also refuses ONE group; the rest the closing lines of ``ssm.mixer``);
      None without a state-space layer;
    - ``kda_conv_kernel_share``: of the delta-rule linear-attention layers,
      the share whose three convolutions and ``silu`` those same kernels run
      on the ``q | k | v`` projection's lanes (all or none:
      ``ssm.conv_kernel_refusal``; the rest ``ssm._conv``); None without
      such a layer;
    - ``kda_delta_kernel_share``: of the delta-rule layers, the share whose
      rule (scores, unit-triangular inverse, carry and outputs, forward and
      backward) the kernels of ``ops/pallas/kda_delta.py`` run (all or none:
      ``kda.delta_kernel_refusal``; the rest ``kda.py``'s ``jax.numpy``
      form); None without such a layer;
    - ``sconv_kernel_share``: of the gated short convolutions, the share
      whose gates and taps (``C * conv(B * X)`` between the layer's two
      products, and backward the projection's whole cotangent) the kernels
      of ``ops/pallas/sconv.py`` run, keeping the projection and the taps
      alone (all or none: ``blocks.sconv_kernel_refusal``; the rest
      ``blocks._sconv_gate``, the ``jax.numpy`` form, which keeps the three
      cuts and the sum); None without such a layer."""
    from znicz_tpu.ops.pallas import attention as pattn
    run = _run_of(mesh, arch, arch.vocab if head_sharded else None)
    b_loc = batch // mesh.shape.get("data", 1)
    t_loc = t // mesh.shape.get("seq", 1)
    indexed = bool(arch.index_top_k)
    rows = pattn.kvb_block_rows(t_loc, arch.head_dim, indexed)
    if not run.use_flash or flash_refusal(t_loc, arch.head_dim, run, indexed):
        rows = dict.fromkeys(rows, 0)
    index = align = None
    if indexed:
        index = float(dsa.index_kernel_refusal(
            t_loc, arch.index_heads, arch.index_dim, run.interpret) is None)
        align = float(dsa.align_kernel_refusal(
            t_loc, run.heads_local, run.kv_heads_local, arch.head_dim,
            run.interpret) is None)
    gmm = None
    if arch.routed_layers():
        pairs = b_loc * t_loc * arch.top_k
        gmm = float(not any(gmm_kernel_refusal(
            r, arch.d, arch.moe_ff, arch.experts_held,
            _default_compute_dtype(), run.interpret)
            for r in {pairs, compact_rows(pairs, arch.experts_held,
                                          arch.n_experts)}))
    scan = conv = gate = None
    if "mamba" in arch.mixers:
        inner = arch.ssm_heads * arch.ssm_head_dim
        itemsize = jnp.dtype(_default_compute_dtype()).itemsize
        conv = float(ssm.conv_kernel_refusal(
            t_loc, inner, inner + 2 * arch.ssm_groups * arch.ssm_state,
            arch.conv_taps, run.interpret) is None)
        scan = float(ssm.scan_kernel_refusal(
            t_loc, arch.ssm_heads, arch.ssm_head_dim, arch.ssm_state,
            arch.ssm_groups, arch.ssm_chunk, itemsize,
            run.interpret) is None)
        gate = float(ssm.gate_kernel_refusal(
            t_loc, inner, arch.ssm_groups, 0, itemsize,
            run.interpret) is None)
    kda_conv = kda_delta = None
    if "kda" in arch.mixers:
        kda_conv = float(ssm.conv_kernel_refusal(
            t_loc, 0, 3 * arch.kda_heads * arch.kda_head_dim,
            arch.conv_taps, run.interpret) is None)
        kda_delta = float(kda.delta_kernel_refusal(
            t_loc, arch.kda_heads, arch.kda_head_dim, arch.kda_chunk,
            jnp.dtype(_default_compute_dtype()).itemsize,
            run.interpret) is None)
    sconv = None
    if "sconv" in arch.mixers:
        sconv = float(sconv_kernel_refusal(
            t_loc, arch.d, arch.conv_taps, False, run.interpret) is None)
    return {
        "ce_grad_in_forward_share": float(ce_grad_in_forward(
            loss_chunks, head_sharded, arch.loop_steps > 1)),
        "attn_kvb_block_rows": rows,
        "checkpoint_kept_bytes": checkpoint_plan(*_plan_of(
            arch, run, b_loc * t_loc, _default_compute_dtype(), loss_chunks)),
        "dsa_index_kernel_share": index, "dsa_align_kernel_share": align,
        "moe_gmm_kernel_share": gmm, "ssm_scan_kernel_share": scan,
        "ssm_conv_kernel_share": conv, "ssm_gate_kernel_share": gate,
        "kda_conv_kernel_share": kda_conv,
        "kda_delta_kernel_share": kda_delta, "sconv_kernel_share": sconv}


def make_train_step(mesh: Mesh, arch, d=None, heads=None, ff=None,
                    vocab=None, lr: float = 0.1,
                    compute_dtype=None, shard_update: bool = False,
                    shard_params: bool = False,
                    masked: bool = False, donate: bool = False,
                    loss_chunks: int | None = None,
                    head_sharded: bool = False,
                    n_experts: int | None = None,
                    moe_aux_weight: float = 0.0,
                    moe_top_k: int = 1,
                    moe_zloss_weight: float = 0.0,
                    quantized_collectives: dict | None = None,
                    stats: bool = False):
    """-> ``(jitted step(params, tokens, labels) -> (params, loss), the
    params' specs)``.  ``tokens`` / ``labels``: int32 ``(batch, time)``,
    batch sharded over ``data`` and time over ``seq``.  ``arch`` says what
    the stack is (:func:`as_arch`): an :class:`Arch`, a model's
    configuration mapping, or the GPT-shaped block's ``n_layers`` followed
    by ``d, heads, ff, vocab`` (with ``n_experts`` experts sharded over
    ``model`` in place of each dense FFN, ``moe_top_k`` of them a token).
    Attention is causal.  Master params and the SGD update stay f32, the
    forward runs in ``compute_dtype`` (:func:`_default_compute_dtype`), the
    loss in f32.  What a layer recomputes follows the architecture and the
    device's memory (:func:`_block_fn`), not a keyword.

    - ``masked``: ``step(params, tokens, labels, mask)``; rows whose mask
      is false (the loader's padded tail) train nothing.
    - ``stats``: ``-> (params, loss, stats)``, float32 scalars: the routed
      layers' counters, ``attn_flash`` / ``attn_direct``, a window layer's
      ``attn_window`` / ``attn_window_tiles`` / ``attn_causal_tiles``, the
      loss's named terms and a looped stack's ``loop_*`` (:func:`_forward_loop_ce`).
    - ``donate``: the params' buffers are the step's to overwrite.
    - ``loss_chunks=k``: the head pass ``k`` chunks of tokens at a time
      (``head._ce_weighted``): the ``(tokens, vocab)`` logits never exist
      whole, nothing is recomputed, only the summation order differs.
    - ``head_sharded``: the head vocab-sharded over ``model``, Megatron's
      parallel cross-entropy (``head._vshard_chunk_nll``; ``vocab % tp ==
      0``); composes with ``loss_chunks``.
    - ``moe_aux_weight`` / ``moe_zloss_weight``: the dense-masked MoE's
      load-balance term (arXiv:2101.03961 eq. 4) and router z-loss
      (arXiv:2202.08906 eq. 5) in the TRAINING loss; eval stays pure CE.
    - ``shard_update``: each ``data`` replica updates a 1/n slice of every
      replicated leaf and a psum reassembles them (arXiv:2004.13336): the
      step is stateless SGD, so this divides compute, not memory, and pins
      the numerics of the fused step's stateful form (``parallel/step.py``).
    - ``shard_params``: the replicated leaves PERSIST flat-sharded over
      ``data`` (``params.shard_params_host`` in, ``unshard_params_host``
      out, specs ``shard_params_specs``), gathered ahead of each forward
      (``zero.gather_chain``), updated on the local slice.  Subsumes and
      refuses ``shard_update``.
    - ``quantized_collectives`` (None: ``engine.quantized_collectives``):
      the loss is differentiated LOCALLY and every gradient, and the
      ``shard_params`` regather, goes through one quantized psum
      (``parallel/qcomm.py``); the reported loss reduces exactly.  The
      exact path gives each batch shard's replica its OWN gradient (AD's
      transpose of the reduced loss), the explicit psum the batch mean:
      the two track each other within a band (``ROADMAP.md`` Queue 3 item
      2).  No error feedback: the step carries no state.  Mode off builds
      the exact program.
    """
    if shard_params and shard_update:
        raise ValueError(
            "shard_params subsumes shard_update (replicated leaves "
            "persist sharded and update in place — there is no "
            "regather left to split); pass only one")
    arch = as_arch(arch, d, heads, ff, vocab, n_experts, moe_top_k)
    run = _run_of(mesh, arch, arch.vocab if head_sharded else None,
                  moe_aux_weight, moe_zloss_weight)
    specs = param_specs(arch, head_sharded)
    cdt = _default_compute_dtype(compute_dtype)
    from znicz_tpu.core.config import root as root_cfg
    if run.use_ring_flash and run.interpret:
        # eval-only mode: interpret-Pallas needs check_vma=False at
        # seq>1, which corrupts replicated-param gradient reduction
        # (docs/TUNING.md "Ring×flash" §3) — refuse to build a silently
        # wrong TRAINING step
        raise ValueError(
            "engine.ring_flash_interpret is eval-only (forward parity "
            "tests): a train step under the relaxed vma checker gets "
            "corrupted replicated-param gradients at seq>1. Train with "
            "engine.flash_attention=False (dense ring) in interpret "
            "mode, or run compiled on TPU.")
    n_data = mesh.shape["data"]
    shapes = param_shapes(arch)
    step_specs = shard_params_specs(specs) if shard_params else specs
    via_psum = bool(root_cfg.common.engine.get("zero_gather_via_psum",
                                               False))
    codec = qcomm.resolve(quantized_collectives)

    def _sharded_sgd(w, g, scale):
        """w - lr*g/scale computed on this replica's 1/n slice only,
        reassembled via a (provably replicating) psum."""
        rank = lax.axis_index("data")
        new_sh = zero.pad_slice(w, rank, n_data) - \
            lr * zero.pad_slice(g, rank, n_data) / scale
        return zero.psum_regather(new_sh, rank, n_data, "data", w)

    def local_step(params, tokens, labels, mask=None):
        if shard_params:
            # materialize full replicated leaves from the flat shards —
            # the on-demand regather chain, OUTSIDE the differentiated
            # function so grads reduce through the same AD-inserted
            # psum as the replicated path (bit-parity; AD through the
            # gather would transpose to a reduce-scatter instead)
            rank = lax.axis_index("data")
            flat_p, treedef = jax.tree.flatten(params)
            flat_s = _spec_leaves(specs)
            flat_shapes = _shape_leaves(shapes)
            idx = [i for i, s in enumerate(flat_s) if s == P()]
            gathered = zero.gather_chain(
                [flat_p[i] for i in idx],
                [jax.ShapeDtypeStruct(flat_shapes[i], flat_p[i].dtype)
                 for i in idx],
                rank, n_data, "data", via_psum=via_psum, codec=codec)
            flat_full = list(flat_p)
            for i, g in zip(idx, gathered):
                flat_full[i] = g
            full_params = jax.tree.unflatten(treedef, flat_full)
        else:
            full_params = params

        def loss_fn(ps):
            return _forward_ce(ps, tokens, labels, mask, arch, run, cdt,
                               loss_chunks=loss_chunks,
                               head_sharded=head_sharded,
                               reduce=codec is None)

        (loss, counters), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(full_params)
        if codec is not None:
            # quantized mode differentiates the LOCAL loss and reduces
            # every grad leaf (replicated AND tensor-sharded — both need
            # the data x seq sum) through the quantized-psum seam; the
            # reported loss scalar reduces exactly (telemetry never
            # quantizes)
            with _probe.scope("grad_reduce"):
                grads, _ = quantized_psum(grads, ("data", "seq"), codec)
            loss = lax.psum(loss, ("data", "seq"))
        n_shards = lax.psum(1, "data") * lax.psum(1, "seq")
        with _probe.scope("update"):
            if shard_params:
                # each replica updates ONLY its slice (grad sliced to
                # match) and keeps it — no regather; tensor-sharded
                # leaves update locally as before
                flat_g = jax.tree.leaves(grads)
                new_leaves = [
                    flat_p[i] -
                    lr * zero.pad_slice(flat_g[i], rank, n_data) / n_shards
                    if flat_s[i] == P()
                    else flat_full[i] - lr * flat_g[i] / n_shards
                    for i in range(len(flat_p))]
                new_params = jax.tree.unflatten(treedef, new_leaves)
            elif shard_update:
                # PartitionSpec is a tuple subclass (a pytree container),
                # so align specs to params by flattening with an is_leaf
                # guard
                flat_w, treedef = jax.tree.flatten(params)
                flat_g = jax.tree.leaves(grads)
                flat_s = _spec_leaves(specs)
                new_leaves = [
                    _sharded_sgd(w, g, n_shards) if s == P()
                    else w - lr * g / n_shards
                    for w, g, s in zip(flat_w, flat_g, flat_s)]
                new_params = jax.tree.unflatten(treedef, new_leaves)
            else:
                new_params = jax.tree.map(
                    lambda w, g: w - lr * g / n_shards, params, grads)
        if not stats:
            return new_params, loss / n_shards
        # the counters are of this shard's tokens: pairs add up over the
        # shards, a load ratio and a share are averaged; the loss's terms
        # are in the loss's convention, reduced as it is
        terms = {k: counters.pop(k) for k in list(counters)
                 if k.startswith(_TERM_PREFIXES)}
        if codec is not None:
            terms = {k: lax.psum(v, ("data", "seq"))
                     for k, v in terms.items()}
        counters = {k: lax.psum(v, ("data", "seq")) /
                    (n_shards if k in MEAN_STATS else 1)
                    for k, v in counters.items()}
        counters.update({k: v / n_shards for k, v in terms.items()})
        return new_params, loss / n_shards, counters

    # replication checking is disabled wholesale by the compat shim
    # (parallel/compat.py) — it false-positives on these psum-composed
    # updates (and cannot infer replication through the shard_params
    # all_gather); _flash_eligible still only allows interpret-flash on
    # a SINGLETON mesh, where the relaxed psum transposition is exact.
    batch_spec = P("data", "seq")
    in_specs = (step_specs, batch_spec, batch_spec) + \
        ((P("data"),) if masked else ())
    step = shard_map(
        local_step, mesh=mesh, in_specs=in_specs,
        out_specs=(step_specs, P()) + ((P(),) if stats else ()))
    return jax.jit(step, donate_argnums=(0,) if donate else ()), \
        step_specs


def make_eval_loss(mesh: Mesh, arch, d=None, heads=None, ff=None,
                   vocab=None, compute_dtype=None,
                   masked: bool = False, loss_chunks: int | None = None,
                   head_sharded: bool = False,
                   n_experts: int | None = None,
                   moe_top_k: int = 1):
    """-> jitted ``eval_loss(params, tokens, labels[, mask]) -> loss`` —
    the train step's forward + CE loss (the SHARED ``_forward_ce`` body,
    so the numerics cannot drift) with no update: validation/test
    passes."""
    arch = as_arch(arch, d, heads, ff, vocab, n_experts, moe_top_k)
    run = _run_of(mesh, arch, arch.vocab if head_sharded else None)
    specs = param_specs(arch, head_sharded)
    cdt = _default_compute_dtype(compute_dtype)

    def local_eval(params, tokens, labels, mask=None):
        n_shards = lax.psum(1, "data") * lax.psum(1, "seq")
        return _forward_ce(params, tokens, labels, mask, arch, run, cdt,
                           loss_chunks=loss_chunks,
                           head_sharded=head_sharded)[0] / n_shards

    batch_spec = P("data", "seq")
    in_specs = (specs, batch_spec, batch_spec) + \
        ((P("data"),) if masked else ())
    fn = shard_map(local_eval, mesh=mesh, in_specs=in_specs,
                   out_specs=P())
    return jax.jit(fn)


def make_logits_fn(mesh: Mesh, arch, d=None, heads=None, ff=None,
                   vocab=None, compute_dtype=None,
                   n_experts: int | None = None, moe_top_k: int = 1):
    """-> jitted ``logits(params, tokens) -> (b, t, vocab)`` f32 — the
    full forward pass through the SAME ``_forward_hidden`` body the
    train/eval steps use, with the LM head applied per position instead
    of the CE reduction.  This is the generative serving plane's
    correctness oracle: ``serve/kvcache.py`` pins greedy KV-cache
    incremental decode against exactly this function (ISSUE 10), so any
    drift between training numerics and the decode path fails a test
    instead of degrading generations silently.

    The head must be replicated (``head_sharded`` has no logits form —
    the vocab-sharded CE never materializes full-vocab rows by design);
    callers wanting Megatron CE keep using :func:`make_eval_loss`."""
    arch = as_arch(arch, d, heads, ff, vocab, n_experts, moe_top_k)
    run = _run_of(mesh, arch)
    cdt = _default_compute_dtype(compute_dtype)

    def local_logits(params, tokens):
        x, _aux, ps, _stats = _forward_hidden(params, tokens, arch, run, cdt)
        return (x @ _head_of(ps, arch)).astype(jnp.float32)

    specs = param_specs(arch, False)
    batch_spec = P("data", "seq")
    fn = shard_map(local_logits, mesh=mesh,
                   in_specs=(specs, batch_spec),
                   out_specs=batch_spec)
    return jax.jit(fn)

