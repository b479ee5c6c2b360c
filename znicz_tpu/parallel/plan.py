"""What a checkpointed layer keeps for the backward pass: the names it
always keeps (:func:`_loop_saves`), a step's footprint reckoned from static
shapes (:func:`step_footprint`) and, from the memory the device reports,
which optional kinds there is room to keep beside them
(:func:`checkpoint_plan`): functions of the architecture and integers.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
from jax.sharding import Mesh

from znicz_tpu.parallel.arch import Arch
from znicz_tpu.parallel.head import _n_chunks
from znicz_tpu.parallel.moe import compact_rows
from znicz_tpu.parallel.params import (_layer_shapes, _shape_leaves,
                                       param_shapes, ssm_in_width)

_log = logging.getLogger("znicz_tpu.transformer")


#: what a checkpointed layer keeps whatever the memory (:func:`_loop_saves`)
_KEPT_ALWAYS = ("attn_qkv", "sub_out", "ssm_y", "ssm_state", "ssm_conv",
                "moe_route", "moe_up", "kda_y", "kda_state")

#: what it keeps beside them where the device has room for all the layers'
#: (:func:`checkpoint_plan`), in the order of time saved a byte kept: a
#: feed-forward unit's wide products (a SwiGLU's two; of a shared expert
#: beside routed ones its form's: two, or a squared-ReLU unit's one), a
#: state-space layer's input projection and a delta-rule layer's (a product
#: made again costs about 12 ms a GiB of its result on a v5e)
_KEPT_IF_ROOM = ("glu_wide", "ssm_in", "kda_in")

#: bytes :func:`checkpoint_plan` leaves free beside the step's reckoned
#: footprint and what it keeps: what :func:`step_footprint` may stand under
#: the compiler's count by, and what else the process holds on the device
PLAN_MARGIN = 2 * 2 ** 30

_SAVED_NAMES = jax.checkpoint_policies.save_only_these_names(*_KEPT_ALWAYS)


def _loop_saves(prim, *_, **params) -> bool:
    """What a layer application of a looped stack keeps for the backward
    pass: the rotated queries, keys and values, each sub-layer's output
    (``attn_qkv``, ``sub_out``: the attention's output product and the
    SwiGLU's) and whatever a kernel wrote (the flash forward's output and
    log-sum-exp rows, so no kernel runs twice); with the layer's input
    that is seven arrays of ``(tokens, d)``.  Recomputed: the four norms,
    the rotary embedding's f32 chain, the residual sums and the SwiGLU's
    two wide products with their gated product (three arrays of
    ``(tokens, ff)``, 12 % of a layer's operations).  It is also the least a
    layer of a stack with state-space layers keeps (``transformer._block_fn``):
    of such a layer the scan's output and each chunk's opening state too
    (``ssm_y``, ``ssm_state``: the scan's forward pass, a Pallas kernel
    where its shape allows, is not run a second time; its backward pass makes
    a chunk's decay and score matrices again, ``parallel/ssm.py``) and, where
    the convolution's kernels run, their result (``ssm_conv``, the scan's
    ``x | B | C``), with the wide input projection and its split, the
    ``jax.numpy`` convolution, the gate and the gated norm made again (where
    the gate's kernels run, ``ops/pallas/ssm_gate.py``, neither made again
    nor kept: their backward kernel writes the gated rows once more): a
    layer holds five or six arrays of ``(tokens, d)``, the scan's operand
    and its chunk states where it would hold ``(tokens, 8.5 d)`` of them.
    Of a delta-rule linear-attention layer (``parallel/kda.py``) likewise the
    heads' output and each chunk's opening state in the compute dtype
    (``kda_y``, ``kda_state``) and, where the convolution's kernels run,
    their result (``q | k | v``); the L2 norms, the log-decays, ``beta``, the
    gate and the head norm are made again, and so is every step of the rule
    inside a chunk when the gradients are.
    Of a routed expert layer in such a stack it keeps the router's choice (``moe_route``: the weights, the sort and its
    inverse, the group sizes; small, and no sort runs twice) and the experts'
    up-projections' results (``moe_up``: a kernel's output that leaves the
    pairs stage's ``cond``, which ``pallas_call`` alone would not name); the
    norm, the gathers, the experts' cast and the shared expert are made
    again.  What such a stack keeps beside this list
    follows the memory: :func:`checkpoint_plan`, :func:`_saves`."""
    return prim.name == "pallas_call" or _SAVED_NAMES(prim, *_, **params)


@functools.lru_cache(maxsize=None)
def _saves(kept: tuple):
    """:func:`_loop_saves` with the names ``kept`` beside its own (one
    policy object a set of names, so a layer's trace is found again)."""
    if not kept:
        return _loop_saves
    named = jax.checkpoint_policies.save_only_these_names(
        *_KEPT_ALWAYS, *kept)

    def saves(prim, *_, **params) -> bool:
        return prim.name == "pallas_call" or named(prim, *_, **params)
    return saves


def _recomputes_by_policy(arch: Arch) -> bool:
    """Whether the stack's layers are checkpointed by :func:`_loop_saves`
    with no keyword asking: a looped stack, a stack with state-space
    or delta-rule layers (a carried state), a stack with window layers
    (``transformer._block_fn``).  A
    window on the scores does nothing under its own length, so such a stack
    is one for long rows: it keeps what :func:`_loop_saves` lists and, of
    the rest, what :func:`checkpoint_plan` finds room for in the device's
    memory."""
    return arch.loop_steps > 1 or bool({"mamba", "kda"} & set(arch.mixers)) \
        or arch.window_layers() > 0


def _n_params(arch: Arch) -> int:
    return sum(math.prod(s) for s in _shape_leaves(param_shapes(arch)))


def step_footprint(arch: Arch, tokens: int, itemsize: int,
                   loss_chunks: int | None = None) -> int:
    """Bytes a train step of ``arch`` holds on a device at its fullest,
    reckoned from static shapes for ``tokens`` local tokens a step and a
    compute dtype of ``itemsize`` bytes, with every layer checkpointed by
    :func:`_loop_saves` and nothing kept beside its list: what
    :func:`checkpoint_plan` takes off the device's memory before it keeps
    anything more.  The sum of

    - the float32 masters and their cast to the compute dtype;
    - the gradients that are whole while the layers' backward passes run:
      the head pass makes the head's (a tied embedding's) float32 gradient
      first and a looped stack carries its layers' through the scan in the
      compute dtype; every other leaf's update runs as its gradient lands;
    - what :func:`_loop_saves` keeps of every layer application (the
      layer's input, ``sub_out`` of each sub-layer, q, k, v and the kernel's
      output and rows of an attention layer (a gated output's gate is made
      again from the layer's input and its weight counts with the masters),
      ``ssm_y``, ``ssm_state`` and
      ``ssm_conv`` of a state-space layer, ``kda_y``, ``kda_state`` and the
      convolution's ``q | k | v`` of a delta-rule layer, ``moe_up`` of a
      routed one), and a looped stack's outputs;
    - one layer's backward pass at work: six arrays of its widest
      activation in the compute dtype (a SwiGLU's two products, their
      gated product and the three gradients), of a routed layer the held
      experts' cast and float32 gradients, of a delta-rule layer the
      operands and cotangents of the rule's kernels (twelve arrays of the
      heads' width, at another time than the layer's feed-forward), and of
      a looped stack the layer's kept arrays once more (cut from the scan's
      stack as copies);
    - the head pass: one chunk's float32 logits and their gradient in the
      compute dtype (a looped stack's passes are one call of ``loop_steps``
      times the chunks), and the gradient to the stack's output it leaves
      behind, float32 and its copy in the compute dtype, a loop step each
      (``head._ce_weighted`` makes it where it makes the logits).

    Held to the two compiled steps the benchmark rehearses
    (``tests/test_checkpoint_plan.py``, a described v5e's
    ``memory_analysis()``: arguments and temporaries): it may stand under
    neither by more than :data:`PLAN_MARGIN`."""
    d, loops = arch.d, arch.loop_steps
    act = tokens * itemsize
    weights = _n_params(arch) * (4 + itemsize)
    grads = arch.vocab * d * 4
    kept = working = 0
    for i in range(arch.n_layers):
        mixer, ffn = arch.kinds(i)
        # the layer's input and each sub-layer's output (``sub_out`` twice
        # of a layer of two)
        layer = (1 + (mixer != "none") + (ffn != "none")) * act * d
        wide = transient = alone = 0
        if mixer == "mamba":
            inner = arch.ssm_heads * arch.ssm_head_dim
            chunks = -(-tokens // arch.ssm_chunk)
            # the scan's output and the chunks' opening states, both in the
            # compute dtype: the scan's kernels write the states as their
            # products read them (``ops/pallas/ssd.py``), and of the
            # ``jax.numpy`` form's float32 ones the compiled step keeps the
            # cast alone; and the convolution's result, the scan's ``x | B |
            # C``, which its kernel writes (``ops/pallas/ssm_conv.py``; the
            # ``jax.numpy`` form's is made again, and then this counts high)
            layer += act * inner + \
                chunks * inner * arch.ssm_state * itemsize + \
                act * (inner + 2 * arch.ssm_groups * arch.ssm_state)
            wide = ssm_in_width(arch.ssm_heads, arch.ssm_head_dim,
                                arch.ssm_state, arch.ssm_groups)
        elif mixer == "kda":
            inner = arch.kda_heads * arch.kda_head_dim
            chunks = -(-tokens // arch.kda_chunk)
            # the heads' output, the chunks' opening states as the outputs'
            # products read them, and the convolution's result (its kernel
            # writes it; the ``jax.numpy`` form's is made again, and then
            # this counts high)
            layer += act * inner + \
                chunks * inner * arch.kda_head_dim * itemsize + \
                act * 3 * inner
            # at work, apart from the feed-forward's time at work: what the
            # rule's kernels read and write beside the kept arrays (the
            # float32 log-decays and their cotangent, dq, dk and dv: seven
            # arrays of the heads' width in the compute dtype), the three
            # joined as the cotangent of ``q | k | v`` (three) and one more
            # pair at the seams; ``q``, ``k`` and ``v`` are cut from the
            # kept convolution result and the rule's scores, inverse and
            # carry live in the kernels' VMEM.  Where the ``jax.numpy`` form
            # runs, its temporaries stand 2 GiB over this at the cell's shape
            alone = 12 * act * inner
        elif mixer in ("attention", "latent"):
            qo, kv = arch.heads * arch.head_dim, arch.kv_heads * arch.head_dim
            layer += act * (2 * qo + 2 * kv) + tokens * arch.heads * 4
            wide = qo
        elif mixer == "sconv":
            wide = 3 * d
        if ffn == "glu":
            wide = max(wide, arch.ff)
        elif ffn == "moe_routed" and _recomputes_by_policy(arch):
            # ``moe_up`` of the compact buffer's rows; at work, the held
            # experts' cast and their float32 gradients beside the rows
            rows = compact_rows(tokens * arch.top_k, arch.experts_held,
                                arch.n_experts)
            ups = 2 if arch.expert_form == "glu" else 1
            layer += rows * arch.moe_ff * ups * itemsize
            wide = max(wide, arch.shared_ff, -(-rows * arch.moe_ff // tokens))
            transient = arch.experts_held * (ups + 1) * d * arch.moe_ff * \
                (4 + itemsize)
        kept += layer
        working = max(working, alone, 6 * act * wide + transient +
                      (layer if loops > 1 else 0))
    if loops > 1:
        grads += itemsize * sum(
            math.prod(shape) for i in range(arch.n_layers)
            for shape in _layer_shapes(arch, i).values())
        kept = loops * (kept + act * d)
    chunk = -(-tokens // _n_chunks(loss_chunks))
    head = (chunk * arch.vocab + loops * tokens * d) * (4 + itemsize)
    return weights + grads + kept + working + head


def _kind_bytes(arch: Arch, tokens: int, itemsize: int) -> dict:
    """``{name: bytes}`` all the layers of ``arch`` hold of each optional
    kind of :data:`_KEPT_IF_ROOM` when a step of ``tokens`` local tokens
    keeps it: ``tokens x width x itemsize x layers that have it`` (a shared
    expert's wide products, two of a gated unit and one of a plain one,
    count with the SwiGLUs')."""
    glu = sum(f == "glu" for f in arch.ffns)
    mamba = sum(m == "mamba" for m in arch.mixers)
    kda = sum(m == "kda" for m in arch.mixers)
    shared = arch.shared_ff * arch.ffns.count("moe_routed")
    gated = arch.expert_form == "glu"
    return {
        "glu_wide": tokens * itemsize * (2 * arch.ff * glu +
                                         (1 + gated) * shared),
        "ssm_in": tokens * itemsize * mamba * ssm_in_width(
            arch.ssm_heads, arch.ssm_head_dim, arch.ssm_state,
            arch.ssm_groups),
        "kda_in": tokens * itemsize * kda * 3 * arch.kda_heads *
        arch.kda_head_dim}


def checkpoint_plan(arch: Arch, tokens: int, itemsize: int,
                    limit: int | None,
                    loss_chunks: int | None = None) -> dict:
    """``{name: bytes}`` of what the checkpointed layers of ``arch`` keep
    for the backward pass beside :func:`_loop_saves`'s list, for each kind
    of :data:`_KEPT_IF_ROOM` the stack has: the bytes all its layers hold
    of a kind that is kept, 0 for one that is refused.

    The kinds are walked in their fixed order of time saved a byte, and a
    kind is kept while its bytes (:func:`_kind_bytes`, from static shapes)
    fit what is left of ``limit``, the device's memory as its backend
    reports it, after :func:`step_footprint`, :data:`PLAN_MARGIN` and the
    kinds kept before it; the first kind that does not fit ends the walk,
    so a later, smaller one never takes the room an earlier one was
    refused.  Kept arrays are the forward pass's own, in its dtype: no
    value of the step changes, only what its backward pass makes again.

    Nothing is kept where no limit is reported (a CPU: its steps are the
    ones they were), and nothing by a LOOPED stack at any limit: what a
    layer application keeps there crosses the scan over the loop steps and
    is stacked, and the stacking costs what the recomputation does.  In
    ``ouro_train_pp8_t4096`` (PERF.md section 5; my chip run, PR 35)
    ``bitcast_dynamic-update-slice_fusion`` takes 53.2 ms a step for seven
    ``(8,192, 2,048)`` arrays an application: 2.2 ms an application for 235
    MB, where the SwiGLU's two wide products of 184 MB cost 2.3 ms to make
    again; and 24 applications of them are 4.12 GiB beside the 11.20 the
    compiled step counts.  Whoever takes the stacking away (a loop
    unrolled, a stack written in place) reopens this."""
    if not _recomputes_by_policy(arch):
        return {}
    sizes = {k: v for k, v in _kind_bytes(arch, tokens, itemsize).items()
             if v}
    plan = dict.fromkeys(sizes, 0)
    if limit is None or arch.loop_steps > 1:
        return plan
    room = limit - step_footprint(arch, tokens, itemsize, loss_chunks) - \
        PLAN_MARGIN
    for name in _KEPT_IF_ROOM:
        if name not in sizes:
            continue
        if sizes[name] > room:
            break
        plan[name] = sizes[name]
        room -= sizes[name]
    return plan


def _memory_limit(mesh: Mesh) -> int | None:
    """Bytes of device memory a step's programs may use, as the backend of
    the mesh's first device reports them (``memory_stats()["bytes_limit"]``:
    15.75 GiB of a v5e's 16), None where it reports none (a CPU, a chip
    that is described and not attached)."""
    try:
        stats = mesh.devices.flat[0].memory_stats()
    except Exception:  # noqa: BLE001 - a device without the call has none
        return None
    return int(stats["bytes_limit"]) if stats and stats.get("bytes_limit") \
        else None


@functools.lru_cache(maxsize=None)
def _report_plan(arch: Arch, tokens: int, itemsize: int,
                 limit: int | None, loss_chunks: int | None) -> tuple:
    """:func:`checkpoint_plan`'s kept names, and what it decided said once
    per step shape per process."""
    plan = checkpoint_plan(arch, tokens, itemsize, limit, loss_chunks)
    if plan:
        gib = 2.0 ** 30
        sizes = _kind_bytes(arch, tokens, itemsize)
        said = ", ".join(f"{name} {'kept' if got else 'refused'} "
                         f"({sizes[name] / gib:.3f} GiB)"
                         for name, got in plan.items())
        if arch.loop_steps > 1:
            why = "a looped stack stacks what it keeps"
        elif limit is None:
            why = "the device reports no memory limit"
        else:
            footprint = step_footprint(arch, tokens, itemsize, loss_chunks)
            why = (f"limit {limit / gib:.3f} GiB, footprint "
                   f"{footprint / gib:.3f}, margin {PLAN_MARGIN / gib:.3f}")
        _log.info("checkpointed layers at %d tokens keep beside their own "
                  "list: %s; %s", tokens, said, why)
    return tuple(name for name, got in plan.items() if got)
