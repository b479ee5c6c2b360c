"""Long-context sharded transformer — the flagship multi-axis SPMD model
(TPU-native extension; the task treats long-context + distributed as
first-class even though the reference predates transformers, SURVEY.md
§6.7).

One ``shard_map``-ped training step over a ``(data, seq, model)`` mesh:

- batch sharded over ``data`` (DP) — gradients reduce via the loss psum;
- sequence sharded over ``seq`` (SP) — exact ring attention rotates K/V
  blocks over ICI (znicz_tpu.parallel.ring_attention);
- attention heads + MLP hidden sharded over ``model`` (TP) — Megatron
  column/row pattern, one psum per block half (znicz_tpu.parallel.tp).

``make_pipeline_step`` provides the complementary ``(data, pipe, expert)``
configuration: GPipe microbatching over ``pipe`` with expert-parallel MoE
blocks over ``expert`` (znicz_tpu.parallel.{pipeline,moe}).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from collections.abc import Mapping

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from znicz_tpu.parallel.compat import quantized_psum, shard_map

from znicz_tpu.observe import probe as _probe
from znicz_tpu.parallel import dsa, qcomm, ssm
from znicz_tpu.parallel.moe import (MEAN_STATS, load_balance_aux, moe_ffn,
                                    moe_routed_ffn, router_z_loss)
from znicz_tpu.parallel.pipeline import pipeline_apply
from znicz_tpu.parallel.ring_attention import (ring_attention,
                                               ring_flash_attention)
from znicz_tpu.parallel import tp, zero


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


def _flash_eligible(mesh: Mesh, interpret: bool) -> bool:
    """Use the Pallas flash kernel when the seq axis is unsharded (the
    ring handles sharded time) on a TPU; per-shape limits are checked at
    trace time (ops.pallas.attention.unsupported_reason — a refusal is
    logged, :func:`_report_flash_choice`).
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


def _default_compute_dtype(compute_dtype=None):
    """Explicit dtype wins; None defers to the framework-wide precision
    policy (core.backends.resolve_compute_dtype) for this process's
    default backend.  (Named differently from the backends policy on
    purpose — its first argument is a dtype, not a platform string.)"""
    if compute_dtype is not None:
        return compute_dtype
    from znicz_tpu.core.backends import resolve_compute_dtype as policy
    return policy(jax.default_backend())


# -- the architecture ---------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Arch:
    """One decoder stack, written once: what each layer mixes with, what
    it feeds forward through, and the sizes.  Every function below reads
    this; nothing else says what a block is.

    ``mixers[i]`` is ``"attention"``, ``"latent"`` (latent attention,
    MLA: queries and keys/values through low-rank latents of ``q_lora``
    and ``kv_lora`` with an RMSNorm on each, a head of ``nope_dim``
    unrotated and ``rope_dim`` rotated entries whose rotated key is one
    for all heads; ``head_dim`` is their sum and the value's width) or
    ``"sconv"`` (a gated short convolution); ``ffns[i]`` is ``"mlp"``
    (biased GELU), ``"moe_dense"`` (:func:`moe.moe_ffn`: softmax scores,
    biased GELU experts sharded over ``model``, every held expert
    computes every token), ``"glu"`` (bias-free SwiGLU) or
    ``"moe_routed"`` (:func:`moe.moe_routed_ffn`: this chip's
    ``experts_held`` of ``n_experts`` from ``experts_first``, token
    dispatch, no drop; with ``shared_ff`` a SwiGLU of that width that
    every token passes, beside it).  ``norm`` is ``"layer"`` (gain and
    bias) or ``"rms"`` (gain); ``kv_heads < heads`` is grouped-query
    attention; ``qk_norm`` puts an RMSNorm with its own gain on each head
    of q and k; ``rope_theta`` rotates them (rotate-half, over the whole
    head; ``rope_interleaved``: the pairs are neighbours, ``(2i, 2i +
    1)``); ``final_norm`` norms the last residual stream and ``tied``
    reads the logits against the embedding matrix.  ``mtp`` adds one
    multi-token-prediction module behind the stack (:func:`_mtp_hidden`:
    a projection of the next token's embedding beside the last state,
    one more layer of the last layer's kinds, index ``n_layers``, a norm
    of its own, the model's embedding and head) whose cross-entropy on
    the second-next token joins the loss ``mtp_weight`` times.
    ``sandwich`` puts a second norm with its own gain on each sub-layer's
    OUTPUT, before the residual sum (written for the attention and SwiGLU
    sub-layers).  ``loop_steps`` runs the whole stack that many times over
    the same weights, the final norm closing every loop step and its
    result fed back into layer 0 (:func:`_looped`); a looped stack has an
    exit gate (``exit_gate``): a biased ``d -> 1`` reads every loop step's
    output, the gates make a distribution over the loop steps token by
    token and the loss is the steps' cross-entropies weighted by it, less
    ``exit_beta`` times its entropy (:func:`_forward_loop_ce`).
    ``index_top_k`` puts an indexer on every attention layer (learned
    sparse attention, DeepSeek-V3.2-Exp's: ``index_heads`` index query
    heads of ``index_dim`` on one index key head read a DETACHED copy of
    the layer's normed input; a query attends to the ``index_top_k`` keys
    of largest index score, one set for all heads; the alignment term,
    the KL from the heads' mean attention probabilities to the softmax of
    the index scores over the selection, joins the loss summed over the
    layers and trains the indexer alone: ``parallel/dsa.py``).
    A ``"mamba"`` mixer is a state-space layer (Mamba-2, ``parallel/
    ssm.py``): ``ssm_heads`` heads of ``ssm_head_dim`` with a state of
    ``ssm_state`` entries a head entry, one group, a depthwise convolution
    of ``conv_taps`` taps with a bias, scanned in chunks of ``ssm_chunk``
    positions (a tile: it changes no value).  Four static multipliers (muP's,
    as the Granite families write them; each emits nothing at its default):
    ``embed_mult`` on the embeddings entering layer 0, ``residual_mult`` on
    every sub-layer's output before the residual sum, ``attn_mult`` the
    attention scores' scale where it is not ``1 / sqrt(head_dim)`` (the
    kernels keep their own scale; q takes ``attn_mult * sqrt(head_dim)``),
    ``logits_div`` dividing the logits (the hidden state in front of the
    head pass takes ``1 / logits_div``).

    Built by :func:`gpt_arch` (the block this module always had: the
    four integers) or :func:`arch_from_config` (a model's own keys)."""

    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    mixers: tuple
    ffns: tuple
    norm: str = "layer"
    eps: float = 1e-5
    qk_norm: bool = False
    rope_theta: float | None = None
    conv_taps: int = 0
    n_experts: int = 0
    experts_first: int = 0
    experts_held: int = 0
    top_k: int = 1
    moe_ff: int = 0
    score: str = "softmax"
    expert_bias: bool = False
    norm_topk: bool = True
    routed_scale: float = 1.0
    final_norm: bool = False
    tied: bool = False
    q_lora: int = 0
    kv_lora: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    rope_interleaved: bool = False
    shared_ff: int = 0
    mtp: bool = False
    mtp_weight: float = 0.0
    sandwich: bool = False
    loop_steps: int = 1
    exit_beta: float = 0.0
    index_heads: int = 0
    index_dim: int = 0
    index_top_k: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_chunk: int = 256
    embed_mult: float = 1.0
    residual_mult: float = 1.0
    attn_mult: float | None = None
    logits_div: float = 1.0

    def __post_init__(self):
        if self._scaled() and (
                self.mtp or self.loop_steps > 1 or self.index_top_k or
                not set(self.mixers) <= {"attention", "mamba"} or
                not set(self.ffns) <= {"glu"}):
            raise ValueError("embed_mult / residual_mult / attn_mult / "
                             "logits_div: the multipliers are written for "
                             "an unlooped stack of plain or grouped-query "
                             "attention, state-space and SwiGLU sub-layers "
                             "with no indexer and no MTP module")
        if "mamba" in self.mixers and not (
                self.ssm_heads > 0 and self.ssm_head_dim > 0 and
                self.ssm_state > 0 and self.conv_taps > 0 and
                self.ssm_chunk > 0):
            raise ValueError("a mamba mixer needs ssm_heads, ssm_head_dim, "
                             "ssm_state, conv_taps and ssm_chunk")
        if self.sandwich and not (set(self.mixers) <= {"attention", "latent"}
                                  and set(self.ffns) <= {"glu"}):
            raise ValueError("sandwich: the second norm is written for "
                             "attention and SwiGLU sub-layers")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps}: at least 1")
        if self.index_top_k and (set(self.mixers) != {"attention"} or
                                 self.loop_steps > 1 or self.mtp or
                                 self.rope_theta is None):
            raise ValueError("index_top_k: the indexer is written for an "
                             "unlooped stack of plain or grouped-query "
                             "attention layers with a rotary embedding and "
                             "no MTP module")
        if self.loop_steps > 1 and (self.mtp or not self.final_norm or
                                    "moe_routed" in self.ffns):
            raise ValueError("a looped stack is written with the final "
                             "norm closing each loop step, no MTP module "
                             "and no routed experts (their counters are "
                             "means over layers, not over loop steps)")

    def _scaled(self) -> bool:
        return (self.embed_mult, self.residual_mult, self.attn_mult,
                self.logits_div) != (1.0, 1.0, None, 1.0)

    @property
    def exit_gate(self) -> bool:
        """A looped stack's loss reads an exit gate; an unlooped stack
        has none (its one output would take the whole weight)."""
        return self.loop_steps > 1

    @property
    def n_layers(self) -> int:
        return len(self.mixers)

    def kinds(self, i: int) -> tuple:
        """``(mixer, ffn)`` of layer ``i``; ``i == n_layers`` is the MTP
        module's layer, of the last layer's kinds."""
        i = min(i, self.n_layers - 1)
        return self.mixers[i], self.ffns[i]

    def routed_layers(self) -> int:
        """Routed expert layers a step runs, the MTP module's among them."""
        n = self.ffns.count("moe_routed")
        return n + (self.mtp and self.kinds(self.n_layers)[1] == "moe_routed")

    def mechanisms(self) -> list:
        """Names of what this stack has beyond the GPT-shaped block: the
        words a refusal is made of (a mesh, ``export_lm``, ``serve/``)."""
        out = []
        if "sconv" in self.mixers:
            out.append("gated short convolution")
        if "mamba" in self.mixers:
            out.append(ssm.MECHANISM)
        if "latent" in self.mixers:
            out.append("latent attention")
        if self.kv_heads != self.heads:
            out.append("grouped-query attention")
        if self.qk_norm:
            out.append("QK-norm")
        if self.rope_theta is not None:
            out.append("rotary embedding")
        if self._scaled():
            out.append("static multipliers (embedding, residual, scores, "
                       "logits)")
        if self.index_top_k:
            out.append("learned sparse attention (indexer)")
        if "glu" in self.ffns:
            out.append("SwiGLU")
        if "moe_routed" in self.ffns:
            out.append("routed experts (moe_routed_ffn)")
        if self.shared_ff:
            out.append("shared expert")
        if self.mtp:
            out.append("multi-token prediction")
        if self.loop_steps > 1:
            out.append("looped stack")
        if self.exit_gate:
            out.append("exit gate")
        if self.sandwich:
            out.append("sandwich norm")
        if self.norm != "layer":
            out.append("RMSNorm")
        if self.final_norm:
            out.append("final norm")
        if self.tied:
            out.append("tied embedding and head")
        return out


def gpt_arch(n_layers: int, d: int, heads: int, ff: int, vocab: int,
             n_experts: int | None = None, moe_top_k: int = 1) -> Arch:
    """The GPT-shaped stack: pre-LayerNorm, as many key/value heads as
    query heads, no positional encoding, a biased GELU MLP (or, with
    ``n_experts``, the dense-masked MoE FFN), an untied head."""
    return Arch(d=int(d), heads=int(heads), kv_heads=int(heads),
                head_dim=int(d) // int(heads), ff=int(ff), vocab=int(vocab),
                mixers=("attention",) * int(n_layers),
                ffns=("moe_dense" if n_experts else "mlp",) * int(n_layers),
                n_experts=int(n_experts or 0),
                experts_held=int(n_experts or 0), top_k=int(moe_top_k))


_LAYER_TYPES = {"conv": "sconv", "full_attention": "attention",
                "attention": "attention", "mamba": "mamba"}


def _experts_held(cfg, n_experts: int) -> tuple:
    held = cfg.get("experts_held") or {"first": 0, "count": n_experts}
    first, count = int(held["first"]), int(held["count"])
    if first < 0 or count < 1 or first + count > max(n_experts, 1):
        raise ValueError(f"experts_held {held} of {n_experts} experts")
    return first, count


def _lfm2_moe_arch(cfg, vocab: int | None) -> Arch:
    """``lfm2_moe`` (``layer_types``, ``num_dense_layers``, ``num_experts``,
    ``num_experts_per_tok``, ``num_key_value_heads``, ``conv_L_cache``,
    ``rope_parameters``, ``norm_eps``, ...): RMSNorm, gated short
    convolutions and GQA attention with QK-norm and rotary embedding by
    ``layer_types``, bias-free SwiGLU in the leading dense layers and
    sigmoid-routed experts after them, a final norm and a tied head."""
    if cfg.get("conv_bias", False):
        raise ValueError("conv_bias: the short convolution here has none")
    types = list(cfg["layer_types"])
    if int(cfg.get("num_hidden_layers", len(types))) != len(types):
        raise ValueError(f"num_hidden_layers {cfg['num_hidden_layers']} "
                         f"against {len(types)} layer_types")
    unknown = sorted(set(types) - {"conv", "full_attention"})
    if unknown:
        raise ValueError(f"layer_types {unknown}: conv or full_attention")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    n_dense = int(cfg.get("num_dense_layers", 0))
    n_experts = int(cfg.get("num_experts", 0))
    first, count = _experts_held(cfg, n_experts)
    rope = cfg.get("rope_parameters") or {}
    return Arch(
        d=d, heads=heads, kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=int(cfg.get("head_dim") or d // heads),
        ff=int(cfg["intermediate_size"]),
        vocab=int(vocab if vocab is not None else cfg["vocab_size"]),
        mixers=tuple(_LAYER_TYPES[t] for t in types),
        ffns=tuple("glu" if i < n_dense or not n_experts else "moe_routed"
                   for i in range(len(types))),
        norm="rms", eps=float(cfg.get("norm_eps", 1e-5)), qk_norm=True,
        rope_theta=float(rope.get("rope_theta", cfg.get("rope_theta", 1e6))),
        conv_taps=int(cfg.get("conv_L_cache", 3)), n_experts=n_experts,
        experts_first=first, experts_held=count,
        top_k=int(cfg.get("num_experts_per_tok", 1)),
        moe_ff=int(cfg.get("moe_intermediate_size", 0)), score="sigmoid",
        expert_bias=bool(cfg.get("use_expert_bias", False)),
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        final_norm=True, tied=bool(cfg.get("tie_word_embeddings", True)))


def _glm4_moe_lite_arch(cfg, vocab: int | None) -> Arch:
    """``glm4_moe_lite`` (DeepSeek-V3's block: ``q_lora_rank``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``first_k_dense_replace``, ``n_routed_experts``,
    ``n_shared_experts``, ``num_nextn_predict_layers``, ...): RMSNorm,
    latent attention in every layer, bias-free SwiGLU in the leading
    dense layers, after them a shared expert beside sigmoid-routed
    experts selected by score plus ``e_score_correction_bias``
    (``topk_method`` ``noaux_tc``, one group), a final norm, an untied
    head and one multi-token-prediction module.  ``n_routed_experts`` is
    the experts held here where ``router_width`` gives the router's
    published width; ``mtp_loss_weight`` (0.3) weighs the module's
    loss."""
    if cfg.get("attention_bias", False):
        raise ValueError("attention_bias: the projections here have none")
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc" or \
            int(cfg.get("n_group", 1)) != 1 or \
            int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("topk_method / n_group / topk_group: noaux_tc "
                         "over one group of experts is what is written")
    if cfg.get("rope_scaling") or \
            float(cfg.get("partial_rotary_factor", 1)) != 1:
        raise ValueError("rope_scaling / partial_rotary_factor: the rotary "
                         "part is rotated whole and unscaled")
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    if int(cfg["v_head_dim"]) != nope + rope:
        raise ValueError(
            f"v_head_dim {cfg['v_head_dim']} against a query/key head of "
            f"{nope + rope}: the attention kernels take one head width")
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    if mtp > 1:
        raise ValueError(f"num_nextn_predict_layers {mtp}: one module is "
                         f"written")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if int(cfg.get("num_key_value_heads", heads)) != heads:
        raise ValueError("num_key_value_heads: latent attention expands "
                         "the latent to every head")
    layers = int(cfg["num_hidden_layers"])
    n_dense = int(cfg.get("first_k_dense_replace", 0))
    n_experts = int(cfg.get("router_width", cfg.get("n_routed_experts", 0)))
    first, count = _experts_held(cfg, n_experts)
    moe_ff = int(cfg.get("moe_intermediate_size", 0))
    return Arch(
        d=d, heads=heads, kv_heads=heads, head_dim=nope + rope,
        ff=int(cfg["intermediate_size"]),
        vocab=int(vocab if vocab is not None else cfg["vocab_size"]),
        mixers=("latent",) * layers,
        ffns=tuple("glu" if i < n_dense or not n_experts else "moe_routed"
                   for i in range(layers)),
        norm="rms", eps=float(cfg.get("rms_norm_eps", 1e-5)),
        rope_theta=float(cfg.get("rope_theta", 1e4)),
        rope_interleaved=bool(cfg.get("rope_interleave", True)),
        n_experts=n_experts, experts_first=first, experts_held=count,
        top_k=int(cfg.get("num_experts_per_tok", 1)), moe_ff=moe_ff,
        score="sigmoid", expert_bias=True,
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        final_norm=True, tied=bool(cfg.get("tie_word_embeddings", False)),
        q_lora=int(cfg["q_lora_rank"]), kv_lora=int(cfg["kv_lora_rank"]),
        nope_dim=nope, rope_dim=rope,
        shared_ff=int(cfg.get("n_shared_experts", 0)) * moe_ff,
        mtp=bool(mtp), mtp_weight=float(cfg.get("mtp_loss_weight", 0.3)))


def _ouro_arch(cfg, vocab: int | None) -> Arch:
    """``ouro`` (a looped LM, arXiv:2510.25741: ``total_ut_steps``,
    ``layer_types`` all ``full_attention``, ``head_dim``,
    ``rms_norm_eps``, ``rope_theta``, ...): a dense stack of RMSNorm
    sandwich-normed layers (plain multi-head or grouped-query attention
    with rotate-half RoPE over the whole head, no bias, no QK-norm; a
    bias-free SwiGLU) run ``total_ut_steps`` times over the same weights,
    the final norm closing every loop step, an exit gate, an untied head.
    ``exit_entropy_weight`` (0.1) is the loss's ``beta``;
    ``early_exit_threshold`` is an inference key and is not read."""
    types = list(cfg.get("layer_types") or
                 ["full_attention"] * int(cfg["num_hidden_layers"]))
    if int(cfg.get("num_hidden_layers", len(types))) != len(types):
        raise ValueError(f"num_hidden_layers {cfg['num_hidden_layers']} "
                         f"against {len(types)} layer_types")
    if set(types) != {"full_attention"}:
        raise ValueError(f"layer_types {sorted(set(types))}: "
                         f"full_attention in every layer is what is written")
    if cfg.get("use_sliding_window", False) or cfg.get("sliding_window"):
        raise ValueError("sliding_window: attention here is causal over "
                         "the whole sequence")
    if cfg.get("rope_scaling") or cfg.get("attention_bias", False):
        raise ValueError("rope_scaling / attention_bias: the rotary "
                         "embedding is unscaled and the projections have "
                         "no bias")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: SwiGLU")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    steps = int(cfg.get("total_ut_steps", 1))
    return Arch(
        d=d, heads=heads, kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=int(cfg.get("head_dim") or d // heads),
        ff=int(cfg["intermediate_size"]),
        vocab=int(vocab if vocab is not None else cfg["vocab_size"]),
        mixers=("attention",) * len(types), ffns=("glu",) * len(types),
        norm="rms", eps=float(cfg.get("rms_norm_eps", 1e-6)),
        rope_theta=float(cfg.get("rope_theta", 1e6)), final_norm=True,
        tied=bool(cfg.get("tie_word_embeddings", False)), sandwich=True,
        loop_steps=steps,
        exit_beta=float(cfg.get("exit_entropy_weight", 0.1)))


def _keye_vl2_arch(cfg, vocab: int | None) -> Arch:
    """``KeyeVL2`` (the language model of Keye-VL-2.0: a Qwen3-MoE-shaped
    decoder, ``num_experts``, ``num_experts_per_tok``,
    ``moe_intermediate_size``, ``norm_topk_prob``, ``decoder_sparse_step``,
    ``mlp_only_layers``, with ``sa_config``, a DeepSeek-Sparse-Attention
    indexer on every attention layer): RMSNorm, grouped-query attention
    with QK-norm and rotate-half RoPE, ``sa_config.indexer_num_heads``
    index heads of ``indexer_head_dim`` on one index key head picking
    ``sa_config.topk`` keys a query, softmax-routed SwiGLU experts in
    every layer (no shared expert, no dense layer, no bias), a final norm,
    an untied head.  ``router_width`` gives the router's published width
    where ``num_experts`` counts the experts held here.
    ``rope_scaling.mrope_section`` splits the rotary frequencies over
    three position streams; a step takes text tokens only, whose three
    streams are one, so the rotation is :func:`_rotate`'s: the sections
    are checked against the head and otherwise not read.  Refused: a
    sliding window, ``mlp_only_layers``, a ``decoder_sparse_step`` other
    than 1, an attention bias, index key heads other than one;
    ``sa_config``'s chunk sizes change no value and are not read."""
    if cfg.get("use_sliding_window", False) or cfg.get("sliding_window"):
        raise ValueError("sliding_window: attention here is over the "
                         "indexer's selection of the whole sequence")
    if cfg.get("mlp_only_layers"):
        raise ValueError(f"mlp_only_layers {cfg['mlp_only_layers']}: every "
                         f"layer routed is what is written")
    if int(cfg.get("decoder_sparse_step", 1)) != 1:
        raise ValueError(f"decoder_sparse_step "
                         f"{cfg['decoder_sparse_step']}: 1 (every layer "
                         f"routed) is what is written")
    if cfg.get("attention_bias", False):
        raise ValueError("attention_bias: the projections here have none")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: SwiGLU")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or d // heads)
    scaling = cfg.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise ValueError(f"rope_scaling {scaling}: the rotary embedding is "
                         f"unscaled")
    sections = scaling.get("mrope_section")
    if sections is not None and 2 * sum(int(n) for n in sections) != hd:
        raise ValueError(f"mrope_section {sections} does not sum to half "
                         f"the head ({hd} / 2)")
    sa = cfg["sa_config"]
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError(f"sa_config.indexer_num_kv_heads "
                         f"{sa['indexer_num_kv_heads']}: one index key head "
                         f"is what is written")
    layers = int(cfg["num_hidden_layers"])
    n_experts = int(cfg.get("router_width", cfg["num_experts"]))
    first, count = _experts_held(cfg, n_experts)
    return Arch(
        d=d, heads=heads, kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=hd, ff=int(cfg.get("intermediate_size", 0)),
        vocab=int(vocab if vocab is not None else cfg["vocab_size"]),
        mixers=("attention",) * layers, ffns=("moe_routed",) * layers,
        norm="rms", eps=float(cfg.get("rms_norm_eps", 1e-6)), qk_norm=True,
        rope_theta=float(cfg.get("rope_theta", 1e7)), n_experts=n_experts,
        experts_first=first, experts_held=count,
        top_k=int(cfg["num_experts_per_tok"]),
        moe_ff=int(cfg["moe_intermediate_size"]), score="softmax",
        norm_topk=bool(cfg.get("norm_topk_prob", True)), final_norm=True,
        tied=bool(cfg.get("tie_word_embeddings", False)),
        index_heads=int(sa["indexer_num_heads"]),
        index_dim=int(sa["indexer_head_dim"]), index_top_k=int(sa["topk"]))


def _granitemoehybrid_arch(cfg, vocab: int | None) -> Arch:
    """``granitemoehybrid`` (Granite 4.0-H: ``layer_types`` of ``mamba`` and
    ``attention``, ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
    ``mamba_d_conv``, ``mamba_chunk_size``, ``shared_intermediate_size``,
    and the four multipliers ``embedding_multiplier``,
    ``attention_multiplier``, ``residual_multiplier``, ``logits_scaling``):
    RMSNorm, Mamba-2 state-space layers (``parallel/ssm.py``: one group, a
    biased convolution, bias-free projections) beside grouped-query
    attention layers with NO positional encoding and the score scale
    ``attention_multiplier``, a bias-free SwiGLU of
    ``shared_intermediate_size`` in every layer (the family's one fused
    ``input_linear`` is ``w1`` and ``w3`` side by side), a final norm, the
    head tied or not.  Refused by name: experts (``num_local_experts`` > 0:
    the family's routed part beside the shared SwiGLU is not written),
    ``mamba_n_groups`` other than 1, a ``normalization_function`` other
    than ``rmsnorm``, a ``position_embedding_type`` other than ``nope``, an
    attention or projection bias, a convolution without its bias, an inner
    width that is not ``mamba_n_heads x mamba_d_head``, an activation other
    than silu.  ``intermediate_size`` (the experts') is not read."""
    if int(cfg.get("num_local_experts") or 0) > 0:
        raise ValueError(f"num_local_experts {cfg['num_local_experts']}: "
                         f"routed experts beside the shared SwiGLU are not "
                         f"written for this family (0 is)")
    if int(cfg.get("mamba_n_groups", 1)) != 1:
        raise ValueError(f"mamba_n_groups {cfg['mamba_n_groups']}: one group "
                         f"(B and C serve all heads) is what is written")
    if cfg.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError(f"normalization_function "
                         f"{cfg['normalization_function']!r}: rmsnorm")
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise ValueError(f"position_embedding_type "
                         f"{cfg['position_embedding_type']!r}: nope (no "
                         f"positional encoding) is what is written")
    if cfg.get("attention_bias", False) or cfg.get("mamba_proj_bias", False):
        raise ValueError("attention_bias / mamba_proj_bias: the projections "
                         "here have none")
    if not cfg.get("mamba_conv_bias", True):
        raise ValueError("mamba_conv_bias false: the state-space layer's "
                         "convolution here carries its bias")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: SwiGLU")
    types = list(cfg["layer_types"])
    if int(cfg.get("num_hidden_layers", len(types))) != len(types):
        raise ValueError(f"num_hidden_layers {cfg['num_hidden_layers']} "
                         f"against {len(types)} layer_types")
    unknown = sorted(set(types) - {"mamba", "attention"})
    if unknown:
        raise ValueError(f"layer_types {unknown}: mamba or attention")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    m_heads, m_dim = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if int(cfg.get("mamba_expand", 2)) * d != m_heads * m_dim:
        raise ValueError(
            f"mamba_expand {cfg.get('mamba_expand', 2)} x hidden_size {d} "
            f"against mamba_n_heads {m_heads} x mamba_d_head {m_dim}")
    return Arch(
        d=d, heads=heads, kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=int(cfg.get("head_dim") or d // heads),
        ff=int(cfg["shared_intermediate_size"]),
        vocab=int(vocab if vocab is not None else cfg["vocab_size"]),
        mixers=tuple(_LAYER_TYPES[t] for t in types),
        ffns=("glu",) * len(types), norm="rms",
        eps=float(cfg.get("rms_norm_eps", 1e-5)),
        conv_taps=int(cfg.get("mamba_d_conv", 4)), final_norm=True,
        tied=bool(cfg.get("tie_word_embeddings", True)),
        ssm_heads=m_heads, ssm_head_dim=m_dim,
        ssm_state=int(cfg["mamba_d_state"]),
        ssm_chunk=int(cfg.get("mamba_chunk_size", 256)),
        embed_mult=float(cfg.get("embedding_multiplier", 1.0)),
        residual_mult=float(cfg.get("residual_multiplier", 1.0)),
        attn_mult=float(cfg["attention_multiplier"])
        if cfg.get("attention_multiplier") is not None else None,
        logits_div=float(cfg.get("logits_scaling", 1.0)))


#: ``model_type`` -> the reader of that family's keys
_FAMILIES = {"lfm2_moe": _lfm2_moe_arch, "glm4_moe_lite": _glm4_moe_lite_arch,
             "ouro": _ouro_arch, "KeyeVL2": _keye_vl2_arch,
             "granitemoehybrid": _granitemoehybrid_arch}


def arch_from_config(cfg, vocab: int | None = None) -> Arch:
    """A model's own keys -> :class:`Arch`, by ``model_type``
    (:data:`_FAMILIES`: :func:`_lfm2_moe_arch`, also what a mapping with
    ``layer_types`` and no ``model_type`` is read as,
    :func:`_glm4_moe_lite_arch`, :func:`_ouro_arch`,
    :func:`_keye_vl2_arch` and :func:`_granitemoehybrid_arch`).  ``experts_held`` (``{"first",
    "count"}``; all by default) is this chip's share of the experts;
    ``vocab`` (the loader's) overrides ``vocab_size``.  Any other
    ``model_type`` is refused by name."""
    kind = cfg.get("model_type", "lfm2_moe" if "layer_types" in cfg else None)
    if kind not in _FAMILIES:
        raise ValueError(
            f"model_type {kind!r}: this stack reads {', '.join(_FAMILIES)} "
            f"configurations and the GPT-shaped integers")
    return _FAMILIES[kind](cfg, vocab)


def as_arch(arch, d=None, heads=None, ff=None, vocab=None,
            n_experts=None, moe_top_k: int = 1) -> Arch:
    """What every factory below takes first: an :class:`Arch`, a model's
    configuration mapping, or the GPT-shaped block's ``n_layers`` followed
    by ``d, heads, ff, vocab``."""
    if isinstance(arch, Arch):
        return arch
    if isinstance(arch, Mapping):
        return arch_from_config(arch, vocab)
    return gpt_arch(arch, d, heads, ff, vocab, n_experts, moe_top_k)


#: a leaf only a layer kind beyond the GPT-shaped block has -> its name
_LEAF_MECHANISMS = {
    "w_in": "gated short convolution", "q_g": "QK-norm",
    "w3": "SwiGLU", "ew3": "routed experts (moe_routed_ffn)",
    "wkv_a": "latent attention", "sw1": "shared expert",
    "ln1o_g": "sandwich norm",
    "wiq": "learned sparse attention (indexer)",
    "ssm_a_log": ssm.MECHANISM,
}


def mechanisms_of_params(params) -> list:
    """The names (:meth:`Arch.mechanisms`) of what a params pytree holds
    beyond the GPT-shaped block, read from its leaves: what ``serve/``
    and ``export_lm`` refuse with."""
    out = []
    for blk in params["blocks"]:
        for leaf, name in _LEAF_MECHANISMS.items():
            if leaf in blk and name not in out:
                out.append(name)
        if "wk" in blk and np.shape(blk["wk"]) != np.shape(blk["wq"]) and \
                "grouped-query attention" not in out:
            out.append("grouped-query attention")
    if "mtp" in params:
        out.append("multi-token prediction")
    if "exit_w" in params:             # only a looped stack carries a gate
        out += ["looped stack", "exit gate"]
    if "norm_g" in params:
        out.append("final norm")
    if "head" not in params:
        out.append("tied embedding and head")
    return out


def _layer_shapes(arch: Arch, i: int) -> dict:
    """``{leaf: shape}`` of layer ``i`` (``n_layers``: the MTP module's):
    the one table the initialiser, the specs and the shapes are read
    from."""
    d, hd = arch.d, arch.head_dim
    bias = arch.norm == "layer"
    mixer, ffn = arch.kinds(i)
    out = {"ln1_g": (d,), "ln2_g": (d,)}
    if bias:
        out.update({"ln1_b": (d,), "ln2_b": (d,)})
    if arch.sandwich:
        out.update({"ln1o_g": (d,), "ln2o_g": (d,)})
    if mixer == "latent":
        out.update({
            "wq_a": (d, arch.q_lora), "q_a_g": (arch.q_lora,),
            "wq_b": (arch.q_lora, arch.heads * hd),
            "wkv_a": (d, arch.kv_lora + arch.rope_dim),
            "kv_a_g": (arch.kv_lora,),
            "wkv_b": (arch.kv_lora, arch.heads * (arch.nope_dim + hd)),
            "wo": (arch.heads * hd, d)})
    elif mixer == "attention":
        out.update({"wq": (d, arch.heads * hd), "wk": (d, arch.kv_heads * hd),
                    "wv": (d, arch.kv_heads * hd), "wo": (arch.heads * hd, d)})
        if arch.qk_norm:
            out.update({"q_g": (hd,), "k_g": (hd,)})
        if arch.index_top_k:
            hi, di = arch.index_heads, arch.index_dim
            out.update({"wiq": (d, hi * di), "wik": (d, di), "wiw": (d, hi),
                        "ik_g": (di,), "ik_b": (di,)})
    elif mixer == "mamba":
        out.update(ssm.leaf_shapes(d, arch.ssm_heads, arch.ssm_head_dim,
                                   arch.ssm_state, arch.conv_taps))
    else:
        out.update({"w_in": (d, 3 * d), "conv_k": (arch.conv_taps, d),
                    "w_out": (d, d)})
    if ffn == "mlp":
        out.update({"w1": (d, arch.ff), "b1": (arch.ff,),
                    "w2": (arch.ff, d), "b2": (d,)})
    elif ffn == "glu":
        out.update({"w1": (d, arch.ff), "w3": (d, arch.ff),
                    "w2": (arch.ff, d)})
    elif ffn == "moe_dense":
        e = arch.n_experts
        out.update({"gate": (d, e), "ew1": (e, d, arch.ff),
                    "eb1": (e, arch.ff), "ew2": (e, arch.ff, d),
                    "eb2": (e, d)})
    else:
        e, f = arch.experts_held, arch.moe_ff
        out.update({"gate": (d, arch.n_experts), "ew1": (e, d, f),
                    "ew3": (e, d, f), "ew2": (e, f, d)})
        if arch.expert_bias:
            out["ebias"] = (arch.n_experts,)
        if arch.shared_ff:
            out.update({"sw1": (d, arch.shared_ff), "sw3": (d, arch.shared_ff),
                        "sw2": (arch.shared_ff, d)})
    return out


def _tail_shapes(arch: Arch) -> dict:
    """``{leaf: shape}`` of what the pytree holds behind ``blocks``: the
    final norm's gain, the MTP module, the exit gate."""
    out = {}
    if arch.final_norm:
        out["norm_g"] = (arch.d,)
    if arch.mtp:
        out["mtp"] = _mtp_shapes(arch)
    if arch.exit_gate:
        out.update({"exit_w": (arch.d, 1), "exit_b": (1,)})
    return out


def _mtp_shapes(arch: Arch) -> dict:
    """``{leaf: shape}`` of the MTP module: the two norms and the
    projection in front of its layer, the layer, the norm behind it."""
    d = arch.d
    return {"enorm_g": (d,), "hnorm_g": (d,), "proj": (2 * d, d),
            "block": _layer_shapes(arch, arch.n_layers), "norm_g": (d,)}


#: leaves that start at one (gains), and those that start at zero
_ONES = ("ln1_g", "ln2_g", "ln1o_g", "ln2o_g", "q_g", "k_g", "norm_g",
         "q_a_g", "kv_a_g", "enorm_g", "hnorm_g", "ik_g", "ssm_g", "ssm_d")
_ZEROS = ("ln1_b", "ln2_b", "b1", "b2", "eb1", "eb2", "ebias", "exit_b",
          "ik_b", "ssm_conv_b")
#: how each leaf of the GPT-shaped block lies over the ``model`` axis
_TP_SPECS = {
    "wq": P(None, "model"), "wk": P(None, "model"), "wv": P(None, "model"),
    "wo": P("model", None), "w1": P(None, "model"), "b1": P("model"),
    "w2": P("model", None), "ew1": P("model", None, None),
    "eb1": P("model", None), "ew2": P("model", None, None),
    "eb2": P("model", None),
}


# -- dp x sp x tp flagship --------------------------------------------------
def init_params(gen, arch, d=None, heads=None, ff=None, vocab=None,
                n_experts: int | None = None):
    """Global (unsharded) parameter pytree from the framework PRNG, for
    ``arch`` (:func:`as_arch`: an :class:`Arch`, a configuration mapping,
    or ``n_layers, d, heads, ff, vocab`` of the GPT-shaped block, where
    ``n_experts`` swaps each block's dense FFN for the dense-masked MoE
    FFN: gate + per-expert w1/b1/w2/b2 stacks, expert-sharded over the
    ``model`` axis at placement time).  Projections are normal
    ``1/sqrt(fan_in)``, the embedding normal 0.02, gains one, biases
    zero; a convolution's taps are normal ``1/sqrt(taps)``; a state-space
    layer's decay rates uniform 1 .. 16 (``ssm_a_log`` their log), its step
    sizes log-uniform 0.001 .. 0.1 (``ssm_dt_b`` their inverse softplus),
    its skip one, as Mamba-2 starts them."""
    arch = as_arch(arch, d, heads, ff, vocab, n_experts)

    def w(shape, scale=None):
        scale = scale or 1.0 / np.sqrt(shape[-2] if len(shape) > 1
                                       else shape[0])
        return gen.normal(0.0, scale, shape).astype(np.float32)

    def leaf(name, shape):
        if name in _ONES:
            return np.ones(shape, np.float32)
        if name in _ZEROS:
            return np.zeros(shape, np.float32)
        if name in ("conv_k", "ssm_conv_k"):
            return w(shape, 1.0 / np.sqrt(shape[0]))
        if name == "ssm_a_log":
            return np.log(gen.uniform(1.0, 16.0, shape)).astype(np.float32)
        if name == "ssm_dt_b":
            dt = np.exp(gen.uniform(np.log(1e-3), np.log(1e-1), shape))
            return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        return w(shape)

    if arch.n_layers and set(arch.ffns) <= {"mlp", "moe_dense"} and \
            set(arch.mixers) == {"attention"} and arch.norm == "layer":
        # the GPT-shaped block draws in the order it always drew in
        # (seeded runs and their pins follow the generator's stream)
        order = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b",
                 "gate", "ew1", "eb1", "ew2", "eb2", "w1", "b1", "w2", "b2")
    else:
        order = None
    blocks = []
    for i in range(arch.n_layers):
        shapes = _layer_shapes(arch, i)
        names = [k for k in order if k in shapes] if order else list(shapes)
        blocks.append({k: leaf(k, shapes[k]) for k in names})
    out = {"emb": w((arch.vocab, arch.d), 0.02)}
    if not arch.tied:
        out["head"] = w((arch.d, arch.vocab))
    out["blocks"] = blocks
    out.update(_map_shapes(leaf, _tail_shapes(arch)))
    return out


def _map_shapes(fn, shapes: dict) -> dict:
    """``fn(leaf name, shape)`` over a nested ``{leaf: shape}`` table."""
    return {k: _map_shapes(fn, v) if isinstance(v, dict) else fn(k, v)
            for k, v in shapes.items()}


def param_specs(arch, head_sharded: bool = False, moe: bool = False):
    """PartitionSpecs matching init_params: attention qkv column-sharded,
    wo row-sharded, MLP Megatron-sharded over ``model``; the rest
    replicated.  ``head_sharded`` vocab-shards the LM head over
    ``model`` (Megatron parallel cross-entropy — pair with
    ``make_train_step(head_sharded=True)``).  ``arch`` is an
    :class:`Arch`, or the GPT-shaped block's ``n_layers`` with ``moe``
    selecting the expert-parallel FFN layout (expert stacks sharded over
    ``model`` on the expert dim, gate replicated).  The leaves of the
    layer kinds that run on no ``model`` axis are replicated."""
    if not isinstance(arch, Arch):
        arch = gpt_arch(arch, 1, 1, 1, 1, n_experts=1 if moe else None)
    gpt = not arch.mechanisms()
    blocks = [{k: _TP_SPECS.get(k, P()) if gpt else P()
               for k in _layer_shapes(arch, i)}
              for i in range(arch.n_layers)]
    out = {"emb": P()}
    if not arch.tied:
        out["head"] = P(None, "model") if head_sharded else P()
    out["blocks"] = blocks
    out.update(_map_shapes(lambda k, shape: P(), _tail_shapes(arch)))
    return out


def param_shapes(arch, d=None, ff=None, vocab=None,
                 n_experts: int | None = None):
    """Shape pytree mirroring :func:`init_params` — the static ``like``
    information the shard_params gather chain needs (a flat-sharded
    leaf has lost its original shape).  ``arch`` is an :class:`Arch`,
    or ``n_layers, d, ff, vocab`` of the GPT-shaped block (no shape of
    which depends on the head count)."""
    if not isinstance(arch, Arch):
        arch = gpt_arch(arch, d, 1, ff, vocab, n_experts)
    out = {"emb": (arch.vocab, arch.d)}
    if not arch.tied:
        out["head"] = (arch.d, arch.vocab)
    out["blocks"] = [_layer_shapes(arch, i) for i in range(arch.n_layers)]
    out.update(_tail_shapes(arch))
    return out


def _spec_leaves(specs):
    # PartitionSpec is a tuple subclass (a pytree container), so spec
    # trees flatten with an is_leaf guard (same trick as local_step)
    return jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))


def _shape_leaves(shapes):
    return jax.tree.leaves(shapes,
                           is_leaf=lambda x: isinstance(x, tuple))


def shard_params_specs(specs):
    """Layout of a ``shard_params`` step's params: every REPLICATED
    (``P()``) leaf becomes a flat array sharded ``P("data")``;
    tensor-sharded leaves keep their specs (they already live
    partitioned)."""
    return jax.tree.map(lambda s: P("data") if s == P() else s, specs,
                        is_leaf=lambda x: isinstance(x, P))


def shard_params_host(params, specs, n: int):
    """Host-side conversion INTO the shard_params layout: replicated
    leaves flatten and zero-pad to a multiple of ``n`` (place them with
    :func:`shard_params_specs`); tensor-sharded leaves pass through.
    ``specs`` is the REPLICATED-layout tree (:func:`param_specs`)."""
    flat_w, treedef = jax.tree.flatten(params)
    out = []
    for w, s in zip(flat_w, _spec_leaves(specs)):
        if s == P():
            f = np.asarray(w).reshape(-1)
            pad = (-f.size) % n
            if pad:
                f = np.pad(f, (0, pad))
            out.append(f)
        else:
            out.append(w)
    return jax.tree.unflatten(treedef, out)


def unshard_params_host(params, specs, shapes):
    """Inverse of :func:`shard_params_host` on host arrays (the caller
    ``jax.device_get``s first): flat-padded leaves slice back to their
    original shapes from the :func:`param_shapes` tree."""
    flat_w, treedef = jax.tree.flatten(params)
    out = []
    for w, s, shp in zip(flat_w, _spec_leaves(specs),
                         _shape_leaves(shapes)):
        if s == P():
            size = int(np.prod(shp))
            out.append(np.asarray(w).reshape(-1)[:size].reshape(shp))
        else:
            out.append(np.asarray(w))
    return jax.tree.unflatten(treedef, out)


@dataclasses.dataclass(frozen=True)
class _Run:
    """What a step build fixes beside the architecture: the local head
    counts, the attention core, the regularizer weights.  ``use_flash``,
    ``interpret`` and ``use_ring_flash`` are captured together at
    step-build time so one config snapshot governs all three
    flash-related decisions (kernel choice, interpreter, vma mode)."""

    heads_local: int
    kv_heads_local: int
    causal: bool = True
    use_flash: bool = False
    interpret: bool = False
    use_ring_flash: bool = False
    moe_aux_weight: float = 0.0
    moe_zloss_weight: float = 0.0
    #: bytes of device memory the backend reports (:func:`_memory_limit`),
    #: None where it reports none: what :func:`checkpoint_plan` divides
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
    recomputation policies (:func:`_block_fn`; a name is no operation),
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
    the residual stream and adding to it.  -> ``(x, aux, stats)``: the
    regularizer term (pre-weighted) and the layer's counters (the routed
    layer's, and :func:`_block_attn`'s of a layer that ran a flash
    kernel or has an indexer, whose alignment term ``aux`` carries).
    Scopes: ``block<index>.attn`` (with ``.attn.latent`` beside it for
    what latent attention does before the kernel, ``.attn.index``,
    ``.attn.select`` and ``.attn.align`` for an indexer), ``.sconv`` or
    ``.ssm`` (with ``.ssm.conv`` and ``.ssm.scan`` beside it), then
    ``block<index>.mlp`` or ``.moe`` (with ``.moe.route``,
    ``.moe.experts`` and ``.moe.shared`` beside it)."""
    mixer, ffn = arch.kinds(index)
    stats: dict = {}
    if mixer == "sconv":
        with _probe.scope(f"block{index}.sconv"):
            x = _block_sconv(x, p, arch)
    elif mixer == "mamba":
        x, stats = _block_ssm(x, p, arch, f"block{index}.ssm")
    else:
        x, stats = _block_attn(x, p, arch, run, f"block{index}.attn")
    if ffn == "moe_routed":
        x, aux, routed = _block_routed(x, p, arch, f"block{index}.moe")
        stats = {**stats, **routed}
    else:
        with _probe.scope(f"block{index}.mlp"):
            x, aux = _block_mlp(x, p, arch, ffn, run)
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
                         arch.ssm_state, arch.ssm_chunk, arch.eps, scope)
    with _probe.scope(scope):
        return x + _sub_out(y, p, "ln1o", arch), stats


def _plain_qkv(h, p, arch: Arch, run: _Run):
    """Queries, keys and values ``(b, t, heads, head_dim)`` of plain or
    grouped-query attention: three projections, the score scale where it is
    not the kernels' own (``arch.attn_mult``: q takes ``attn_mult *
    sqrt(head_dim)``, exact where that is a power of two), the optional
    QK-norm, the optional rotary embedding over the whole head: rotate-half, by the
    in-place row kernel where :func:`_rows_rope` says so (a head of 128:
    the whole head is the kernel's tail), else :func:`_rotate`'s f32 chain
    of array operations.  What no kernel wrote is named ``attn_qkv`` for
    the looped stack's recomputation policy, which keeps every kernel's
    output anyway (:func:`_loop_saves`; a name is no operation)."""
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
    if arch.rope_theta is not None and _rows_rope(t_loc, arch, run):
        from znicz_tpu.ops.pallas import rope as prope
        cos, sin = _rope_angles(t_loc, arch.head_dim, arch.rope_theta)
        return tuple(prope.rope_tail(
            a.reshape(b, t_loc, -1), cos, sin, a.shape[2],
            run.interpret).reshape(a.shape) for a in (q, k)) + (v,)
    if arch.rope_theta is not None:
        q, k = _rotate(q, arch.rope_theta), _rotate(k, arch.rope_theta)
    return checkpoint_name(q, "attn_qkv"), checkpoint_name(k, "attn_qkv"), v


def _rows_rope(t: int, arch: Arch, run: _Run) -> bool:
    """Whether the rotated columns of every head (latent attention's
    ``rope_dim`` tail; of plain attention the whole head) are rotated as
    whole rows of heads by the in-place kernel (``ops/pallas/rope.py``):
    where the flash kernels read the layer's own layout
    (``attention.direct_layout``) and the kernel takes the shape.
    Elsewhere the head is cut and concatenated, and the flash kernels fold
    or copy it anyway."""
    from znicz_tpu.ops.pallas import attention as pattn, rope as prope
    dh = arch.head_dim
    return run.use_flash and pattn.direct_layout(t, dh) and \
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


def _block_attn(x, p, arch: Arch, run: _Run, scope: str):
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
    as a regularizer's is) and the selection's counts."""
    from znicz_tpu.ops.pallas import attention as pattn
    with _probe.scope(scope):
        h = _norm(x, p, "ln1", arch)
    b, t_loc, _ = h.shape
    if "wkv_a" in p:
        with _probe.scope(f"{scope}.latent"):
            q, k, v = _latent_qkv(h, p, arch, run)
    else:
        with _probe.scope(scope):
            q, k, v = _plain_qkv(h, p, arch, run)
    sel, picked = None, {}
    if arch.index_top_k:
        sel, picked = _select_keys(h, q, k, p, arch, run, scope)
    with _probe.scope(scope):
        dh = q.shape[-1]
        why = None
        if run.use_flash and sel is not None:
            why = pattn.blocked_unsupported_reason(t_loc, dh)
        elif run.use_flash:
            why = pattn.form_of(t_loc, dh)[1]
        elif run.use_ring_flash:       # the ring merges whole-row blocks
            why = pattn.unsupported_reason(t_loc, dh)
        eligible = run.use_flash or run.use_ring_flash
        flash = eligible and not why
        direct = bool(flash and run.use_flash and
                      pattn.direct_layout(t_loc, dh))
        if eligible:
            _report_flash_choice(
                t_loc, dh, why, direct, None if sel is None else
                _dsa_choice(t_loc, q.shape[2], k.shape[2], dh,
                            arch.index_heads, arch.index_dim, run.interpret))
        if run.use_flash and not why:
            o = pattn.flash_attention(q, k, v, causal=run.causal,
                                      interpret=run.interpret, sel=sel)
        elif sel is not None:
            o = _selected_attention_dense(q, k, v, sel)
        else:
            group = q.shape[2] // k.shape[2]
            if group > 1:
                k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
            if run.use_ring_flash and not why:
                o = ring_flash_attention(q, k, v, "seq", causal=run.causal,
                                         interpret=run.interpret)
            else:
                o = ring_attention(q, k, v, "seq", causal=run.causal)
        o = o.reshape(b, t_loc, -1)                  # (b, t_loc, d_local)
        # a layer that ran a flash kernel counts itself, and once more if
        # its kernels read the layer's layout: known as the step is traced
        stats = {"attn_flash": jnp.ones((), jnp.float32),
                 "attn_direct": jnp.full((), float(direct), jnp.float32)} \
            if flash else {}
        y = tp.row_parallel(o, p["wo"], None, "model")
        return x + _sub_out(y, p, "ln1o", arch), {**stats, **picked}


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


def _block_sconv(x, p, arch: Arch):
    """Gated short convolution: ``[B, C, X] = split3(u W_in)``; ``z = B *
    X``; a depthwise causal convolution over time, ``conv_taps`` taps a
    channel, zeros before the sequence starts (``c_t = sum_j k_j
    z_{t-taps+1+j}``, accumulated in f32); ``out = (C * c) W_out``."""
    u = _norm(x, p, "ln1", arch)
    t = u.shape[1]
    gate_b, gate_c, xin = jnp.split(u @ p["w_in"], 3, axis=-1)
    z = (gate_b * xin).astype(jnp.float32)
    taps = arch.conv_taps
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    kf = p["conv_k"].astype(jnp.float32)
    c = sum(kf[j] * zp[:, j:j + t] for j in range(taps))
    return x + (gate_c * c.astype(x.dtype)) @ p["w_out"]


def _glu(m, w1, w3, w2):
    """Bias-free SwiGLU.  Its two wide products are named for the
    recomputation policy (``glu_wide``: kept where :func:`checkpoint_plan`
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


def _block_routed(x, p, arch: Arch, scope: str):
    """This chip's share of a routed expert layer
    (:func:`moe.moe_routed_ffn`); the norm and the residual sum lie
    under ``scope``, the layer's two parts under ``scope.route`` and
    ``scope.experts``, and the shared expert, which every chip computes
    alike for every token, under ``scope.shared``."""
    with _probe.scope(scope):
        m = _norm(x, p, "ln2", arch)
    if "sw1" in p:
        with _probe.scope(f"{scope}.shared"):
            x = x + _glu(m, p["sw1"], p["sw3"], p["sw2"])
    y, stats = moe_routed_ffn(
        m.reshape(-1, m.shape[-1]), p["gate"], p.get("ebias"), p["ew1"],
        p["ew3"], p["ew2"], first=arch.experts_first, top_k=arch.top_k,
        score=arch.score, norm_topk=arch.norm_topk,
        scale=arch.routed_scale, scope=scope)
    with _probe.scope(scope):
        return x + y.reshape(m.shape), jnp.zeros((), jnp.float32), stats


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


def attn_kvb_block_rows(mesh: Mesh, arch: Arch, t: int) -> dict:
    """``{pass: rows}`` of the tile each pass of the key/value-blocked
    flash kernels runs in a step's attention layers at ``t`` positions
    (``attention.kvb_block_rows``, by ``t``, the head width and the pass),
    0 in every pass where they run another form or no flash kernel, or the
    stack has no attention layer: what :func:`_block_attn` will trace,
    known from the mesh, the architecture and the sequence length."""
    from znicz_tpu.ops.pallas import attention as pattn
    rows = pattn.kvb_block_rows(t, arch.head_dim, bool(arch.index_top_k))
    if {"attention", "latent"} & set(arch.mixers) and \
            _run_of(mesh, arch, causal=True).use_flash:
        return rows
    return dict.fromkeys(rows, 0)


def checkpoint_kept_bytes(mesh: Mesh, arch: Arch, batch: int, t: int,
                          loss_chunks: int | None = None,
                          compute_dtype=None) -> dict:
    """:func:`checkpoint_plan` of a train step of ``batch`` rows of ``t``
    positions on ``mesh``: ``{name: bytes}`` of what its checkpointed
    layers keep beside their own list (0: refused; empty: no layer is
    checkpointed by that policy), what :func:`_forward_ce` will trace,
    known from the mesh, its first device's memory, the architecture and
    the batch's shape."""
    tokens = (batch // mesh.shape.get("data", 1)) * \
        (t // mesh.shape.get("seq", 1))
    cdt = _default_compute_dtype(compute_dtype)
    return checkpoint_plan(arch, tokens, jnp.dtype(cdt).itemsize,
                           _memory_limit(mesh), loss_chunks)


def dsa_kernel_shares(mesh: Mesh, arch: Arch, t: int) -> dict | None:
    """Of a step's layers with an indexer at ``t`` positions, the share
    whose index scores and their gradients (``"index"``) and whose alignment
    target (``"align"``) the kernels make (``ops/pallas/dsa.py``; each all
    or none: the layers share their shape), None for a stack without an
    indexer: what :func:`_select_keys` will trace, known from the mesh, the
    architecture and the sequence length (``dsa.index_kernel_refusal``,
    ``dsa.align_kernel_refusal``)."""
    if not arch.index_top_k or "attention" not in arch.mixers:
        return None
    run = _run_of(mesh, arch, causal=True)
    return {
        "index": float(dsa.index_kernel_refusal(
            t, arch.index_heads, arch.index_dim, run.interpret) is None),
        "align": float(dsa.align_kernel_refusal(
            t, run.heads_local, run.kv_heads_local, arch.head_dim,
            run.interpret) is None)}


def ce_grad_in_forward(arch: Arch, loss_chunks: int | None,
                       head_sharded: bool) -> bool:
    """Whether a train step's head passes make their gradients where they
    make their logits (:func:`_ce_weighted`): a looped stack's always,
    another's when chunked against a replicated head (an unchunked pass
    and a vocab-sharded head leave them to AD)."""
    return arch.loop_steps > 1 or (
        not head_sharded and _n_chunks(loss_chunks) > 1)


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


#: named selective-remat policies for ``jax.checkpoint`` around each
#: block: "dots" saves matmul outputs and recomputes the cheap
#: elementwise chain (the usual sweet spot); "dots_no_batch" saves only
#: non-batch dots (layernorm stats etc. recompute); "nothing" is full
#: recompute — the maximum-memory-savings end of the dial
_REMAT_POLICIES = {
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch":
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "nothing": jax.checkpoint_policies.nothing_saveable,
}


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
    float32 chains).  A looped stack reads this one cast in every loop
    step."""
    out = jax.tree.map(lambda w: w.astype(cdt), ps)
    layers = list(zip(ps["blocks"], out["blocks"]))
    if arch.mtp:
        layers.append((ps["mtp"]["block"], out["mtp"]["block"]))
    for master, cast in layers:
        if "ew3" in master:                # a routed layer's, no other's
            for k in ("gate", "ebias", "ew1", "ew3", "ew2"):
                if k in master:
                    cast[k] = master[k]
        if "ssm_a_log" in master:
            cast.update({k: master[k] for k in ssm.F32_LEAVES})
    if arch.exit_gate:                     # its product is taken in f32
        out.update({k: ps[k] for k in ("exit_w", "exit_b")})
    return out


def _head_of(ps, arch: Arch):
    """The ``(d, vocab)`` matrix the logits are read against."""
    return ps["emb"].T if arch.tied else ps["head"]


#: prefixes of the stats that come in the loss's own convention (reduced
#: and scaled as the loss is): the loss's named terms (``loss_main``,
#: ``loss_mtp`` of a stack with an MTP module, ``loss_index`` of one with
#: an indexer) and a looped stack's means
#: over tokens (``loop_exit_step_mean``, ``loop_exit_entropy``,
#: ``loop_loss_step<r>``)
_TERM_PREFIXES = ("loss_", "loop_")


def _sum_stats(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in {**a, **b}}


#: what a checkpointed layer keeps whatever the memory (:func:`_loop_saves`)
_KEPT_ALWAYS = ("attn_qkv", "sub_out", "ssm_y", "ssm_state")

#: what it keeps beside them where the device has room for all the layers'
#: (:func:`checkpoint_plan`), in the order of time saved a byte kept: the
#: SwiGLU's two wide products and a state-space layer's input projection
#: (a product made again costs about 12 ms a GiB of its result on a v5e),
#: then the convolution's float32 sum (elementwise: about 10 ms a GiB)
_KEPT_IF_ROOM = ("glu_wide", "ssm_in", "ssm_conv_sum")

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
    ``(tokens, ff)``, 12 % of a layer's operations).  It is also the least
    a layer of a stack with state-space layers keeps (:func:`_block_fn`):
    of such a layer the scan's output and each chunk's opening state too
    (``ssm_y``, ``ssm_state``: the scan's forward pass is not run a second
    time; its backward pass makes a chunk's decay and score matrices again,
    ``parallel/ssm.py``), with the wide input projection and its split, the
    convolution, the gate and the gated norm made again: a layer holds five
    or six arrays of ``(tokens, d)`` and its chunk states where it would
    hold ``(tokens, 8.5 d)`` of them.  What such a stack keeps beside this
    list follows the memory: :func:`checkpoint_plan`, :func:`_saves`."""
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
    layers (:func:`_block_fn`)."""
    return arch.loop_steps > 1 or "mamba" in arch.mixers


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
      layer's input, ``sub_out`` twice, q, k, v and the kernel's output and
      rows of an attention layer, ``ssm_y`` and ``ssm_state`` of a
      state-space layer), and a looped stack's outputs;
    - one layer's backward pass at work: six arrays of its widest
      activation in the compute dtype (a SwiGLU's two products, their
      gated product and the three gradients), and of a looped stack the
      layer's kept arrays once more (cut from the scan's stack as copies);
    - the head pass: one chunk's float32 logits and their gradient in the
      compute dtype (a looped stack's passes are one call of ``loop_steps``
      times the chunks), and the gradient to the stack's output it leaves
      behind, float32 and its copy in the compute dtype, a loop step each
      (:func:`_ce_weighted` makes it where it makes the logits).

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
        layer = 3 * act * d
        if mixer == "mamba":
            inner = arch.ssm_heads * arch.ssm_head_dim
            chunks = -(-tokens // arch.ssm_chunk)
            layer += act * inner + chunks * inner * arch.ssm_state * 4
            wide = ssm.in_width(arch.ssm_heads, arch.ssm_head_dim,
                                arch.ssm_state)
        elif mixer in ("attention", "latent"):
            qo, kv = arch.heads * arch.head_dim, arch.kv_heads * arch.head_dim
            layer += act * (2 * qo + 2 * kv) + tokens * arch.heads * 4
            wide = qo
        else:
            wide = 3 * d
        kept += layer
        if ffn == "glu":
            wide = max(wide, arch.ff)
        working = max(working, 6 * act * wide +
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
    keeps it: ``tokens x width x itemsize x layers that have it`` (the
    convolution's sum is float32 whatever the compute dtype)."""
    glu = sum(f == "glu" for f in arch.ffns)
    mamba = sum(m == "mamba" for m in arch.mixers)
    inner = arch.ssm_heads * arch.ssm_head_dim
    return {
        "glu_wide": tokens * 2 * arch.ff * itemsize * glu,
        "ssm_in": tokens * itemsize * mamba * ssm.in_width(
            arch.ssm_heads, arch.ssm_head_dim, arch.ssm_state),
        "ssm_conv_sum": tokens * (inner + 2 * arch.ssm_state) * 4 * mamba}


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


def _block_fn(remat: bool, remat_policy: str | None, arch: Arch,
              kept: tuple = ()):
    """:func:`_block`, or its checkpointed form.  A looped stack holds
    ``loop_steps`` times the activations its weights suggest, so it always
    recomputes, the cheapest things first (:func:`_loop_saves`), and that
    is its one recomputation path: the two keywords are refused there.  A
    stack with state-space layers recomputes by the same policy unless a
    keyword says otherwise (a layer's wide arrays are 8.5 ``d`` a token),
    and keeps the names ``kept`` beside the policy's own: what
    :func:`checkpoint_plan` found room for."""
    if remat or remat_policy:
        if arch.loop_steps > 1:
            raise ValueError("remat / remat_policy: a looped stack always "
                             "recomputes by _loop_saves")
        pol = _REMAT_POLICIES[remat_policy] if remat_policy else None
    elif _recomputes_by_policy(arch):
        pol = _saves(kept)
    else:
        return _block
    return jax.checkpoint(_block, policy=pol, static_argnums=(2, 3, 4))


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
                    remat: bool = False,
                    remat_policy: str | None = None, kept: tuple = ()):
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
    blk = _block_fn(remat, remat_policy, arch, kept)
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
    into their mean over the layers."""
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
                remat: bool = False,
                loss_chunks: int | None = None,
                head_sharded: bool = False,
                remat_policy: str | None = None,
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
    # a keyword's policy is the caller's: it keeps what that policy says
    kept = () if remat or remat_policy else _report_plan(
        arch, tokens.size, jnp.dtype(cdt).itemsize, run.hbm_limit,
        loss_chunks)
    if arch.loop_steps > 1:
        return _forward_loop_ce(ps, tokens, labels, mask, arch, run, cdt,
                                _block_fn(remat, remat_policy, arch, kept),
                                loss_chunks, reduce)
    x, aux_term, ps, stats = _forward_hidden(
        ps, tokens, arch, run, cdt, remat=remat, remat_policy=remat_policy,
        kept=kept)
    head = _head_of(ps, arch)
    with _probe.scope("ce"):
        loss = _ce_from_hidden(x, head, labels, mask, aux_term, loss_chunks,
                               head_sharded, reduce)
    if arch.mtp:
        # position i reads token i+1 (its label) and predicts token i+2,
        # the next position's label; the last position has none
        y, aux, st = _mtp_hidden(ps, x, labels, arch, run,
                                 _block_fn(remat, remat_policy, arch, kept))
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


def _ce_from_hidden(x, head, labels, mask, aux_term, loss_chunks,
                    head_sharded, reduce, skip_last: bool = False):
    """Head matmul + masked CE over the hidden states, normalised and
    (``reduce``) summed over the data x seq shards: the tail of
    :func:`_forward_ce`.  ``skip_last`` leaves each row's last position
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
    if head_sharded:
        nll = _ce_token_nll_sum(x, labels, _vshard_chunk_nll(head),
                                n_chunks, mvec)
    elif n_chunks > 1:
        nll, _ = _ce_weighted(
            x.reshape(-1, x.shape[-1]), head, labels.reshape(-1),
            _token_weights(mvec, *labels.shape), n_chunks)
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


def _run_of(mesh: Mesh, arch: Arch, causal: bool,
            vocab_sharded: int | None = None, moe_aux_weight: float = 0.0,
            moe_zloss_weight: float = 0.0) -> _Run:
    """The step build's snapshot: local head counts on this mesh (or a
    refusal) and the attention core the config and the mesh allow."""
    heads_local, kv_local = _check_tp(mesh, arch, vocab_sharded)
    from znicz_tpu.core.config import root as root_cfg
    interp = bool(root_cfg.common.engine.get("pallas_interpret", False))
    return _Run(heads_local, kv_local, causal,
                use_flash=_flash_eligible(mesh, interp), interpret=interp,
                use_ring_flash=_ring_flash_eligible(mesh, interp),
                moe_aux_weight=float(moe_aux_weight),
                moe_zloss_weight=float(moe_zloss_weight),
                hbm_limit=_memory_limit(mesh))


def make_train_step(mesh: Mesh, arch, d=None, heads=None, ff=None,
                    vocab=None, lr: float = 0.1, causal: bool = True,
                    compute_dtype=None, shard_update: bool = False,
                    shard_params: bool = False,
                    masked: bool = False, donate: bool = False,
                    remat: bool = False, loss_chunks: int | None = None,
                    head_sharded: bool = False,
                    n_experts: int | None = None,
                    moe_aux_weight: float = 0.0,
                    moe_top_k: int = 1,
                    remat_policy: str | None = None,
                    moe_zloss_weight: float = 0.0,
                    quantized_collectives: dict | None = None,
                    stats: bool = False):
    """-> jitted ``step(params, tokens, labels) -> (params, loss)``
    (``masked=True``: ``step(params, tokens, labels, mask)`` with a
    per-row bool mask — padded loader rows train nothing;
    ``stats=True``: ``-> (params, loss, stats)`` with the routed expert
    layers' counters of the step, the count of attention layers that ran
    a flash kernel and of those whose kernels read the layer's layout
    (``attn_flash``, ``attn_direct``), of a stack with an MTP module the
    loss's two terms (``loss_main``, ``loss_mtp``, unweighted) and of a
    looped stack its exit distribution's means and each loop step's own
    cross-entropy (``loop_*``, :func:`_forward_loop_ce`), float32 scalars,
    an empty dict for a stack that has none).

    ``arch`` says what the stack is (:func:`as_arch`): an :class:`Arch`,
    a model's configuration mapping, or, as ever, the GPT-shaped block's
    ``n_layers`` followed by ``d, heads, ff, vocab``.

    ``donate=True`` donates the params buffers to the step (the training
    loop's natural contract — the caller rebinds; the old pytree is dead
    after the call), halving parameter HBM traffic.  ``remat=True``
    wraps each block in ``jax.checkpoint``: backward recomputes block
    activations instead of saving them — the standard long-context
    trade (HBM for FLOPs) once t grows past what activations fit;
    ``remat_policy`` ("dots" | "dots_no_batch" | "nothing") selects a
    SELECTIVE checkpoint policy instead of the all-or-nothing default
    (implies remat when set).  A looped stack refuses both: it always
    recomputes by :func:`_loop_saves`.
    ``loss_chunks=k`` computes the CE loss k token-chunks at a time
    (:func:`_ce_weighted`) so the ``(tokens, vocab)`` f32 logits never
    materialize — the dominant HBM stream when vocab ≫ d — and nothing
    is recomputed: each chunk's pass makes its logits, ``dlogits`` and
    both gradient products (three products with the vocabulary axis),
    and the backward pass scales the results by the loss's cotangent.
    Loss differs from the dense path only in summation order (~1 ulp);
    the dense default keeps historical pins bit-stable.
    ``head_sharded=True`` vocab-shards the LM head over ``model`` and
    computes the CE with Megatron parallel cross-entropy
    (:func:`_vshard_chunk_nll`): head memory, the head GEMM, and its
    gradient all divide by tp, at the cost of one pmax + two psums per
    chunk; composes with ``loss_chunks``.  Requires ``vocab % tp == 0``.
    ``n_experts=E`` swaps every block's dense FFN for a top-1
    expert-parallel MoE FFN with the E experts sharded over ``model``
    (parallel/moe.py; requires ``E % tp == 0``; pass matching
    ``init_params(..., n_experts=E)`` params).  ``moe_aux_weight``
    adds the switch-transformer load-balance aux (arXiv:2101.03961
    eq. 4, summed over blocks) to the TRAINING loss — without it top-1
    routing tends to collapse onto few experts; eval losses stay pure
    CE.  ``moe_top_k=k`` routes each token to its k best experts with
    GShard-renormalized gate weights (k=1 is switch routing).
    ``moe_zloss_weight`` adds the ST-MoE router z-loss
    (arXiv:2202.08906 eq. 5) — penalizes router-logit drift, the bf16
    MoE instability the balance aux does not catch; training loss
    only, like the balance aux.

    ``tokens``/``labels``: int32 ``(batch, time)``, batch sharded over
    ``data`` and time over ``seq``; per-position class targets (CE loss).

    Mixed precision follows the FusedTrainStep recipe: master params and
    the SGD update stay f32; the forward casts params + activations to
    ``compute_dtype`` (bf16 on accelerators, see
    :func:`_default_compute_dtype`), and the loss/log-softmax runs f32.
    AD transposes the casts, so gradients land f32 on the masters.

    ``shard_update`` applies the ZeRO-style cross-replica update split
    (arXiv:2004.13336) to the REPLICATED leaves (embeddings, head,
    layernorms): each data-axis replica updates a 1/n slice and the
    slices reassemble through a psum.  NOTE the honest scope: this step
    is stateless SGD, so there is no optimizer-state memory to shard —
    the split divides the update COMPUTE and pins the numerics the
    fused step's stateful shard_update (parallel/step.py, where the
    ZeRO-1 memory win is real) must match.  Tensor-sharded leaves
    already live partitioned and update locally.

    ``shard_params`` (ISSUE 15) goes further: the replicated leaves
    PERSIST flat-sharded over ``data`` between steps — per-chip
    parameter memory for those leaves is 1/n — and full weights
    materialize on demand through the per-leaf all-gather chain
    (zero.gather_chain) ahead of each forward; the update applies on
    the local slice and the post-update regather disappears.  Params
    must arrive in the :func:`shard_params_host` layout and the
    returned specs are :func:`shard_params_specs`; read results back
    with :func:`unshard_params_host`.  Subsumes (and refuses to compose
    with) ``shard_update``.

    ``quantized_collectives`` (ISSUE 18; ``None`` defers to the
    ``engine.quantized_collectives`` config) ships the gradient
    reduction and the shard_params regather chain quantized
    (parallel/qcomm.py): the loss differentiates LOCALLY and ALL grads
    reduce through one explicit quantized psum over ``("data", "seq")``,
    while the reported loss scalar still reduces exactly.  NOTE the
    reduction semantics: the exact path's grads come from AD's
    psum-transpose of the reduced loss, which applies each batch
    shard's OWN gradient to its replica; the quantized path's explicit
    psum applies the true batch-mean gradient instead — on a
    ``model=1`` mesh its trajectory matches a single-device full-batch
    run to within codec noise (pinned in the flag fuzz), where the
    exact path's does not.  The two paths therefore track each other
    within a band, not bitwise.  No error feedback here: the step is
    stateless (pure ``(params, batch) -> params``), so there is no
    residual carry; prefer bf16 mode or the fused step for EF-grade
    convergence.  mode=off builds today's program bit for bit.
    """
    if shard_params and shard_update:
        raise ValueError(
            "shard_params subsumes shard_update (replicated leaves "
            "persist sharded and update in place — there is no "
            "regather left to split); pass only one")
    arch = as_arch(arch, d, heads, ff, vocab, n_experts, moe_top_k)
    if remat_policy is not None and remat_policy not in _REMAT_POLICIES:
        raise ValueError(f"remat_policy={remat_policy!r} — choose from "
                         f"{sorted(_REMAT_POLICIES)}")
    run = _run_of(mesh, arch, causal, arch.vocab if head_sharded else None,
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
                               remat=remat, loss_chunks=loss_chunks,
                               head_sharded=head_sharded,
                               remat_policy=remat_policy,
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
                   vocab=None, causal: bool = True, compute_dtype=None,
                   masked: bool = False, loss_chunks: int | None = None,
                   head_sharded: bool = False,
                   n_experts: int | None = None,
                   moe_top_k: int = 1):
    """-> jitted ``eval_loss(params, tokens, labels[, mask]) -> loss`` —
    the train step's forward + CE loss (the SHARED ``_forward_ce`` body,
    so the numerics cannot drift) with no update: validation/test
    passes."""
    arch = as_arch(arch, d, heads, ff, vocab, n_experts, moe_top_k)
    run = _run_of(mesh, arch, causal, arch.vocab if head_sharded else None)
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
                   vocab=None, causal: bool = True, compute_dtype=None,
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
    run = _run_of(mesh, arch, causal)
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


# -- dp x pipe x expert configuration ---------------------------------------
def init_moe_pipeline_params(gen, n_stages: int, d: int, ff: int,
                             n_experts: int):
    """Stage-stacked MoE-block params (leading dim = pipe stage)."""
    def w(shape, scale=None):
        scale = scale or 1.0 / np.sqrt(shape[-2])
        return gen.normal(0.0, scale, shape).astype(np.float32)

    return {
        "gate": w((n_stages, d, n_experts)),
        "w1": w((n_stages, n_experts, d, ff)),
        "b1": np.zeros((n_stages, n_experts, ff), np.float32),
        "w2": w((n_stages, n_experts, ff, d)),
        "b2": np.zeros((n_stages, n_experts, d), np.float32),
    }


def moe_pipeline_specs():
    return {k: P("pipe", "expert") if k != "gate" else P("pipe")
            for k in ("gate", "w1", "b1", "w2", "b2")}


def make_pipeline_step(mesh: Mesh, n_experts: int, lr: float = 0.05,
                       compute_dtype=None):
    """-> jitted ``step(params, xs, ys) -> (params, loss)`` on a
    ``(data, pipe, expert)`` mesh: each pipe stage is an expert-parallel
    MoE residual block; xs ``(n_micro, mb, d)`` microbatches (data-sharded
    on mb), ys same shape (regression targets — keeps the demo loss
    self-contained).  Feature/ff sizes flow from the params pytree.
    Mixed precision follows the same recipe as make_train_step: bf16
    compute on accelerators, f32 masters/updates, f32 loss."""
    n_stages = mesh.shape["pipe"]
    ep = mesh.shape["expert"]
    if n_experts % ep:
        raise ValueError(f"expert-axis size {ep} must divide "
                         f"n_experts={n_experts}")
    specs = moe_pipeline_specs()
    cdt = _default_compute_dtype(compute_dtype)

    def stage_fn(p, x):
        y, _ = moe_ffn(x, p["gate"][0], p["w1"][0], p["b1"][0],
                       p["w2"][0], p["b2"][0], jax.nn.gelu, "expert")
        return x + y

    def local_step(params, xs, ys):
        def loss_fn(ps):
            ps = jax.tree.map(lambda w: w.astype(cdt), ps)
            out = pipeline_apply(
                lambda _unused, x: stage_fn(ps, x), None,
                xs.astype(cdt), n_stages, "pipe")
            diff = out.astype(jnp.float32) - ys
            return lax.psum((diff * diff).mean(), "data")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        n_data = lax.psum(1, "data")
        new_params = jax.tree.map(
            lambda w, g: w - lr * g / n_data, params, grads)
        return new_params, loss / n_data

    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P(None, "data"), P(None, "data")),
        out_specs=(specs, P()))
    return jax.jit(step), specs
