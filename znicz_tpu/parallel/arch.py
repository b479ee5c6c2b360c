"""What a decoder stack IS: :class:`Arch`, the one description the
language-model path reads, the combinations it refuses, the readers that
make one from a model family's own keys (:data:`_FAMILIES`) and the names of
what a stack or a params pytree holds beyond the GPT-shaped block.  Which
layer kinds exist is decided here; nothing of ``parallel``, ``ops`` or
``observe`` is imported, so a server reads it without loading a train step.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Mapping

import numpy as np

import jax

#: names in a refusal (:meth:`Arch.mechanisms`, :func:`mechanisms_of_params`)
_SSM_MECHANISM = "state-space layer (Mamba-2)"
_KDA_MECHANISM = "delta-rule linear attention (a decay a key channel)"
_GROUPS_MECHANISM = "state-space groups (B and C a group of heads)"
_ONE_SUB_LAYER_MECHANISM = "layers of one sub-layer"
_ROUTED_MECHANISM = "routed experts (moe_routed_ffn)"
_RELU2_MECHANISM = "squared-ReLU experts (two weights, no gate)"
_WINDOW_MECHANISM = "window on the attention scores"
_SOME_ROTATED_MECHANISM = "rotary embedding on some layers only"
_ATTN_GATE_MECHANISM = "gated attention output"


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
    dispatch, no drop; with ``shared_ff`` a shared expert of that width
    that every token passes, beside it; ``expert_form`` says what an
    expert, routed or shared, is: ``"glu"``, the gated unit ``w2 (silu(x
    w1) * (x w3))``, or ``"relu2"``, the plain ``w2 relu(x w1)^2`` of two
    weights).  One of the two may be ``"none"``: the layer is then ONE
    sub-layer behind ONE norm (a ``"none"`` mixer leaves the ``ln2``
    norm and the feed-forward part, a ``"none"`` feed-forward the ``ln1``
    norm and the mixer).  ``norm`` is ``"layer"`` (gain and
    bias) or ``"rms"`` (gain); ``kv_heads < heads`` is grouped-query
    attention; ``qk_norm`` puts an RMSNorm with its own gain on each head
    of q and k; ``rope_theta`` rotates them (rotate-half, over the whole
    head; ``rope_interleaved``: the pairs are neighbours, ``(2i, 2i +
    1)``); ``final_norm`` norms the last residual stream and ``tied``
    reads the logits against the embedding matrix.  ``mtp`` adds one
    multi-token-prediction module behind the stack
    (``transformer._mtp_hidden``: a projection of the next token's
    embedding beside the last state, one more layer of the last layer's
    kinds, index ``n_layers``, a norm of its own, the model's embedding and
    head) whose cross-entropy on the second-next token joins the loss
    ``mtp_weight`` times.
    ``sandwich`` puts a second norm with its own gain on each sub-layer's
    OUTPUT, before the residual sum (written for the attention and SwiGLU
    sub-layers).  ``loop_steps`` runs the whole stack that many times over
    the same weights, the final norm closing every loop step and its
    result fed back into layer 0 (``transformer._looped``); a looped stack
    has an exit gate (``exit_gate``): a biased ``d -> 1`` reads every loop
    step's output, the gates make a distribution over the loop steps token
    by token and the loss is the steps' cross-entropies weighted by it, less
    ``exit_beta`` times its entropy (``transformer._forward_loop_ce``).
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
    ``ssm_state`` entries a head entry, ``ssm_groups`` groups of heads that
    share their ``B`` and ``C`` (and the gated norm's statistic), a
    depthwise convolution of ``conv_taps`` taps with a bias, scanned in
    chunks of ``ssm_chunk`` positions (a tile: it changes no value).  Four
    static multipliers (muP's,
    as the Granite families write them; each emits nothing at its default):
    ``embed_mult`` on the embeddings entering layer 0, ``residual_mult`` on
    every sub-layer's output before the residual sum, ``attn_mult`` the
    attention scores' scale where it is not ``1 / sqrt(head_dim)`` (the
    kernels keep their own scale; q takes ``attn_mult * sqrt(head_dim)``),
    ``logits_div`` dividing the logits (the hidden state in front of the
    head pass takes ``1 / logits_div``).
    ``window`` and ``windowed`` (a flag a layer; empty: no layer) put a
    window on an attention layer's scores: query ``i`` sees key ``j`` iff
    ``0 <= i - j < window`` (:meth:`window_of`).  ``rotated`` (a flag a
    layer; empty: every layer, where ``rope_theta`` is set) says which
    attention layers rotate q and k (:meth:`rotates`): a stack may rotate
    its window layers and leave its full ones position-free.  ``attn_gate``
    gates the attention's output, ``o = W_o (sigmoid(W_g u) * heads'
    output)``, ``u`` the layer's normed input and ``W_g`` as wide as the
    heads' output.
    A ``"kda"`` mixer is a linear-attention layer whose state follows the
    delta rule under a decay a key channel (Kimi Delta Attention,
    ``parallel/kda.py``): ``kda_heads`` heads of ``kda_head_dim`` (keys and
    values alike), a depthwise bias-free convolution of ``conv_taps`` taps on
    each of q, k and v, the log-decay and the output gate through low-rank
    pairs of ``kda_rank``, ``beta`` in (0, 2) where ``kda_neg_eigval`` (in
    (0, 1) without), the rule run in chunks of ``kda_chunk`` positions (a
    tile, a power of two: it changes no value).

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
    ssm_groups: int = 1
    expert_form: str = "glu"
    embed_mult: float = 1.0
    residual_mult: float = 1.0
    attn_mult: float | None = None
    logits_div: float = 1.0
    window: int = 0
    windowed: tuple = ()
    rotated: tuple = ()
    attn_gate: bool = False
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_rank: int = 0
    kda_neg_eigval: bool = False
    kda_chunk: int = 64

    def __post_init__(self):
        if self._scaled() and (
                self.mtp or self.loop_steps > 1 or self.index_top_k or
                not set(self.mixers) <= {"attention", "mamba"} or
                not set(self.ffns) <= {"glu", "moe_routed"} or
                self.expert_form != "glu"):
            raise ValueError("embed_mult / residual_mult / attn_mult / "
                             "logits_div: the multipliers are written for "
                             "an unlooped stack of plain or grouped-query "
                             "attention, state-space, SwiGLU and gated "
                             "routed-expert sub-layers with no indexer and "
                             "no MTP module")
        for name, flags in (("windowed", self.windowed),
                            ("rotated", self.rotated)):
            if flags and len(flags) != len(self.mixers):
                raise ValueError(f"{name}: {len(flags)} flags for "
                                 f"{len(self.mixers)} layers")
            if any(flag and mixer != "attention"
                   for flag, mixer in zip(flags, self.mixers)):
                raise ValueError(f"{name}: written for plain or "
                                 f"grouped-query attention layers")
        if any(self.windowed) != bool(self.window) or self.window < 0:
            raise ValueError(f"window {self.window} with windowed "
                             f"{self.windowed}: a window needs its layers "
                             f"and they a window of at least 1")
        if any(self.rotated) and self.rope_theta is None:
            raise ValueError("rotated layers need rope_theta")
        if (any(self.windowed) or self.attn_gate or self.rotated) and (
                self.index_top_k or self.mtp or self.loop_steps > 1):
            raise ValueError("window / rotated / attn_gate: written for an "
                             "unlooped stack with no indexer (a selection "
                             "holds its own cut) and no MTP module")
        if self.attn_gate and "latent" in self.mixers:
            raise ValueError("attn_gate: written for plain or grouped-query "
                             "attention layers")
        if "mamba" in self.mixers and not (
                self.ssm_heads > 0 and self.ssm_head_dim > 0 and
                self.ssm_state > 0 and self.conv_taps > 0 and
                self.ssm_chunk > 0):
            raise ValueError("a mamba mixer needs ssm_heads, ssm_head_dim, "
                             "ssm_state, conv_taps and ssm_chunk")
        if "kda" in self.mixers and not (
                self.kda_heads > 0 and self.kda_head_dim > 0 and
                self.kda_rank > 0 and self.conv_taps > 0 and
                self.kda_chunk > 0 and
                self.kda_chunk & (self.kda_chunk - 1) == 0):
            raise ValueError("a kda mixer needs kda_heads, kda_head_dim, "
                             "kda_rank, conv_taps and a kda_chunk that is a "
                             "power of two")
        if "kda" in self.mixers and (self.mtp or self.loop_steps > 1 or
                                     self.norm != "rms"):
            raise ValueError("a kda mixer is written for an unlooped "
                             "RMSNorm stack with no MTP module")
        if self.ssm_groups < 1 or self.ssm_heads % self.ssm_groups:
            raise ValueError(f"ssm_groups {self.ssm_groups} must divide "
                             f"ssm_heads {self.ssm_heads}")
        if self.expert_form not in ("glu", "relu2"):
            raise ValueError(f"expert_form {self.expert_form!r}: glu (the "
                             f"gated unit) or relu2 (plain squared ReLU)")
        if any(pair == ("none", "none")
               for pair in zip(self.mixers, self.ffns)):
            raise ValueError("a layer of no sub-layer: one of its mixer and "
                             "its feed-forward part may be none, not both")
        if "none" in self.mixers + self.ffns and (
                self.sandwich or self.mtp or self.loop_steps > 1 or
                self.norm != "rms"):
            raise ValueError("a layer of one sub-layer is written for an "
                             "unlooped RMSNorm stack with no sandwich norm "
                             "and no MTP module")
        if self.sandwich and not (
                set(self.mixers) <= {"attention", "latent"} and
                set(self.ffns) <= {"glu", "moe_routed"} and
                self.expert_form == "glu"):
            raise ValueError("sandwich: the second norm is written for "
                             "attention, SwiGLU and gated routed-expert "
                             "sub-layers (the routed and the shared experts' "
                             "sum behind one norm)")
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

    def window_of(self, i: int) -> int | None:
        """The window on layer ``i``'s attention scores (query ``i`` sees
        key ``j`` iff ``0 <= i - j < window``), None where it has none."""
        return self.window if self.windowed and \
            self.windowed[min(i, self.n_layers - 1)] else None

    def rotates(self, i: int) -> bool:
        """Whether layer ``i``'s attention rotates q and k."""
        if self.rope_theta is None:
            return False
        return not self.rotated or bool(
            self.rotated[min(i, self.n_layers - 1)])

    def window_layers(self) -> int:
        """Attention layers with a window on their scores."""
        return sum(bool(w) for w in self.windowed)

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
            out.append(_SSM_MECHANISM)
        if "mamba" in self.mixers and self.ssm_groups > 1:
            out.append(_GROUPS_MECHANISM)
        if "kda" in self.mixers:
            out.append(_KDA_MECHANISM)
        if "none" in self.mixers + self.ffns:
            out.append(_ONE_SUB_LAYER_MECHANISM)
        if "latent" in self.mixers:
            out.append("latent attention")
        if self.kv_heads != self.heads:
            out.append("grouped-query attention")
        if self.qk_norm:
            out.append("QK-norm")
        if self.rope_theta is not None:
            out.append("rotary embedding")
        if self.rotated and not all(
                r for r, m in zip(self.rotated, self.mixers)
                if m == "attention"):
            out.append(_SOME_ROTATED_MECHANISM)
        if self.window_layers():
            out.append(_WINDOW_MECHANISM)
        if self.attn_gate:
            out.append(_ATTN_GATE_MECHANISM)
        if self._scaled():
            out.append("static multipliers (embedding, residual, scores, "
                       "logits)")
        if self.index_top_k:
            out.append("learned sparse attention (indexer)")
        if "glu" in self.ffns:
            out.append("SwiGLU")
        if "moe_routed" in self.ffns:
            out.append(_ROUTED_MECHANISM)
        if "moe_routed" in self.ffns and self.expert_form == "relu2":
            out.append(_RELU2_MECHANISM)
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
    streams are one, so the rotation is ``blocks._rotate``'s: the sections
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
    the family's routed part beside the shared SwiGLU in ONE layer is not
    written), ``mamba_n_groups`` other than 1 (the scan takes groups; this
    family's reference walks one), a ``normalization_function`` other than
    ``rmsnorm``, a ``position_embedding_type`` other than ``nope``, an
    attention or projection bias, a convolution without its bias, an inner
    width that is not ``mamba_n_heads x mamba_d_head``, an activation other
    than silu.  ``intermediate_size`` (the experts') is not read."""
    if int(cfg.get("num_local_experts") or 0) > 0:
        raise ValueError(f"num_local_experts {cfg['num_local_experts']}: "
                         f"routed experts beside the shared SwiGLU in one "
                         f"layer are not written for this family (0 is)")
    if int(cfg.get("mamba_n_groups", 1)) != 1:
        raise ValueError(f"mamba_n_groups {cfg['mamba_n_groups']}: this "
                         f"family is read with one group (the scan takes "
                         f"groups, Arch.ssm_groups, and the nemotron_h "
                         f"reader hands them over; no reference of this "
                         f"family walks them)")
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


#: a character of ``hybrid_override_pattern`` -> that layer's (mixer, ffn)
_NEMOTRON_LAYERS = {"M": ("mamba", "none"), "*": ("attention", "none"),
                    "E": ("none", "moe_routed")}


def _nemotron_h_arch(cfg, vocab: int | None) -> Arch:
    """``nemotron_h`` (Nemotron-H / Nemotron 3: ``hybrid_override_pattern``, a
    character a layer, ``mamba_num_heads``, ``mamba_head_dim``,
    ``ssm_state_size``, ``n_groups``, ``conv_kernel``, ``chunk_size``,
    ``n_routed_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``moe_shared_expert_intermediate_size``, ``n_shared_experts``, ...): an
    RMSNorm stack whose layer is ONE sub-layer behind one norm, ``x <- x +
    f(RMSNorm(x))``: ``M`` a Mamba-2 state-space layer (``parallel/ssm.py``,
    ``n_groups`` groups, a biased convolution, an inner width of
    ``mamba_num_heads x mamba_head_dim`` whatever ``expand`` says), ``*``
    grouped-query attention with NO positional encoding (the family's
    modelling code applies none: ``rope_theta`` and
    ``partial_rotary_factor`` are not read), ``E`` sigmoid-routed experts
    selected by score plus ``e_score_correction_bias`` (one group) beside a
    shared expert of ``n_shared_experts x
    moe_shared_expert_intermediate_size``, every expert the plain ``W_down
    relu(W_up x)^2``; a final norm, the head tied or not.
    ``n_routed_experts`` is the experts held here where ``router_width``
    gives the router's published width.  Refused by name: ``-`` (dense MLP)
    layers and any other character, a pattern whose length is not
    ``num_hidden_layers``, ``n_group`` / ``topk_group`` other than 1, any of
    the four biases (``attention_bias``, ``mlp_bias``, ``mamba_proj_bias``,
    ``use_bias``), a convolution without its bias, an ``mlp_hidden_act``
    other than ``relu2``, a ``mamba_hidden_act`` other than ``silu``, a
    sliding window.  ``time_step_*`` and ``rescale_prenorm_residual`` are
    an initialiser's keys, ``intermediate_size`` the ``-`` layers' width."""
    pattern = str(cfg["hybrid_override_pattern"])
    if int(cfg.get("num_hidden_layers", len(pattern))) != len(pattern):
        raise ValueError(f"num_hidden_layers {cfg['num_hidden_layers']} "
                         f"against a hybrid_override_pattern of "
                         f"{len(pattern)} characters")
    unknown = sorted(set(pattern) - set(_NEMOTRON_LAYERS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern characters {unknown}: M (Mamba-2), E "
            f"(experts) and * (attention) are what is written"
            f"{' (- is a dense MLP layer)' if '-' in unknown else ''}")
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("n_group / topk_group: selection over one group of "
                         "experts is what is written")
    biased = [k for k in ("attention_bias", "mlp_bias", "mamba_proj_bias",
                          "use_bias") if cfg.get(k, False)]
    if biased:
        raise ValueError(f"{' / '.join(biased)}: the projections here have "
                         f"no bias")
    if not cfg.get("use_conv_bias", True):
        raise ValueError("use_conv_bias false: the state-space layer's "
                         "convolution here carries its bias")
    if cfg.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError(f"mlp_hidden_act {cfg['mlp_hidden_act']!r}: relu2 "
                         f"(the experts are W_down relu(W_up x)^2)")
    if cfg.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(f"mamba_hidden_act {cfg['mamba_hidden_act']!r}: "
                         f"silu")
    if cfg.get("sliding_window"):
        raise ValueError("sliding_window: attention here is causal over "
                         "the whole sequence")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    routed = "E" in pattern
    n_experts = int(cfg.get("router_width", cfg.get("n_routed_experts", 0)))
    first, count = _experts_held(cfg, n_experts)
    mixers, ffns = zip(*(_NEMOTRON_LAYERS[c] for c in pattern))
    return Arch(
        d=d, heads=heads, kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=int(cfg.get("head_dim") or d // heads),
        ff=int(cfg.get("intermediate_size", 0)),
        vocab=int(vocab if vocab is not None else cfg["vocab_size"]),
        mixers=mixers, ffns=ffns, norm="rms",
        eps=float(cfg.get("layer_norm_epsilon", 1e-5)),
        conv_taps=int(cfg.get("conv_kernel", 4)),
        n_experts=n_experts if routed else 0,
        experts_first=first, experts_held=count if routed else 0,
        top_k=int(cfg.get("num_experts_per_tok", 1)),
        moe_ff=int(cfg.get("moe_intermediate_size", 0)), score="sigmoid",
        expert_bias=True, norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        final_norm=True, tied=bool(cfg.get("tie_word_embeddings", False)),
        shared_ff=int(cfg.get("n_shared_experts", 0)) * int(
            cfg.get("moe_shared_expert_intermediate_size", 0)) * routed,
        ssm_heads=int(cfg["mamba_num_heads"]),
        ssm_head_dim=int(cfg["mamba_head_dim"]),
        ssm_state=int(cfg["ssm_state_size"]),
        ssm_chunk=int(cfg.get("chunk_size", 256)),
        ssm_groups=int(cfg.get("n_groups", 1)), expert_form="relu2")


#: ``layer_types`` of the ``afmoe`` family -> whether the layer has a window
_AFMOE_LAYERS = {"sliding_attention": True, "full_attention": False}


def _afmoe_arch(cfg, vocab: int | None) -> Arch:
    """``afmoe`` (Arcee's Trinity family: ``layer_types`` of
    ``sliding_attention`` and ``full_attention``, ``sliding_window``,
    ``num_dense_layers``, ``num_experts``, ``num_experts_per_tok``,
    ``num_shared_experts``, ``moe_intermediate_size``, ``score_func``,
    ``route_norm``, ``route_scale``, ``mup_enabled``, ...): an RMSNorm stack
    of four norms a layer (each sub-layer's input and its output, ``h = x +
    N2(Attn(N1 x))``, ``x' = h + N4(F(N3 h))``), grouped-query attention
    with QK-norm whose output is gated by a sigmoid of the layer's normed
    input (``attn_gate``); a ``sliding_attention`` layer rotates q and k
    (rotate-half over the whole head, ``rope_theta``) and sees
    ``sliding_window`` positions back, a ``full_attention`` layer rotates
    nothing and is causal alone; a bias-free SwiGLU of ``intermediate_size``
    in the first ``num_dense_layers`` layers, after them sigmoid-routed
    SwiGLU experts of ``moe_intermediate_size`` selected by score plus a
    selection bias (one group), their weights normalised over the selected
    (``route_norm``) times ``route_scale``, beside one shared SwiGLU of
    ``num_shared_experts x moe_intermediate_size`` (the family sums the
    width), both behind the one fourth norm; the embeddings times
    ``sqrt(hidden_size)`` where ``mup_enabled``; a final norm, the head tied
    or not.  ``num_experts`` is the experts held here where ``router_width``
    gives the router's published width.  Refused by name: a ``rope_scaling``
    that is not null, ``n_group`` / ``topk_group`` / ``num_expert_groups``
    / ``num_limited_groups`` other than 1, a ``score_func`` other than
    sigmoid, any other ``layer_types`` entry, a ``hidden_act`` other than
    silu, an attention bias, window layers without a ``sliding_window`` of at
    least 1 (the kernels take any such window, a multiple of their block or
    not).  ``load_balance_coeff`` is read by nothing: the family's modelling
    code returns no auxiliary loss and the step adds none;
    ``global_attn_every_n_layers`` is what ``layer_types`` spells out."""
    if cfg.get("rope_scaling"):
        raise ValueError(f"rope_scaling {cfg['rope_scaling']}: the rotary "
                         f"embedding is unscaled")
    grouped = [k for k in ("n_group", "topk_group", "num_expert_groups",
                           "num_limited_groups") if int(cfg.get(k, 1)) != 1]
    if grouped:
        raise ValueError(f"{' / '.join(grouped)}: selection over one group "
                         f"of experts is what is written")
    if cfg.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(f"score_func {cfg['score_func']!r}: sigmoid scores "
                         f"with normalised weights are what is written for "
                         f"these keys")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: SwiGLU")
    if cfg.get("attention_bias", False):
        raise ValueError("attention_bias: the projections here have none")
    types = list(cfg["layer_types"])
    if int(cfg.get("num_hidden_layers", len(types))) != len(types):
        raise ValueError(f"num_hidden_layers {cfg['num_hidden_layers']} "
                         f"against {len(types)} layer_types")
    unknown = sorted(set(types) - set(_AFMOE_LAYERS))
    if unknown:
        raise ValueError(f"layer_types {unknown}: sliding_attention or "
                         f"full_attention")
    windowed = tuple(_AFMOE_LAYERS[t] for t in types)
    window = int(cfg.get("sliding_window") or 0) if any(windowed) else 0
    if any(windowed) and window < 1:
        raise ValueError(f"sliding_window {cfg.get('sliding_window')!r} with "
                         f"sliding_attention layers: at least 1")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    n_dense = int(cfg.get("num_dense_layers", 0))
    n_experts = int(cfg.get("router_width", cfg.get("num_experts", 0)))
    first, count = _experts_held(cfg, n_experts)
    moe_ff = int(cfg.get("moe_intermediate_size", 0))
    ffns = tuple("glu" if i < n_dense or not n_experts else "moe_routed"
                 for i in range(len(types)))
    routed = "moe_routed" in ffns
    return Arch(
        d=d, heads=heads, kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=int(cfg.get("head_dim") or d // heads),
        ff=int(cfg["intermediate_size"]),
        vocab=int(vocab if vocab is not None else cfg["vocab_size"]),
        mixers=("attention",) * len(types), ffns=ffns, norm="rms",
        eps=float(cfg.get("rms_norm_eps", 1e-5)), qk_norm=True,
        rope_theta=float(cfg.get("rope_theta", 1e4)) if any(windowed)
        else None,
        n_experts=n_experts if routed else 0, experts_first=first,
        experts_held=count if routed else 0,
        top_k=int(cfg.get("num_experts_per_tok", 1)), moe_ff=moe_ff,
        score="sigmoid", expert_bias=True,
        norm_topk=bool(cfg.get("route_norm", True)),
        routed_scale=float(cfg.get("route_scale", 1.0)), final_norm=True,
        tied=bool(cfg.get("tie_word_embeddings", False)),
        shared_ff=int(cfg.get("num_shared_experts", 0)) * moe_ff * routed,
        sandwich=True,
        embed_mult=float(np.sqrt(d)) if cfg.get("mup_enabled", False)
        else 1.0,
        window=window, windowed=windowed, rotated=windowed, attn_gate=True)


def _solar_open2_arch(cfg, vocab: int | None) -> Arch:
    """``solar_open2`` (Upstage's Solar Open 2: ``gqa_layers`` /
    ``gqa_interval``, ``linear_attn_config`` (``num_heads``, ``head_dim``,
    ``short_conv_kernel_size``, ``num_kv_heads``), ``kda_use_full_proj``,
    ``kda_allow_neg_eigval``, ``use_rope``, ``use_gqa_gate``,
    ``first_k_dense_replace``, ``n_routed_experts``, ``n_shared_experts``,
    ``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
    ...): an RMSNorm stack of two norms a layer whose mixer is, on the layers
    ``gqa_layers`` lists, grouped-query attention with NO positional
    encoding and a sigmoid gate on the heads' output (``attn_gate``; no
    QK-norm, no bias), and on every other layer Kimi Delta Attention
    (``parallel/kda.py``: the log-decay and the gate through low-rank pairs of
    ``linear_attn_config.head_dim``, ``beta`` in (0, 2) where
    ``kda_allow_neg_eigval``); in every layer a shared SwiGLU expert of
    ``n_shared_experts x moe_intermediate_size`` (the DeepSeek family sums
    the width) beside sigmoid-routed SwiGLU experts selected by score plus a
    selection bias (the DeepSeek-V3 keys this config carries, read as
    ``glm4_moe_lite`` reads them; one group), their weights normalised over
    the selected where ``norm_topk_prob``, times ``routed_scaling_factor``; a
    final norm, the head tied or not.  ``gqa_layers`` defaults to every
    ``gqa_interval + 1``-th layer from 0.  ``n_routed_experts`` is the
    experts held here where ``router_width`` gives the router's published
    width.  Refused by name: ``use_rope`` true (and with it any
    ``partial_rotary_factor`` other than 1), ``kda_use_full_proj`` true (the
    low-rank pair is what is written), ``linear_attn_config.num_kv_heads``
    other than null or ``num_heads``, a ``short_conv_kernel_size`` under 1,
    ``first_k_dense_replace`` other than 0 (``intermediate_size``, the dense
    width, is read by nothing), ``use_gqa_gate`` false, ``n_group`` /
    ``topk_group`` other than 1, a ``scoring_func`` other than sigmoid, a
    ``hidden_act`` other than silu, an attention bias, a ``gqa_layers``
    entry outside the stack."""
    if cfg.get("use_rope", False):
        raise ValueError(
            "use_rope true: the grouped-query layers here carry no rotary "
            "embedding (the linear layers carry the order)" + (
                "; partial_rotary_factor "
                f"{cfg['partial_rotary_factor']} would rotate part of a head"
                if float(cfg.get("partial_rotary_factor", 1)) != 1 else ""))
    if cfg.get("kda_use_full_proj", False):
        raise ValueError("kda_use_full_proj true: the log-decay and the gate "
                         "through their low-rank pairs are what is written")
    if not cfg.get("use_gqa_gate", True):
        raise ValueError("use_gqa_gate false: the grouped-query layers' "
                         "gated output is what is written")
    if int(cfg.get("first_k_dense_replace", 0)) != 0:
        raise ValueError(f"first_k_dense_replace "
                         f"{cfg['first_k_dense_replace']}: every layer "
                         f"routed is what is written (intermediate_size is "
                         f"not read)")
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("n_group / topk_group: selection over one group of "
                         "experts is what is written")
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"scoring_func {cfg['scoring_func']!r}: sigmoid "
                         f"scores are what is written for these keys")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: SwiGLU")
    if cfg.get("attention_bias", False):
        raise ValueError("attention_bias: the projections here have none")
    lin = cfg["linear_attn_config"]
    k_heads, k_dim = int(lin["num_heads"]), int(lin["head_dim"])
    if lin.get("num_kv_heads") not in (None, k_heads):
        raise ValueError(f"linear_attn_config.num_kv_heads "
                         f"{lin['num_kv_heads']}: as many key/value as query "
                         f"heads ({k_heads}, or null) is what is written")
    taps = int(lin.get("short_conv_kernel_size", 4))
    if taps < 1:
        raise ValueError(f"linear_attn_config.short_conv_kernel_size {taps}: "
                         f"at least one tap")
    layers = int(cfg["num_hidden_layers"])
    full = cfg.get("gqa_layers")
    if full is None:
        full = range(0, layers, int(cfg.get("gqa_interval", 3)) + 1)
    full = sorted(int(i) for i in full)
    if full and not 0 <= full[0] <= full[-1] < layers:
        raise ValueError(f"gqa_layers {full} outside the {layers} layers")
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    n_experts = int(cfg.get("router_width", cfg.get("n_routed_experts", 0)))
    first, count = _experts_held(cfg, n_experts)
    moe_ff = int(cfg.get("moe_intermediate_size", 0))
    return Arch(
        d=d, heads=heads, kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=int(cfg.get("head_dim") or d // heads),
        ff=int(cfg.get("intermediate_size", 0)),
        vocab=int(vocab if vocab is not None else cfg["vocab_size"]),
        mixers=tuple("attention" if i in full else "kda"
                     for i in range(layers)),
        ffns=("moe_routed",) * layers, norm="rms",
        eps=float(cfg.get("rms_norm_eps", 1e-5)), conv_taps=taps,
        n_experts=n_experts, experts_first=first, experts_held=count,
        top_k=int(cfg.get("num_experts_per_tok", 1)), moe_ff=moe_ff,
        score="sigmoid", expert_bias=True,
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        final_norm=True, tied=bool(cfg.get("tie_word_embeddings", False)),
        shared_ff=int(cfg.get("n_shared_experts", 0)) * moe_ff,
        attn_gate=bool(full), kda_heads=k_heads, kda_head_dim=k_dim,
        kda_rank=k_dim, kda_neg_eigval=bool(cfg.get("kda_allow_neg_eigval",
                                                    False)))


#: ``model_type`` -> the reader of that family's keys
_FAMILIES = {"lfm2_moe": _lfm2_moe_arch, "glm4_moe_lite": _glm4_moe_lite_arch,
             "ouro": _ouro_arch, "KeyeVL2": _keye_vl2_arch,
             "granitemoehybrid": _granitemoehybrid_arch,
             "nemotron_h": _nemotron_h_arch, "afmoe": _afmoe_arch,
             "solar_open2": _solar_open2_arch}


def arch_from_config(cfg, vocab: int | None = None) -> Arch:
    """A model's own keys -> :class:`Arch`, by ``model_type``
    (:data:`_FAMILIES`: :func:`_lfm2_moe_arch`, also what a mapping with
    ``layer_types`` and no ``model_type`` is read as,
    :func:`_glm4_moe_lite_arch`, :func:`_ouro_arch`,
    :func:`_keye_vl2_arch`, :func:`_granitemoehybrid_arch`,
    :func:`_nemotron_h_arch`, :func:`_afmoe_arch` and
    :func:`_solar_open2_arch`).
    ``experts_held`` (``{"first", "count"}``; all by default) is this chip's
    share of the experts;
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
    "w3": "SwiGLU", "wkv_a": "latent attention", "sw1": "shared expert",
    "ln1o_g": "sandwich norm", "wg": _ATTN_GATE_MECHANISM,
    "wiq": "learned sparse attention (indexer)",
    "ssm_a_log": _SSM_MECHANISM, "kda_a_log": _KDA_MECHANISM,
}


def routed_block(blk) -> bool:
    """Whether a layer's leaves are a routed expert layer's: unbiased expert
    stacks (the dense-masked experts carry ``eb1``), gated or plain."""
    return "ew1" in blk and "eb1" not in blk


def _block_mechanisms(blk) -> list:
    """The names of what one layer's leaves show beyond the GPT-shaped
    block: the leaves of :data:`_LEAF_MECHANISMS`, and what only the leaves
    together say: routed experts (:func:`routed_block`), without ``ew3`` the
    plain squared-ReLU form; one
    norm's gain alone is a layer of one sub-layer; a gated norm's gain laid
    out a group (``params._ssm_leaf_shapes``) is state-space groups; fewer
    key/value than query columns grouped-query attention."""
    out = [name for leaf, name in _LEAF_MECHANISMS.items() if leaf in blk]
    if routed_block(blk):
        out.append(_ROUTED_MECHANISM)
        if "ew3" not in blk:
            out.append(_RELU2_MECHANISM)
    if ("ln1_g" in blk) != ("ln2_g" in blk):
        out.append(_ONE_SUB_LAYER_MECHANISM)
    if np.ndim(blk.get("ssm_g")) > 1:
        out.append(_GROUPS_MECHANISM)
    if "wk" in blk and np.shape(blk["wk"]) != np.shape(blk["wq"]):
        out.append("grouped-query attention")
    return out


def mechanisms_of_params(params) -> list:
    """The names (:meth:`Arch.mechanisms`) of what a params pytree holds
    beyond the GPT-shaped block, read from its leaves: what ``serve/``
    and ``export_lm`` refuse with."""
    out = []
    for blk in params["blocks"]:
        out += [name for name in _block_mechanisms(blk) if name not in out]
    if "mtp" in params:
        out.append("multi-token prediction")
    if "exit_w" in params:             # only a looped stack carries a gate
        out += ["looped stack", "exit gate"]
    if "norm_g" in params:
        out.append("final norm")
    if "head" not in params:
        out.append("tied embedding and head")
    return out

