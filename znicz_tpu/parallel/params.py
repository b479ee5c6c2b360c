"""What a stack's params pytree holds: one table of ``{leaf: shape}`` a
layer (:func:`_layer_shapes`) from which the initialiser, the partition
specs and the shapes are all read, and the host-side conversion into and out
of the flat-sharded ``shard_params`` layout.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import PartitionSpec as P

from znicz_tpu.parallel.arch import Arch, as_arch, gpt_arch


def ssm_in_width(heads: int, head_dim: int, state: int,
                 groups: int = 1) -> int:
    """Columns of a state-space layer's ``W_in``: the gate, the convolved
    ``[x | B | C]`` (``B`` and ``C`` a group), a step size a head."""
    return 2 * heads * head_dim + 2 * groups * state + heads


def _ssm_leaf_shapes(d: int, heads: int, head_dim: int, state: int,
                     taps: int, groups: int = 1) -> dict:
    """``{leaf: shape}`` of a state-space layer (``parallel/ssm.py``); the
    inner width is ``heads x head_dim``.  The gated norm's gain lies a group
    a row where there are several, as its statistic is taken."""
    inner = heads * head_dim
    conv = inner + 2 * groups * state
    return {"ssm_in": (d, ssm_in_width(heads, head_dim, state, groups)),
            "ssm_conv_k": (taps, conv), "ssm_conv_b": (conv,),
            "ssm_dt_b": (heads,), "ssm_a_log": (heads,), "ssm_d": (heads,),
            "ssm_g": (inner,) if groups == 1 else (groups, inner // groups),
            "ssm_out": (inner, d)}


def _kda_leaf_shapes(d: int, heads: int, head_dim: int, rank: int,
                     taps: int) -> dict:
    """``{leaf: shape}`` of a delta-rule linear-attention layer
    (``parallel/kda.py``): ``q | k | v`` from one product with a tap row a
    channel, the log-decay's and the gate's low-rank pairs with their
    biases, a decay rate and a ``beta`` a head, the head norm's gain."""
    inner = heads * head_dim
    return {"kda_in": (d, 3 * inner), "kda_conv_k": (taps, 3 * inner),
            "kda_f1": (d, rank), "kda_f2": (rank, inner),
            "kda_dt_b": (inner,), "kda_a_log": (heads,),
            "kda_b": (d, heads), "kda_g1": (d, rank),
            "kda_g2": (rank, inner), "kda_g_b": (inner,),
            "kda_norm_g": (head_dim,), "kda_out": (inner, d)}


def _layer_shapes(arch: Arch, i: int) -> dict:
    """``{leaf: shape}`` of layer ``i`` (``n_layers``: the MTP module's):
    the one table the initialiser, the specs and the shapes are read
    from.  A layer of one sub-layer has that sub-layer's norm alone."""
    d, hd = arch.d, arch.head_dim
    bias = arch.norm == "layer"
    mixer, ffn = arch.kinds(i)
    norms = [n for n, kind in (("ln1", mixer), ("ln2", ffn))
             if kind != "none"]
    out = {f"{n}_g": (d,) for n in norms}
    if bias:
        out.update({f"{n}_b": (d,) for n in norms})
    if arch.sandwich:
        out.update({f"{n}o_g": (d,) for n in norms})
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
        if arch.attn_gate:
            out["wg"] = (d, arch.heads * hd)
        if arch.index_top_k:
            hi, di = arch.index_heads, arch.index_dim
            out.update({"wiq": (d, hi * di), "wik": (d, di), "wiw": (d, hi),
                        "ik_g": (di,), "ik_b": (di,)})
    elif mixer == "mamba":
        out.update(_ssm_leaf_shapes(d, arch.ssm_heads, arch.ssm_head_dim,
                                    arch.ssm_state, arch.conv_taps,
                                    arch.ssm_groups))
    elif mixer == "kda":
        out.update(_kda_leaf_shapes(d, arch.kda_heads, arch.kda_head_dim,
                                    arch.kda_rank, arch.conv_taps))
    elif mixer == "sconv":
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
    elif ffn == "moe_routed":
        # the gated unit's second up-projection is what the plain form lacks
        e, f, gated = arch.experts_held, arch.moe_ff, arch.expert_form == "glu"
        out.update({"gate": (d, arch.n_experts), "ew1": (e, d, f)})
        if gated:
            out["ew3"] = (e, d, f)
        out["ew2"] = (e, f, d)
        if arch.expert_bias:
            out["ebias"] = (arch.n_experts,)
        if arch.shared_ff:
            out["sw1"] = (d, arch.shared_ff)
            if gated:
                out["sw3"] = (d, arch.shared_ff)
            out["sw2"] = (arch.shared_ff, d)
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
         "q_a_g", "kv_a_g", "enorm_g", "hnorm_g", "ik_g", "ssm_g", "ssm_d",
         "kda_norm_g")
_ZEROS = ("ln1_b", "ln2_b", "b1", "b2", "eb1", "eb2", "ebias", "exit_b",
          "ik_b", "ssm_conv_b", "kda_g_b")
#: how each leaf of the GPT-shaped block lies over the ``model`` axis
_TP_SPECS = {
    "wq": P(None, "model"), "wk": P(None, "model"), "wv": P(None, "model"),
    "wo": P("model", None), "w1": P(None, "model"), "b1": P("model"),
    "w2": P("model", None), "ew1": P("model", None, None),
    "eb1": P("model", None), "ew2": P("model", None, None),
    "eb2": P("model", None),
}


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
    its skip one, as Mamba-2 starts them; a delta-rule layer's ``kda_a_log``
    and ``kda_dt_b`` likewise, as Kimi Linear starts them."""
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
        if name in ("conv_k", "ssm_conv_k", "kda_conv_k"):
            return w(shape, 1.0 / np.sqrt(shape[0]))
        if name in ("ssm_a_log", "kda_a_log"):
            return np.log(gen.uniform(1.0, 16.0, shape)).astype(np.float32)
        if name in ("ssm_dt_b", "kda_dt_b"):
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
