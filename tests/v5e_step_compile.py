"""Compile one benchmark cell's train step for a TPU v5e that is described
and not attached, in a process of its own (a process that has loaded the
TPU's library disturbs the profiler's tests that run after it), and print
one JSON line: the compiled step's memory, the compiler's operation count,
the checkpoint plan a v5e's memory limit gives, the footprint the plan
reckoned with, how often each of the key/value-blocked flash kernels stands
in the compiled step (under a window and without one) and, of a stack with
delta-rule layers how often the rule's two kernels each stand in it and
which float32 arrays with two chunk-length axes do (beside a head's
channels, and at all), of a stack with state-space layers how often the
scan's,
the convolution's and the gate's two kernels each stand in the compiled
step, which float32 arrays with two chunk-length axes do, which float32
arrays as long as the tokens and as wide as the convolution's channels, and
which arrays a (token, group) a row.  ``tests/test_checkpoint_plan.py`` runs it.

    python tests/v5e_step_compile.py CONFIG TRAFFIC [LIMIT_GIB]
"""

import json
import math
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _written(text: str, shape: str) -> list:
    """The arrays of ``shape`` (a pattern) that instructions outside fused
    computations write: what crosses HBM (the convolution's float32 sum,
    its padded operand, a shifted cotangent)."""
    found, fused = set(), False
    for line in text.splitlines():
        if line.endswith("{") and " -> " in line:
            fused = "fused_computation" in line.split("(", 1)[0]
        elif not fused:
            m = re.match(rf"\s*(?:ROOT )?%?[\w.\-]+ = \(?({shape})", line)
            if m:
                found.add(m.group(1))
    return sorted(found)


def main(config: str, traffic: str, limit_gib: float = 15.75) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.plan import PLAN_MARGIN, step_footprint
    from znicz_tpu.parallel.mesh import make_mesh

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "cannot"
        return {"skip": f"no v5e:2x2 topology can be described here: {exc}"}
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           traffic + ".json")) as f:
        rows = json.load(f)
    # the step asks jax.default_backend(), which is the CPU here, and the
    # described chip reports no memory: a v5e's own answers
    tfm._flash_eligible = lambda mesh, interp: True
    # ... and the grouped products' kernels wherever their shapes take them
    from znicz_tpu.parallel import moe, ssm
    moe._kernels_eligible = lambda interpret: True
    # ... and the scan's
    ssm._kernels_eligible = lambda interpret: True
    limit = int(limit_gib * 2 ** 30)
    tfm._memory_limit = lambda mesh: limit
    opts = cfg["builders"]["lm_train_keys"]
    arch = tfm.arch_from_config({k: cfg[k] for k in opts["model_keys"]})
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, topo.devices[:1])
    step, _ = tfm.make_train_step(
        mesh, arch, lr=float(cfg["hyper"]["lr"]), masked=True, donate=True,
        loss_chunks=opts["loss_chunks"], stats=True,
        compute_dtype=jnp.bfloat16)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep),
        tfm.param_shapes(arch), is_leaf=lambda x: isinstance(x, tuple))
    b, t = int(rows["minibatch_size"]), int(rows["seq_len"])
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "seq")))
    mask = jax.ShapeDtypeStruct((b,), jnp.bool_,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(params, tok, tok, mask).compile()
    m = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    text = compiled.as_text()
    out = os.environ.get("V5E_STEP_TEXT")
    if out:
        with open(out, "w") as f:
            f.write(text)
    from znicz_tpu.ops.pallas import (attention as pattn, kda_delta, ssd,
                                      ssm_conv, ssm_gate)
    q = arch.ssm_chunk
    channels = arch.ssm_heads * arch.ssm_head_dim + \
        2 * arch.ssm_groups * arch.ssm_state

    def stands(*names):
        return {name: len(re.findall(
            rf'custom_call_target="tpu_custom_call"[^\n]*{name}', text))
            for name in names}

    return {
        "scan_kernels": stands(ssd.FWD_KERNEL_NAME, ssd.BWD_KERNEL_NAME),
        "conv_kernels": stands(ssm_conv.FWD_KERNEL_NAME,
                               ssm_conv.BWD_KERNEL_NAME),
        "gate_kernels": stands(ssm_gate.FWD_KERNEL_NAME,
                               ssm_gate.BWD_KERNEL_NAME),
        # the gated norm's group-wise view, a (token, group) a row
        "group_rows": _written(
            text, rf"\w+\[{b * t * arch.ssm_groups},\d+\]")
        if arch.ssm_groups > 1 else [],
        # the blocked flash kernels, under a window and without one
        # (``\b``: the plain names are no prefix of the windowed ones, but
        # of nothing else either)
        "attn_kernels": stands(*(rf"{name}\b" for name in (
            *pattn.KVB_SWA_KERNEL_NAMES.values(), pattn.KVB_FWD_KERNEL_NAME,
            pattn.KVB_DKV_KERNEL_NAME, pattn.KVB_DQ_KERNEL_NAME))),
        "window_layers": arch.window_layers(),
        "chunk_squares": sorted(set(re.findall(
            rf"f32\[(?:\d+,)*{q},{q}\]", text))) if q else [],
        # ... as long as a row (its digits, so the padded ones too)
        "conv_wide_f32": _written(
            text, rf"f32\[\d+,\d{{{len(str(t))},}},{channels}\]")
        if channels else [],
        "state_space_layers": arch.mixers.count("mamba"),
        # of a stack with delta-rule layers: float32 arrays with two
        # chunk-length axes beside a head's channels, every chunk's (a
        # chunk's decay matrix a channel: ``(.., t / C, C, C, K)``)
        "delta_rule_layers": arch.mixers.count("kda"),
        "delta_kernels": stands(kda_delta.FWD_KERNEL_NAME,
                                kda_delta.BWD_KERNEL_NAME),
        # ... and with two chunk-length axes at all (a chunk's scores, the
        # unit-triangular inverse)
        "delta_chunk_squares": sorted(set(re.findall(
            rf"f32\[(?:\d+,)*{arch.kda_chunk},{arch.kda_chunk}\]", text)))
        if arch.mixers.count("kda") else [],
        "chunk_channel_squares": sorted(set(re.findall(
            rf"f32\[(?:\d+,)*{t // arch.kda_chunk},{arch.kda_chunk},"
            rf"{arch.kda_chunk},{arch.kda_head_dim}\]", text)))
        if arch.mixers.count("kda") else [],
        "params": sum(math.prod(s.shape) for s in jax.tree.leaves(params)),
        "tokens": b * t,
        "argument_bytes": m.argument_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "flops": cost.get("flops"), "bytes_accessed":
            cost.get("bytes accessed"),
        "limit": limit,
        "plan": tfm.checkpoint_plan(arch, b * t, 2, limit,
                                    opts["loss_chunks"]),
        "footprint": step_footprint(arch, b * t, 2, opts["loss_chunks"]),
        "margin": PLAN_MARGIN}


if __name__ == "__main__":
    args = sys.argv[1:]
    print(json.dumps(main(args[0], args[1],
                          *(float(a) for a in args[2:3]))))
