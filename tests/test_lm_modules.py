"""The language-model path's modules and what holds them together: the
imports each module of ``znicz_tpu/parallel/`` may make (arrows one way: the
description, the params, the layers, the head pass and the memory plan under
the step that is built from them), a server that loads the description and
the layers without the train step, and ``transformer.step_choices`` held to
the step it describes, read off the traced step."""

import ast
import importlib
import os
import re
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from test_lfm2_arch import _pallas_interpret
from znicz_tpu.ops.pallas import (attention as pattn, dsa as pdsa,
                                  grouped as pgrouped, kda_delta as pdelta,
                                  sconv as psconv,
                                  ssd as pssd, ssm_conv as pconv,
                                  ssm_gate as pgate)
from znicz_tpu.parallel import plan, transformer as tfm
from znicz_tpu.parallel.mesh import make_mesh
from znicz_tpu.parallel.params import param_shapes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARALLEL = os.path.join(REPO, "znicz_tpu", "parallel")

#: module -> the modules of ``znicz_tpu`` it may import, by prefix (the
#: standard library, numpy and jax are everyone's)
MAY_IMPORT = {
    "arch": ("znicz_tpu.core",),
    "params": ("znicz_tpu.parallel.arch",),
    "blocks": ("znicz_tpu.parallel.arch", "znicz_tpu.parallel.dsa",
               "znicz_tpu.parallel.ssm", "znicz_tpu.parallel.kda",
               "znicz_tpu.parallel.moe",
               "znicz_tpu.parallel.tp", "znicz_tpu.parallel.ring_attention",
               "znicz_tpu.observe.probe", "znicz_tpu.ops.pallas"),
    "head": ("znicz_tpu.parallel.arch",),
    # the delta-rule layer borrows the state-space layer's convolution and
    # its choice between the two forms; its rule's kernels are its own
    "kda": ("znicz_tpu.parallel.ssm", "znicz_tpu.observe.probe",
            "znicz_tpu.ops.pallas"),
    # ``head`` for the one reading of ``loss_chunks`` (``_n_chunks``),
    # ``moe`` for the rows of a routed layer's compact pairs buffer
    "plan": ("znicz_tpu.parallel.arch", "znicz_tpu.parallel.params",
             "znicz_tpu.parallel.head", "znicz_tpu.parallel.moe"),
    "transformer": (
        "znicz_tpu.parallel.arch", "znicz_tpu.parallel.params",
        "znicz_tpu.parallel.blocks", "znicz_tpu.parallel.head",
        "znicz_tpu.parallel.plan", "znicz_tpu.parallel.dsa",
        "znicz_tpu.parallel.ssm", "znicz_tpu.parallel.kda",
        "znicz_tpu.parallel.moe",
        "znicz_tpu.parallel.compat", "znicz_tpu.parallel.qcomm",
        "znicz_tpu.parallel.zero", "znicz_tpu.observe.probe",
        "znicz_tpu.ops.pallas", "znicz_tpu.core.config"),
}
#: what ``ops/pallas`` may be imported inside a function only
INSIDE_FUNCTIONS_ONLY = "znicz_tpu.ops.pallas"


def _imports(path: str):
    """``(module named, at module level)`` of every import statement."""
    with open(path) as f:
        tree = ast.parse(f.read())
    top = set(tree.body)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node in top
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "relative import"
            for alias in node.names:
                # ``from znicz_tpu.parallel import dsa`` names a module
                yield f"{node.module}.{alias.name}", node in top


@pytest.mark.parametrize("module", list(MAY_IMPORT))
def test_a_module_imports_what_its_row_of_the_table_allows(module):
    allowed = MAY_IMPORT[module]
    for name, at_top in _imports(os.path.join(PARALLEL, module + ".py")):
        if not name.startswith("znicz_tpu"):
            assert name.split(".")[0] in (
                "__future__", "dataclasses", "functools", "logging", "math",
                "collections", "numpy", "jax"), name
            continue
        assert any(name == a or name.startswith(a + ".") for a in allowed), \
            f"parallel/{module}.py imports {name}"
        if name.startswith(INSIDE_FUNCTIONS_ONLY):
            assert not at_top, f"parallel/{module}.py: {name} at the top"
    if module == "arch":            # the description stands alone
        loaded = subprocess.run(
            [sys.executable, "-c",
             "import sys; import znicz_tpu.parallel.arch; "
             "print([m for m in sys.modules if m.startswith("
             "('znicz_tpu.ops', 'znicz_tpu.observe'))])"],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert loaded.returncode == 0, loaded.stderr
        # the package's own __init__ loads the fused step; arch adds nothing
        assert "znicz_tpu.ops.pallas" not in loaded.stdout


def test_a_server_loads_the_description_and_the_layers_not_the_step():
    """A ``KVDecoder`` built and run on the CPU in a process of its own:
    the train step's module, the memory plan and the head pass stay
    unloaded."""
    code = """
import sys
import numpy as np
from znicz_tpu.parallel.params import init_params
from znicz_tpu.serve.kvcache import KVDecoder
params = init_params(np.random.default_rng(0), 1, 16, 2, 32, 11)
dec = KVDecoder(params, heads=2, max_len=16)
assert len(dec.generate([1, 2, 3], 2)) == 2
print(sorted(m for m in sys.modules if m.startswith("znicz_tpu.parallel.")))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=REPO,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    loaded = out.stdout.strip().splitlines()[-1]
    assert "znicz_tpu.parallel.arch" in loaded
    assert "znicz_tpu.parallel.blocks" in loaded
    for module in ("transformer", "plan", "head"):
        assert f"znicz_tpu.parallel.{module}'" not in loaded, loaded


# -- step_choices against the step it describes -------------------------------

def _tiny(family: str, wide: bool):
    """A family's tiny architecture (its test file's ``TINY``); ``wide``:
    at a head of 128 where the family's attention takes one, the widths the
    kernels accept."""
    module = importlib.import_module({
        "lfm2_moe": "test_lfm2_arch", "glm4_moe_lite":
        "test_glm4_moe_lite_arch", "ouro": "test_ouro_arch",
        "KeyeVL2": "test_keye_vl2_arch",
        "granitemoehybrid": "test_granitemoehybrid_arch",
        "nemotron_h": "test_nemotron_h_arch",
        "afmoe": "test_afmoe_arch",
        "solar_open2": "test_solar_open2_arch"}[family])
    over = {}
    if wide and family == "KeyeVL2":
        over = {"hidden_size": 64, "head_dim": 128, "num_attention_heads": 2,
                "num_key_value_heads": 1, "num_hidden_layers": 1,
                "rope_scaling": {"mrope_section": [16, 24, 24],
                                 "rope_type": "default", "type": "default"}}
    elif wide and family == "nemotron_h":
        # experts 192 wide: over 128 lanes and not a multiple of them; two
        # groups of eight heads, a state and a chunk of 128: the scan's
        # kernels' shape
        over = {"hidden_size": 128, "num_attention_heads": 2,
                "num_key_value_heads": 1, "head_dim": 128,
                "moe_intermediate_size": 192,
                "moe_shared_expert_intermediate_size": 256,
                "mamba_num_heads": 16, "mamba_head_dim": 16,
                "ssm_state_size": 128, "n_groups": 2, "chunk_size": 128}
    elif wide and family == "granitemoehybrid":
        over = {"mamba_n_heads": 8, "mamba_d_head": 16, "mamba_expand": 4,
                "mamba_d_state": 128, "mamba_chunk_size": 128}
    elif wide and family == "afmoe":
        # a window of 160 under rows of 256: the window layers run the
        # windowed blocked kernels, the full layer the whole-row form
        over = {"hidden_size": 256, "num_attention_heads": 2,
                "num_key_value_heads": 1, "head_dim": 128,
                "sliding_window": 160, "moe_intermediate_size": 128}
    elif wide and family == "solar_open2":
        # two linear heads of one lane tile each: the convolution's
        # kernels' shape (q | k | v whole lane tiles wide) and the delta
        # rule's (a head 128 lanes)
        over = {"hidden_size": 128, "num_attention_heads": 2,
                "num_key_value_heads": 1, "head_dim": 128,
                "moe_intermediate_size": 128,
                "linear_attn_config": {"short_conv_kernel_size": 4,
                                       "head_dim": 128, "num_heads": 2,
                                       "num_kv_heads": None}}
    elif wide and family in ("ouro", "lfm2_moe"):
        over = {"hidden_size": 256, "num_attention_heads": 2,
                "num_key_value_heads": 2, "head_dim": 128}
    if family == "lfm2_moe":
        return module._arch(module._cfg(["conv", "full_attention"], 1, **over))
    if wide and family == "solar_open2":
        # ... in chunks of 128: a head a stack, the two heads one visit
        return module._arch(module._cfg(**over), chunk=128)
    return module._arch(module._cfg(**over))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for one in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(one, "jaxpr", one)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _traced(arch, batch: int, t: int, loss_chunks):
    """-> ``(the step's jaxpr as text, the policies of its checkpointed
    layers, whether an equation came out of _ce_weighted's forward rule)``."""
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])
    step, _ = tfm.make_train_step(mesh, arch, lr=0.05, stats=True,
                                  loss_chunks=loss_chunks)
    params = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32),
        param_shapes(arch), is_leaf=lambda x: isinstance(x, tuple))
    tokens = jax.ShapeDtypeStruct((batch, t), jnp.int32)
    jaxpr = jax.make_jaxpr(step)(params, tokens, tokens)
    policies, rule = set(), False
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.params.get("policy") is not None:
            policies.add(eqn.params["policy"])
        frames = eqn.source_info.traceback.frames \
            if eqn.source_info.traceback is not None else ()
        rule = rule or any(f.function_name == "_ce_weighted_fwd"
                           for f in frames)
    return str(jaxpr), policies, rule, mesh


@pytest.mark.parametrize("family", list(tfm._FAMILIES))
def test_step_choices_says_what_the_traced_step_does(family, monkeypatch):
    """On the CPU as it is (every decider refuses its kernel, no memory
    limit is reported, the head pass unchunked) and with the kernels
    interpreted at 256 positions, a device that reports 64 GiB and a
    chunked head pass: a kernel's name stands in the step exactly where its
    share reads 1.0 (its tile's rows are not 0), the checkpointed layers'
    policy keeps exactly the plan's names, and ``_ce_weighted``'s forward
    rule ran exactly where ``ce_grad_in_forward_share`` is 1.0."""
    seen = set()
    for interpret, limit, t, chunks in ((False, None, 16, None),
                                        (True, 64 * 2 ** 30, 256, 2)):
        monkeypatch.setattr(tfm, "_memory_limit", lambda mesh: limit)
        jax.clear_caches()
        arch = _tiny(family, wide=interpret)
        with _pallas_interpret(interpret):
            text, policies, rule, mesh = _traced(arch, 1, t, chunks)
            chose = tfm.step_choices(mesh, arch, 1, t, chunks)
        # the head pass
        assert rule == (chose["ce_grad_in_forward_share"] == 1.0)
        assert chose["ce_grad_in_forward_share"] == float(
            bool(chunks) or arch.loop_steps > 1)
        # the attention kernels' form and tiles
        rows = chose["attn_kvb_block_rows"]
        assert set(rows) == {"fwd", "dkv", "dq"}
        blocked = pattn.KVB_SEL_KERNEL_NAMES if arch.index_top_k else {
            "fwd": pattn.KVB_FWD_KERNEL_NAME,
            "dkv": pattn.KVB_DKV_KERNEL_NAME, "dq": pattn.KVB_DQ_KERNEL_NAME}
        for name, kernel in blocked.items():
            assert bool(re.search(rf"name={kernel}\b", text)) == \
                bool(rows[name]), (name, rows)
        # an indexer's kernels
        for key, kernels in (
                ("dsa_index_kernel_share", (pdsa.INDEX_SCORES_KERNEL_NAME,
                                            pdsa.INDEX_GRADS_KERNEL_NAME)),
                ("dsa_align_kernel_share", (pdsa.ALIGN_KERNEL_NAME,))):
            assert (chose[key] is None) == (not arch.index_top_k)
            for kernel in kernels:
                assert (kernel in text) == (chose[key] == 1.0), (key, kernel)
        # the grouped products' form
        assert (chose["moe_gmm_kernel_share"] is None) == \
            (not arch.routed_layers())
        for kernel in (pgrouped.ROWS_KERNEL_NAME,
                       pgrouped.ROWS_T_KERNEL_NAME,
                       pgrouped.WEIGHTS_KERNEL_NAME):
            assert (kernel in text) == (chose["moe_gmm_kernel_share"] == 1.0)
        # the scan's form
        assert (chose["ssm_scan_kernel_share"] is None) == \
            ("mamba" not in arch.mixers)
        for kernel in (pssd.FWD_KERNEL_NAME, pssd.BWD_KERNEL_NAME):
            assert (kernel in text) == (chose["ssm_scan_kernel_share"] == 1.0)
        # the convolution's form, a state-space layer's or a delta-rule
        # layer's (the same kernels)
        assert (chose["ssm_conv_kernel_share"] is None) == \
            ("mamba" not in arch.mixers)
        assert (chose["kda_conv_kernel_share"] is None) == \
            ("kda" not in arch.mixers)
        for kernel in (pconv.FWD_KERNEL_NAME, pconv.BWD_KERNEL_NAME):
            assert (kernel in text) == (1.0 in (
                chose["ssm_conv_kernel_share"],
                chose["kda_conv_kernel_share"]))
        # the delta rule's form
        assert (chose["kda_delta_kernel_share"] is None) == \
            ("kda" not in arch.mixers)
        for kernel in (pdelta.FWD_KERNEL_NAME, pdelta.BWD_KERNEL_NAME):
            assert (kernel in text) == \
                (chose["kda_delta_kernel_share"] == 1.0)
        # the gate's and the gated norm's form
        assert (chose["ssm_gate_kernel_share"] is None) == \
            ("mamba" not in arch.mixers)
        for kernel in (pgate.FWD_KERNEL_NAME, pgate.BWD_KERNEL_NAME):
            assert (kernel in text) == (chose["ssm_gate_kernel_share"] == 1.0)
        # the gated short convolution's form
        assert (chose["sconv_kernel_share"] is None) == \
            ("sconv" not in arch.mixers)
        for kernel in (psconv.FWD_KERNEL_NAME, psconv.BWD_KERNEL_NAME):
            assert (kernel in text) == (chose["sconv_kernel_share"] == 1.0)
        # what a checkpointed layer keeps
        kept = chose["checkpoint_kept_bytes"]
        if plan._recomputes_by_policy(arch):
            assert policies == {plan._saves(tuple(
                name for name, size in kept.items() if size))}
        else:
            assert kept == {} and not policies
        if not interpret:            # the CPU as it is: nothing chosen
            assert not any(rows.values()) and "pallas_call" not in text
            assert not any(kept.values())
            assert chose["dsa_index_kernel_share"] in (None, 0.0)
        seen |= {key for key, value in chose.items()
                 if value == 1.0 or (isinstance(value, dict) and
                                     any(value.values()))}
    # the positive side was exercised where the family has it
    want = {"ce_grad_in_forward_share"}
    if family == "KeyeVL2":
        want |= {"attn_kvb_block_rows", "dsa_index_kernel_share",
                 "dsa_align_kernel_share"}
    if family == "granitemoehybrid":
        want |= {"checkpoint_kept_bytes", "ssm_scan_kernel_share",
                 "ssm_conv_kernel_share"}
    if family == "nemotron_h":
        want |= {"checkpoint_kept_bytes", "moe_gmm_kernel_share",
                 "ssm_scan_kernel_share", "ssm_conv_kernel_share",
                 "ssm_gate_kernel_share"}
    if family == "lfm2_moe":
        want |= {"sconv_kernel_share"}
    if family == "solar_open2":
        want |= {"checkpoint_kept_bytes", "moe_gmm_kernel_share",
                 "kda_conv_kernel_share", "kda_delta_kernel_share"}
    if family == "afmoe":
        want |= {"checkpoint_kept_bytes", "moe_gmm_kernel_share"}
        # the windowed kernels stand in the interpreted step by their own
        # names, beside the full layer's whole-row pair
        for kernel in pattn.KVB_SWA_KERNEL_NAMES.values():
            assert re.search(rf"name={kernel}\b", text)
        assert pattn.FWD_KERNEL_NAME in text
    assert want <= seen, (want, seen)
