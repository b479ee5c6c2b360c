"""The scope join's fuller row (ISSUE 50): ``probe.scope_table()`` says of
every instruction which pass it belongs to, whose scope it carries, by which
rule, what it holds and whom its work belongs to; ``probe.scope_map()`` is
the ``scope`` column of the same one parse and returns what it returned
before.  All on the CPU: hand-written HLO, small lowered programs, and the
benchmark's reader on hand-made operations."""

import importlib.util
import os
import sys

import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.observe import probe
from znicz_tpu.standard_workflow import StandardWorkflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader(name):
    """A reader of the benchmark, imported by path (the tier-1 command
    does not collect benchmark/tests)."""
    bench = os.path.join(REPO, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        f"_reader_{name}", os.path.join(bench, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the pass and the path, from one op_name -----------------------------------

NAMES = {"conv.00_c", "update", "ce", "block.attn", "block.mlp",
         "block3.ssm", "block3.ssm.gate", "block3.moe.route"}


@pytest.mark.parametrize("op_name,way,path", [
    # a checkpointed layer under a scan, jax 0.9.0: backward ...
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/block.attn/"
     "dot_general", "bwd", ("block.attn",)),
    # ... and its forward made again
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/block.attn/tanh", "remat", ("block.attn",)),
    # checkpointed without the scan
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "block.mlp/cos", "remat", ("block.mlp",)),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/block.mlp/mul", "bwd",
     ("block.mlp",)),
    # the scan's forward body, and its stacking write under no scope
    ("jit(f)/jvp()/while/body/closed_call/block.mlp/dot_general", "fwd",
     ("block.mlp",)),
    ("jit(f)/jvp()/while/body/dynamic_update_slice", "fwd", ()),
    ("jit(f)/transpose(jvp())/while/body/dynamic_slice", "bwd", ()),
    ("jit(f)/jvp(ce)/while/body/dynamic_slice", "fwd", ("ce",)),
    ("jit(f)/transpose(jvp(conv.00_c))/mul", "bwd", ("conv.00_c",)),
    ("jit(f)/jvp(conv.00_c)/inner/mul", "fwd", ("conv.00_c",)),
    # scope_bwd's literal label inside a checkpointed layer's scope
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/block3.ssm/"
     "transpose(jvp(block3.ssm.gate))/mul", "bwd",
     ("block3.ssm", "block3.ssm.gate")),
    ("jit(f)/jvp(block3.moe.route)/transpose(jvp(block3.moe.route))/sort",
     "bwd", ("block3.moe.route", "block3.moe.route")),
    ("jit(f)/transpose(jvp())/while/cond/lt", "bwd", ()),
    ("jit(f)/jvp()/while/cond/lt", "fwd", ()),
    ("jit(f)/while/body/update/sub", "fwd", ("update",)),
    ("jit(f)/jit(_threefry_split)/xor", "fwd", ()),
])
def test_the_pass_and_the_path_are_read_off_any_component(op_name, way,
                                                          path):
    assert probe.way_of(op_name) == way
    assert probe.path_of(op_name, NAMES) == path
    # the projection keeps the outermost component alone, as it stands
    kept = probe.scope_of(op_name, NAMES)
    assert (kept.rstrip(")").rsplit("(", 1)[-1] if kept else "") == \
        (path[0] if path else "")


def test_scope_bwd_writes_the_label_the_pass_is_read_from():
    import jax
    import jax.numpy as jnp

    def f(x):
        with probe.scope_bwd("tbl.hand"):
            return jnp.sin(x)

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    label = "transpose(jvp(tbl.hand))"
    assert label in text
    assert probe.way_of(f"jit(f)/{label}/sin") == "bwd"
    assert probe.path_of(f"jit(f)/{label}/sin") == ("tbl.hand",)


# -- hand-written modules ------------------------------------------------------

def _parent_parse_scopes(hlo_text, names):
    """``probe.parse_scopes`` as it stood before ISSUE 50, word for word:
    what the projection has to return."""
    module, comp = "", None
    scope, operands, opcode_of, comp_of = {}, {}, {}, {}
    fused, caller, roots, inside = {}, {}, {}, {}
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = probe._INSTRUCTION.match(line)
        if m is None:
            c = probe._COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        is_root, name, opcode = m.groups()
        op = probe._OP_NAME.search(line)
        sc = probe.scope_of(op.group(1), names) if op else ""
        if is_root:
            roots[comp] = sc
        if sc:
            inside.setdefault(comp, sc)
        for key, callee in probe._CALLED.findall(line):
            caller.setdefault(callee, name)
            if key == "calls":
                fused[name] = callee
        scope[name], opcode_of[name], comp_of[name] = sc, opcode, comp
        operands[name] = probe._OPERAND.findall(line[m.end():])
    for name, called in fused.items():
        if not scope[name]:
            scope[name] = roots.get(called) or inside.get(called, "")
    users = {}
    for name, ops in operands.items():
        for o in ops:
            if o in scope:
                users.setdefault(o, []).append(name)

    def neighbour(name):
        for n in (*users.get(name, ()), *operands.get(name, ()),
                  caller.get(comp_of[name])):
            if scope.get(n):
                return scope[n]
        return ""

    for _ in range(8):
        moved = False
        for name, sc in scope.items():
            if not sc:
                got = neighbour(name)
                if got:
                    scope[name], moved = got, True
        if not moved:
            break
    return module, {n: sc for n, sc in scope.items()
                    if opcode_of[n] not in probe.TRIVIAL_OPCODES}


def _same_projection(hlo, names):
    """Both views of one text; the map is the parent's, entry for entry."""
    module, scopes = probe.parse_scopes(hlo, names)
    module2, rows = probe.parse_scopes(hlo, names, rows=True)
    assert module == module2
    assert {n: r.scope for n, r in rows.items()} == scopes
    want = _parent_parse_scopes(hlo, names)
    assert (module, scopes) == want and list(scopes) == list(want[1])
    return rows


PRODUCT_IN_ANOTHERS_FUSION = """HloModule jit_step, is_scheduled=true

%fused_update (p0: f32[8,8], p1: f32[4,8], p2: f32[4,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[4,8]{1,0} parameter(1)
  %p2 = f32[4,8]{1,0} parameter(2)
  %dot.1 = f32[8,8]{1,0} dot(%p1, %p2), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/block3.ssm/block3.ssm.in/dot_general"}
  %mul.2 = f32[8,8]{1,0} multiply(%dot.1, %dot.1), metadata={op_name="jit(step)/update/mul"}
  ROOT %sub.3 = f32[8,8]{1,0} subtract(%p0, %mul.2), metadata={op_name="jit(step)/update/sub"}
}

%fused_two (p0: f32[4,8], p1: f32[8,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.4 = f32[4,8]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(block3.ssm)/block3.ssm.in/dot_general"}
  ROOT %dot.5 = f32[4,8]{1,0} dot(%dot.4, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(block3.mlp)/dot_general"}
}

ENTRY %main (w: f32[8,8], u: f32[4,8], d: f32[4,8]) -> (f32[8,8], f32[4,8]) {
  %w = f32[8,8]{1,0} parameter(0)
  %u = f32[4,8]{1,0} parameter(1)
  %d = f32[4,8]{1,0} parameter(2)
  %copy.6 = f32[4,8]{0,1} copy(%u)
  %multiply_subtract_fusion.7 = f32[8,8]{1,0} fusion(%w, %copy.6, %d), kind=kOutput, calls=%fused_update
  %fusion.8 = f32[4,8]{1,0} fusion(%u, %w), kind=kOutput, calls=%fused_two
  %gate.9 = f32[4,8]{1,0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/block3.ssm/block3.ssm.gate/jit(gate_fwd)/ssm_gate_fwd/pallas_call"}
  %pad.10 = f32[4,8]{1,0} pad(%gate.9, %gate.9), padding=0_0x0_0
  ROOT %tuple.11 = (f32[8,8]{1,0}, f32[4,8]{1,0}) tuple(%multiply_subtract_fusion.7, %pad.10)
}
"""
SSM_NAMES = {"update", "block3.ssm", "block3.ssm.in", "block3.ssm.gate",
             "block3.mlp"}


def test_a_fusion_that_holds_anothers_product_is_booked_by_the_work():
    rows = _same_projection(PRODUCT_IN_ANOTHERS_FUSION, SSM_NAMES)
    fusion = rows["multiply_subtract_fusion.7"]
    # the map's rule: the root's scope, forward
    assert (fusion.scope, fusion.path, fusion.way, fusion.how) == \
        ("update", ("update",), "fwd", "root")
    # the work: the one scope's product it holds, a backward one
    assert fusion.holds == "product" and fusion.mixed
    assert fusion.by_work == ("block3.ssm", "bwd") and fusion.moved
    # products of two scopes: the root's stands
    two = rows["fusion.8"]
    assert two.how == "root" and two.mixed and two.holds == "product"
    assert two.by_work == ("block3.mlp", "fwd") and not two.moved
    # a bare copy carries its consumer's scope, by that rule and no other
    lent = rows["copy.6"]
    assert (lent.scope, lent.how, lent.holds, lent.mixed) == \
        ("update", "lent", "copy", False)
    assert lent.by_work == ("update", "fwd") and not lent.moved
    # a Mosaic call with both scopes in its path, made again
    kernel = rows["gate.9"]
    assert kernel.scope == "block3.ssm"
    assert kernel.path == ("block3.ssm", "block3.ssm.gate")
    assert (kernel.way, kernel.how, kernel.holds) == \
        ("remat", "own", "kernel")
    assert kernel.by_work == ("block3.ssm", "remat")
    pad = rows["pad.10"]
    assert (pad.how, pad.holds, pad.way) == ("lent", "pad", "remat")
    # inside the fused computations every instruction has its own row
    assert rows["dot.1"].path == ("block3.ssm", "block3.ssm.in")
    assert rows["dot.1"].way == "bwd" and rows["dot.1"].how == "own"


_STACK_HEAD = """HloModule jit_step, is_scheduled=true

%fused_stack (p0: f32[4,8], p1: s32[], p2: f32[8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p2 = f32[8]{0} parameter(2)
  %bitcast.1 = f32[1,8]{1,0} bitcast(%p2), metadata={op_name="jit(step)/jvp()/while/body/broadcast_in_dim"}
  %p1 = s32[] parameter(1)
  %zero.2 = s32[] constant(0)
  ROOT %dus.3 = f32[4,8]{1,0} dynamic-update-slice(%p0, %bitcast.1, %p1, %zero.2), metadata={op_name="jit(step)/jvp()/while/body/dynamic_update_slice"}
}

%fused_read (p0: f32[4,8], p1: s32[]) -> f32[8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p1 = s32[] parameter(1)
  %zero.4 = s32[] constant(0)
  %ds.5 = f32[1,8]{1,0} dynamic-slice(%p0, %p1, %zero.4), dynamic_slice_sizes={1,8}, metadata={op_name="jit(step)/transpose(jvp())/while/body/dynamic_slice"}
  ROOT %bitcast.6 = f32[8]{0} bitcast(%ds.5), metadata={op_name="jit(step)/transpose(jvp())/while/body/squeeze"}
}

%body (c: (s32[], f32[8], f32[4,8])) -> (s32[], f32[8], f32[4,8]) {
  %c = (s32[], f32[8], f32[4,8]) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %x = f32[8]{0} get-tuple-element(%c), index=1
  %saved = f32[4,8]{1,0} get-tuple-element(%c), index=2
"""
_STACK_LINES = {
    "count": """  %one.7 = s32[] constant(1)
  %add.8 = s32[] add(%i, %one.7), metadata={op_name="jit(step)/jvp()/while/body/closed_call/block0.mlp/add"}
""",
    "attn": """  %tanh.9 = f32[8]{0} tanh(%x), metadata={op_name="jit(step)/jvp()/while/body/closed_call/block0.attn/tanh"}
""",
    "stack": """  %bitcast_dynamic-update-slice_fusion.10 = f32[4,8]{1,0} fusion(%saved, %i, %tanh.9), kind=kLoop, calls=%fused_stack, metadata={op_name="jit(step)/jvp()/while/body/dynamic_update_slice"}
""",
    "read": """  %dynamic-slice_bitcast_fusion.11 = f32[8]{0} fusion(%saved, %i), kind=kLoop, calls=%fused_read, metadata={op_name="jit(step)/transpose(jvp())/while/body/squeeze"}
""",
    "exit": """  %mul.12 = f32[8]{0} multiply(%dynamic-slice_bitcast_fusion.11, %dynamic-slice_bitcast_fusion.11), metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/loop.exit/mul"}
  %lt.17 = pred[] compare(%i, %i), direction=LT, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/loop.exit/lt"}
""",
    "mlp": """  %sin.13 = f32[8]{0} sine(%dynamic-slice_bitcast_fusion.11), metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/block0.mlp/sin"}
  %cos.14 = f32[8]{0} cosine(%dynamic-slice_bitcast_fusion.11), metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/block0.mlp/cos"}
""",
}
_STACK_TAIL = """  ROOT %out.15 = (s32[], f32[8], f32[4,8]) tuple(%add.8, %sin.13, %bitcast_dynamic-update-slice_fusion.10)
}

ENTRY %main (a: (s32[], f32[8], f32[4,8])) -> (s32[], f32[8], f32[4,8]) {
  %a = (s32[], f32[8], f32[4,8]) parameter(0)
  ROOT %while.16 = (s32[], f32[8], f32[4,8]) while(%a), condition=%body, body=%body
}
"""
LOOP_NAMES = {"block0.mlp", "block0.attn", "loop.exit"}


@pytest.mark.parametrize("order,old_stack,old_read", [
    # the map gives both the scope of whichever neighbour stands first in
    # the text: of the loop counter's first reader (what PR 35's 11-12 ms
    # were), of the slice's first reader
    (("count", "attn", "stack", "read", "exit", "mlp"),
     "block0.mlp", "loop.exit"),
    (("count", "attn", "stack", "read", "mlp", "exit"),
     "block0.mlp", "block0.mlp"),
    (("attn", "stack", "read", "exit", "count", "mlp"),
     "loop.exit", "loop.exit"),
    (("attn", "stack", "read", "mlp", "exit", "count"),
     "loop.exit", "block0.mlp"),
])
def test_a_stacking_write_or_read_is_booked_by_its_value_in_any_order(
        order, old_stack, old_read):
    hlo = _STACK_HEAD + "".join(_STACK_LINES[k] for k in order) + _STACK_TAIL
    rows = _same_projection(hlo, LOOP_NAMES)
    write = rows["bitcast_dynamic-update-slice_fusion.10"]
    assert (write.how, write.holds, write.way) == ("lent", "stack", "fwd")
    # the map follows the order of the text ...
    assert write.scope == old_stack
    # ... the work does not: the scope that made the value written
    assert write.by_work == ("block0.attn", "fwd") and write.moved
    read = rows["dynamic-slice_bitcast_fusion.11"]
    assert (read.how, read.holds, read.way) == ("lent", "stack", "bwd")
    assert read.scope == old_read
    # two of the three that read the slice are block0.mlp's
    assert read.by_work == ("block0.mlp", "bwd")
    # an instruction under a scope of its own is no stacking, wherever
    assert rows["add.8"].holds == "other" and rows["add.8"].how == "own"


# -- lowered on the CPU --------------------------------------------------------

def test_a_checkpointed_scan_has_all_three_passes_under_one_scope():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def layer(x, w):
        with probe.scope("tbl.layer"):
            return x + jnp.sin(x @ w) @ w

    def objective(ws, x):
        y, _ = lax.scan(lambda c, w: (jax.checkpoint(layer)(c, w), None),
                        x, ws)
        return (y * y).sum()

    ws, x = jnp.ones((3, 8, 8)) * 0.1, jnp.ones((4, 8))
    text = jax.jit(jax.grad(objective)).lower(ws, x).compile().as_text()
    _, rows = probe.parse_scopes(text, rows=True)
    mine = [r for r in rows.values() if r.path == ("tbl.layer",)
            and r.how == "own"]
    assert {r.way for r in mine} == {"fwd", "remat", "bwd"}
    # the projection cannot tell them apart: one bare component for all
    assert {r.scope for r in mine} == {"tbl.layer"}
    products = [r for r in mine if r.holds == "product"]
    assert {r.way for r in products} == {"fwd", "remat", "bwd"}
    assert all(r.by_work == ("tbl.layer", r.way) for r in products)
    # the scan's own stacking stands under no scope of its own
    stacks = [r for r in rows.values() if r.holds == "stack"]
    assert {"fwd", "bwd"} <= {r.way for r in stacks}
    assert "lent" in {r.how for r in stacks}
    assert all(r.how != "own" and r.by_work[0] == "tbl.layer"
               for r in stacks)


LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 24},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 6},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
]
LOADER = {"n_classes": 6, "sample_shape": (10, 10), "n_train": 240,
          "n_valid": 120, "minibatch_size": 40, "spread": 2.5,
          "noise": 1.0}


@pytest.fixture(scope="module")
def fused_workflow():
    """A tiny fused step, run once."""
    prng.seed_all(5)
    w = StandardWorkflow(
        name="ScopeTable", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": 1})
    w.initialize(device=XLADevice())
    w.run()
    return w


def test_both_views_cost_one_compile_a_program_together(fused_workflow,
                                                        monkeypatch):
    import gc

    gc.collect()
    compiles, parses = [], []
    lower, parse = probe._CompileTimed.lower, probe.parse_scopes

    def counted_lower(self, *args, **kw):
        compiles.append(self._label)
        return lower(self, *args, **kw)

    def counted_parse(*args, **kw):
        parses.append(1)
        return parse(*args, **kw)

    monkeypatch.setattr(probe._CompileTimed, "lower", counted_lower)
    monkeypatch.setattr(probe, "parse_scopes", counted_parse)
    for prog in probe._timed_programs:
        prog._table = None
    watched = [p for p in probe._timed_programs if p._abstract is not None]
    assert fused_workflow.step._train_fn_idx in watched
    scopes = probe.scope_map()
    assert len(compiles) == len(parses) == len(watched)
    table = probe.scope_table()
    assert probe.scope_map() == scopes
    assert len(compiles) == len(parses) == len(watched)     # none again
    assert scopes == {m: {n: r.scope for n, r in rows.items()}
                      for m, rows in table.items()}
    train = table["jit__local_train_idx"]
    assert {r.way for r in train.values()} == {"fwd", "bwd"}
    assert {"own", "lent"} <= {r.how for r in train.values()}
    assert any(r.holds == "product" for r in train.values())
    # a program that runs with other shapes is joined again, alone
    fn = fused_workflow.step._train_fn_idx
    fn._abstract = (fn._abstract[0], dict(fn._abstract[1]))
    probe.scope_table()
    assert len(compiles) == len(watched) + 1


def test_the_map_of_the_tiny_fused_step_is_the_parents(fused_workflow):
    fn = fused_workflow.step._train_fn_idx
    text = fn.lower(*fn._abstract[0]).compile().as_text()
    rows = _same_projection(text, probe._scope_names)
    found = {r.path[0] for r in rows.values() if r.path}
    assert {"gather_batch", "loss", "update"} <= found
    assert any(f.startswith("fc.00_") for f in found)
    bwd = {r.path[0] for r in rows.values() if r.way == "bwd" and r.path}
    assert any(f.startswith("fc.00_") for f in bwd) and "loss" in bwd


def test_a_run_never_builds_the_table(monkeypatch):
    calls = []
    real = probe.parse_scopes
    monkeypatch.setattr(
        probe, "parse_scopes",
        lambda *a, **k: calls.append(1) or real(*a, **k))
    prng.seed_all(7)
    w = StandardWorkflow(
        name="ScopeTableLazy", layers=LAYERS, loss_function="softmax",
        loader_name="synthetic_classifier", loader_config=LOADER,
        decision_config={"max_epochs": 1})
    w.initialize(device=XLADevice())
    w.run()
    assert calls == [] and w.step._train_fn_idx._table is None


# -- nested scopes: the state-space layer's parts ------------------------------

def _mixer_rows(kernels: bool, monkeypatch):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from znicz_tpu.parallel import ssm

    from znicz_tpu.core.config import root

    # the gate's kernels take several groups of whole lane tiles
    heads, head_dim, state, groups, d = 8, 32, 128, 2 if kernels else 1, 64
    inner, bc = heads * head_dim, groups * state
    monkeypatch.setattr(root.common.engine, "pallas_interpret", kernels,
                        raising=False)
    assert (ssm.gate_kernel_refusal(256, inner, groups, 0, 4, kernels)
            is None) == kernels
    rng = np.random.default_rng(0)

    def leaf(*shape, scale=0.05):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    p = {"ssm_in": leaf(d, 2 * inner + 2 * bc + heads),
         "ssm_conv_k": leaf(4, inner + 2 * bc),
         "ssm_conv_b": leaf(inner + 2 * bc),
         "ssm_dt_b": leaf(heads), "ssm_a_log": leaf(heads),
         "ssm_d": leaf(heads), "ssm_g": leaf(inner, scale=1.0),
         "ssm_out": leaf(inner, d)}
    u = leaf(1, 256, d, scale=1.0)

    def objective(p, u):
        def layer(u, p):
            with probe.scope("block7.ssm"):     # the norm's place
                h = u * lax.rsqrt((u * u).mean(-1, keepdims=True) + 1e-5)
            out, _ = ssm.mixer(h, p, heads, head_dim, state, 128, 1e-5,
                               "block7.ssm", groups)
            with probe.scope("block7.ssm"):     # the residual sum's
                return u + out
        return (jax.checkpoint(layer)(u, p) ** 2).sum()

    text = jax.jit(jax.grad(objective)).lower(p, u).compile().as_text()
    return probe.parse_scopes(text, rows=True)[1]


@pytest.mark.parametrize("kernels", [False, True])
def test_the_mixers_parts_are_innermost_in_the_table_and_not_in_the_map(
        kernels, monkeypatch):
    rows = _mixer_rows(kernels, monkeypatch)
    own = [r for r in rows.values() if r.how == "own"]
    inner = {r.path[-1] for r in own if r.path[0] == "block7.ssm"}
    assert {"block7.ssm", "block7.ssm.in", "block7.ssm.gate",
            "block7.ssm.out"} <= inner
    # the map keeps the outermost: ssm_proj_device_ms_per_step's scope
    for part in ("in", "gate", "out"):
        part_rows = [r for r in own if r.path[-1] == f"block7.ssm.{part}"]
        assert {r.path[0] for r in part_rows} == {"block7.ssm"}
        assert all(r.scope.rstrip(")").rsplit("(", 1)[-1] == "block7.ssm"
                   for r in part_rows)
        assert "bwd" in {r.way for r in part_rows}, part
    # the convolution and the scan stay the siblings they are
    assert {("block7.ssm.conv",), ("block7.ssm.scan",)} <= \
        {r.path for r in own}
    # both products of the layer are found under their parts, both ways
    for part in ("in", "out"):
        ways = {r.way for r in own if r.holds == "product" and
                r.path[-1] == f"block7.ssm.{part}"}
        assert "bwd" in ways and ways & {"fwd", "remat"}, (part, ways)


# -- the reader, on hand-made operations (times in ns) ---------------------------

def _row(scope, path, way, how, holds, by_work=None, mixed=False):
    return probe.ScopeRow(scope, path, way, how, mixed, holds,
                          by_work or (path[0] if path else "", way))


def test_reader_pass_seconds_by_hand():
    sp = _reader("scope_pass")
    ssm, attn = ("block3.ssm",), ("block0.attn",)
    rows = {"jit_step": {
        "fusion.1": _row("block3.ssm", ssm + ("block3.ssm.in",), "fwd",
                         "own", "product"),
        "gate.2": _row("block3.ssm", ssm + ("block3.ssm.gate",), "remat",
                       "own", "kernel"),
        "fusion.3": _row("update", ("update",), "fwd", "root", "product",
                         ("block3.ssm", "bwd"), mixed=True),
        "while.4": _row("block0.attn", attn, "bwd", "lent", "other"),
        "stack.5": _row("loop.exit", ("loop.exit",), "bwd", "lent", "stack",
                        ("block0.attn", "bwd")),
        "copy.6": _row("", (), "fwd", "none", "copy")}}
    modules = [(0, 1000, "jit_step"), (2000, 2100, "jit_add")]
    ops = [(0, 100, "fusion.1", "fusion"),
           (100, 250, "gate.2", "custom-call"),
           (250, 300, "fusion.3", "fusion"),
           (300, 700, "while.4", "while"),
           (350, 450, "stack.5", "fusion"),      # inside the while
           (700, 760, "copy.6", "copy"),
           (800, 830, "add.9", "add"),           # no row in the table
           (2000, 2100, "fusion.1", "fusion")]   # another program
    got = sp.pass_seconds(ops, modules, rows)
    ns = {k: round(v * 1e9) for k, v in got.items()}
    K = sp.Key
    assert ns == {
        K("block3.ssm", "block3.ssm", "block3.ssm.in", "fwd", "own", False,
          "product"): 100,
        K("block3.ssm", "block3.ssm", "block3.ssm.gate", "remat", "own",
          False, "kernel"): 150,
        K("block3.ssm", "update", "update", "bwd", "root", True,
          "product"): 50,
        K("block0.attn", "block0.attn", "block0.attn", "bwd", "lent", False,
          "other"): 300,
        K("block0.attn", "loop.exit", "loop.exit", "bwd", "lent", False,
          "stack"): 100,
        K(sp.UNSCOPED, sp.UNSCOPED, sp.UNSCOPED, "fwd", "none", False,
          "copy"): 60,
        sp.NO_ROW: 130}
    assert sum(ns.values()) == 760 + 30 + 100           # the busy union
    table = {r[0]: dict(zip(sp.COLUMNS, r[1:]))
             for r in sp.table(got, steps=2)}
    assert table["block3.ssm"] == pytest.approx(
        {"fwd": 50e-6, "remat": 75e-6, "bwd": 25e-6, "lent": 0, "mixed":
         25e-6, "kernel": 75e-6, "product": 75e-6, "stack": 0,
         "gained": 25e-6, "lost": 0})
    assert table["block0.attn"]["lent"] == pytest.approx(200e-6)
    assert table["block0.attn"]["gained"] == pytest.approx(50e-6)
    assert table["loop.exit"] == pytest.approx(
        {**dict.fromkeys(sp.COLUMNS, 0.0), "lost": 50e-6})
    assert table["update"]["lost"] == pytest.approx(25e-6)

    class Run:
        pass

    def metric(**params):
        rc = Run()
        rc.metric = {"params": params}
        rc.trace = object()
        sp._CACHE.clear()
        sp._CACHE[id(rc.trace)] = (got, 2)
        return sp.read(rc)

    assert metric(way="remat") == pytest.approx(75e-6)
    assert metric(way="bwd") == pytest.approx((50 + 300 + 100) / 2 * 1e-6)
    assert metric(share="lent") == pytest.approx(100 * 400 / 890)
    assert metric(share="mixed") == pytest.approx(100 * 50 / 890)
    assert metric(innermost=[r"block\d+\.ssm\.in"]) == pytest.approx(50e-6)
    assert metric(innermost=[r"block\d+\.ssm\.gate"]) == \
        pytest.approx(75e-6)
    # a cell that lists a metric reads a number where no row matches
    assert metric(innermost=[r"block\d+\.ssm\.out"]) == 0.0
    # ... and nothing where the program gave no table
    rc = Run()
    rc.metric, rc.trace = {"params": {"way": "bwd"}}, object()
    sp._CACHE.clear()
    sp._CACHE[id(rc.trace)] = None
    assert sp.read(rc) is None
