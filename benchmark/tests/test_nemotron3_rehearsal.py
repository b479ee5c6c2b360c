"""CPU rehearsal of the ``nemotron3_nano_train_ep8_t8192`` cell: ``run.py``
end to end over a tiny overlay of its configuration and traffic (every
mechanism kept: layers of one sub-layer, Mamba-2 with two groups, sigmoid
routing with a selection bias over 16 experts of which 4 are held, plain
squared-ReLU experts beside a shared one, position-free grouped-query
attention, an untied head, a row that spans several chunks), the traced
run's per-layer metrics with the two metric files this cell adds, both read
from a rehearsal's samples, the kernel count's arithmetic against a hand
count, the control that must come out as not correct (the reference in fp8),
the refusal a program that cannot read the family gives before the reference
runs, the operation count, and a compile-only rehearsal of the step at the
real widths for a v5e that is described and not attached, with the grouped
products on their Pallas kernels at the width 128 does not divide.
"""

import copy
import json
import math
import os
import types

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import benchlib                                       # noqa: E402
import run                                            # noqa: E402
import tiny                                           # noqa: E402

CELL = "nemotron3_nano_train_ep8_t8192"
CONFIG, TRAFFIC = "nemotron_3_nano_30b_a3b", "train_tokens_ep8_t8192"
TINY_NEMOTRON = {
    "hidden_size": 64, "intermediate_size": 24, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 61,
    "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "mamba_num_heads": 8, "mamba_head_dim": 12, "ssm_state_size": 8,
    "n_groups": 2, "chunk_size": 8, "n_routed_experts": 4,
    "router_width": 16, "experts_held": {"first": 4, "count": 4},
    "num_experts_per_tok": 3, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "hyper": {"lr": 0.05},
}
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 32,
               "k_steps": 2}
HBM_USABLE = 15.75 * 2 ** 30      # what the runtime leaves of 16 GiB


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("nemotron_overlay"))
    for kind, name, changes in (("configs", CONFIG, TINY_NEMOTRON),
                                ("traffic", TRAFFIC, TINY_TOKENS)):
        doc = copy.deepcopy(benchlib.load_json(
            os.path.join(tiny.BENCH_DIR, kind, name + ".json")))
        doc.update(changes)
        if kind == "configs":           # the model's keys stay as listed
            doc["builders"]["lm_train_keys"]["loss_chunks"] = 2
        os.makedirs(os.path.join(root, kind), exist_ok=True)
        with open(os.path.join(root, kind, name + ".json"), "w") as f:
            json.dump(doc, f)
    return root


def _run(overlay, seed=7, seconds=1.0, trace=0, control=False):
    return run.execute(["--workload", CELL, "--seed", str(seed), "--seconds",
                        str(seconds), "--trace", str(trace)],
                       roots_extra=[overlay], allow_cpu=True, control=control)


@pytest.fixture(scope="module")
def traced(overlay):
    return _run(overlay, seed=13, seconds=2.0, trace=1)


def test_cell_runs_end_to_end_tiny(overlay):
    rc, result, outcome = _run(overlay, seed=2147483711)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(outcome["samples"]["readings"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff_gap"}
    # one builder copies both kinds' counters
    ssm, moe = outcome["samples"]["ssm"], outcome["samples"]["moe"]
    assert 0.0 < ssm["decay_mean"] < 1.0 and ssm["final_state_rms"] > 0.0
    assert set(moe) == {"pairs_held_per_step", "load_max_over_mean",
                        "compact_share", "tile_fill", "act_zero_share"}
    assert 0.25 < moe["act_zero_share"] < 0.75
    first = outcome["samples"]["ssm_first_step"]
    for key in ("decay_mean", "final_state_rms"):
        assert first["program"][key] == pytest.approx(
            first["reference"][key], rel=5e-3), first
    held = outcome["samples"]["config_as_run"]["moe_pairs_held_per_step"]
    assert held == moe["pairs_held_per_step"] > 0


def test_traced_run_reports_every_metric_that_lists_the_cell(traced):
    rc, result, outcome = traced
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    # the device-trace readers find no TPU plane on the CPU; the program's
    # counters and spans are all there
    assert {"graph_ms_per_step", "train_step_rate_median", "ssm_decay_mean",
            "ssm_final_state_rms", "moe_expert_load_max_over_mean",
            "moe_compact_share", "moe_gmm_tile_fill",
            "moe_act_zero_share"} <= got
    bench = benchlib.benchmark_json(benchlib.Roots())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "attn_device_ms_per_step", "ce_device_ms_per_step",
        "moe_route_device_ms_per_step", "moe_experts_device_ms_per_step",
        "shared_expert_device_ms_per_step", "ssm_proj_device_ms_per_step",
        "ssm_conv_device_ms_per_step", "ssm_scan_device_ms_per_step",
        "ssm_decay_mean", "ssm_final_state_rms",
        "moe_expert_load_max_over_mean", "moe_compact_share",
        "moe_gmm_tile_fill", "moe_gmm_plain_roofline", "moe_act_zero_share"}
    # the two this cell adds list no other cell; the gated products' share
    # keeps its nine and does not list this one
    for name in ("moe_gmm_plain_roofline", "moe_act_zero_share"):
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
    (nine,) = [m for m in bench["per_layer"]
               if m["name"] == "moe_gmm_roofline"]
    assert CELL not in nine["workloads"]
    for name in listed:                 # each has its file and its reader
        spec = benchlib.Roots().data("metrics", name)
        benchlib.Roots().module("readers", spec["reader"])


def test_both_new_metric_files_read_a_rehearsals_samples(traced):
    """``moe_act_zero_share`` from the counters the builder copied, and
    ``moe_gmm_plain_roofline`` from the pairs it wrote into the
    configuration as run and a trace in which the six-a-layer grouped
    products took twice their least time: 50 %.  A program without the
    counter (the parent's cells) gives the first nothing to read."""
    _, _, outcome = traced
    samples = outcome["samples"]
    roots = benchlib.Roots()
    spec = roots.data("metrics", "moe_act_zero_share")
    reader = roots.module("readers", spec["reader"])
    rc = types.SimpleNamespace(samples=samples, metric=spec)
    assert reader.read(rc) == samples["moe"]["act_zero_share"]
    assert reader.read(types.SimpleNamespace(
        samples={"moe": {"tile_fill": 0.5}}, metric=spec)) is None
    assert reader.read(types.SimpleNamespace(samples={}, metric=spec)) is None

    spec = roots.data("metrics", "moe_gmm_plain_roofline")
    assert spec["params"] == {"kernel": "moe_gmm_plain"}
    reader = roots.module("readers", spec["reader"])
    cfg = samples["config_as_run"]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    (call,) = roots.module("kernels", "moe_gmm_plain").calls_per_step(cfg, {})
    least = call["count"] * max(call["flops"] / peaks["bf16_flops"],
                                call["bytes"] / peaks["hbm_bytes_per_s"])
    steps = benchlib.traced_steps(samples)
    assert steps and call["count"] == 6 * 2

    class Trace:
        def matching_s(self, patterns):
            assert patterns == ["ragged-dot-none"]
            return 2.0 * least * steps, call["count"] * steps

    rc = types.SimpleNamespace(samples=samples, metric=spec, trace=Trace(),
                               peaks=peaks, config=cfg, traffic={},
                               roots=roots, log=lambda line: None)
    assert reader.read(rc) == pytest.approx(50.0)
    rc.trace = None                     # an untraced run: nothing to read
    assert reader.read(rc) is None


def test_the_kernel_count_is_a_hand_count():
    """Six grouped products an ``E`` layer a step, ``2 x pairs x 2,688 x
    1,856`` operations each, the ``E`` layers counted from the pattern as
    run: at the cell's mean load (16,384 tokens x 6 x 16 / 128 = 12,288
    pairs a layer, 49,152 a step) 24 products of 122.6 GFLOP, 2.94 TFLOP a
    step; compute-bound on a v5e (0.62 ms against 0.20 ms of bytes)."""
    roots = benchlib.Roots()
    cfg = {**roots.data("configs", CONFIG), "moe_pairs_held_per_step": 49152.0}
    (call,) = roots.module("kernels", "moe_gmm_plain").calls_per_step(cfg, {})
    assert call["pattern"] == "ragged-dot-none" and call["count"] == 24
    assert call["flops"] == 2.0 * 12288 * 2688 * 1856
    assert call["flops"] == pytest.approx(122.6e9, rel=1e-3)
    assert call["bytes"] == 2.0 * (12288 * (2688 + 1856) + 16 * 2688 * 1856)
    assert call["flops"] / 197e12 > call["bytes"] / 819e9
    # the name the three Pallas kernels and XLA's own share
    from znicz_tpu.ops.pallas import grouped
    for name in (grouped.ROWS_KERNEL_NAME, grouped.ROWS_T_KERNEL_NAME,
                 grouped.WEIGHTS_KERNEL_NAME):
        assert call["pattern"] in name
    # no pairs counted (a program without the counter), or no E layer: no
    # call, and the reader reads nothing
    for other in ({**cfg, "moe_pairs_held_per_step": None},
                  {**cfg, "hybrid_override_pattern": "MM*"}):
        (none,) = roots.module("kernels", "moe_gmm_plain").calls_per_step(
            {k: v for k, v in other.items() if v is not None}, {})
        assert none["count"] == 0


def test_the_step_books_every_layers_operations_under_its_scopes():
    """A one-sub-layer layer emits its kind's scopes and no other's: ``M``
    ``block<i>.ssm`` / ``.ssm.conv`` / ``.ssm.scan``, ``*`` ``block<i>.attn``,
    ``E`` ``block<i>.moe`` / ``.moe.route`` / ``.moe.experts`` /
    ``.moe.shared``; no ``.mlp`` anywhere; hardly an operation bare."""
    import re

    import jax
    import jax.numpy as jnp

    from znicz_tpu.observe import probe
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    def matches(metric, scope):
        spec = benchlib.Roots().data("metrics", metric)
        return any(re.compile(p).fullmatch(scope)
                   for p in spec["params"]["patterns"])

    assert matches("shared_expert_device_ms_per_step", "block1.moe.shared")
    assert matches("moe_experts_device_ms_per_step", "block8.moe.experts")
    assert not matches("moe_experts_device_ms_per_step", "block1.moe.shared")
    cfg = {**benchlib.Roots().data("configs", CONFIG), **TINY_NEMOTRON}
    arch = tfm.arch_from_config(
        {k: cfg[k] for k in cfg["builders"]["lm_train_keys"]["model_keys"]})
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])
    step, _ = tfm.make_train_step(mesh, arch, lr=0.05, stats=True,
                                  loss_chunks=2)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        tfm.param_shapes(arch), is_leaf=lambda x: isinstance(x, tuple))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = step.lower(params, tok, tok).compile().as_text()
    _, scopes = probe.parse_scopes(text)
    seen = {probe_name.rstrip(")").rsplit("(", 1)[-1]
            for probe_name in scopes.values() if probe_name}
    assert {"embed", "ce", "update", "block0.ssm", "block0.ssm.conv",
            "block0.ssm.scan", "block1.moe", "block1.moe.route",
            "block1.moe.experts", "block1.moe.shared", "block2.ssm.scan",
            "block3.attn", "block4.moe.experts"} <= seen
    assert not [s for s in seen if s.endswith(".mlp")]
    assert not [s for s in seen if s.startswith(("block1.ssm", "block1.attn",
                                                 "block0.moe", "block3.moe"))]
    bare = [n for n, sc in scopes.items() if not sc]
    assert len(bare) < 0.02 * len(scopes), bare


def test_operation_count_is_the_issues_arithmetic():
    """Forward, a token (ISSUE 45): an ``M`` layer 77.4 M (and its scan's
    least 2.8 M), the shared expert 39.9 M, the routed experts' held share
    15.0 M, the ``*`` layer 113.9 M at 8,192 positions, the head 88.1 M; a
    step of 2 x 8,192 tokens 36 TFLOP, the new parts (``M`` and ``E``) 72 %
    of it."""
    roots = benchlib.Roots()
    cfg = roots.data("configs", CONFIG)
    ref = roots.module("reference", "nemotron_h")
    traffic = roots.data("traffic", TRAFFIC)
    t, batch = int(traffic["seq_len"]), int(traffic["minibatch_size"])
    parts = ref.forward_flops_per_token(cfg, t)
    scan = ref.scan_flops_per_token(ref.dims(cfg), 128)
    assert parts["M"] - scan == pytest.approx(77.4e6, rel=1e-3)
    assert scan == pytest.approx(2.76e6, rel=5e-3)
    assert parts["E_shared"] == pytest.approx(39.9e6, rel=1e-3)
    assert parts["E_routed"] == pytest.approx(15.0e6, rel=3e-3)
    assert parts["*"] == pytest.approx(113.9e6, rel=1e-3)
    assert parts["head"] == pytest.approx(88.1e6, rel=1e-3)
    step = batch * ref.train_flops_per_sample(cfg, t)
    assert step == pytest.approx(36e12, rel=2e-2)
    new = 4 * parts["M"] + 4 * (parts["E_router"] + parts["E_shared"] +
                                parts["E_routed"])
    assert 3 * batch * t * new / step == pytest.approx(0.72, abs=0.015)


def test_fp8_control_fails_a_limit(overlay):
    rc, result, outcome = _run(overlay, seed=5, control=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    ref = benchlib.Roots().module("reference", "nemotron_h")
    control = outcome["samples"]["control_readings"]
    assert any(control[k] > ref.LIMITS[k] for k in control), control


def test_a_program_that_cannot_read_the_family_is_refused_at_once(
        overlay, monkeypatch, capsys):
    """What the parent commit does with this cell: ``arch_from_config``
    refuses the ``model_type`` by name, and the run ends with exit code 1
    and no result line before the reference has run."""
    from znicz_tpu.parallel import transformer as tfm

    ref = benchlib.Roots().module("reference", "nemotron_h")
    monkeypatch.delitem(tfm._FAMILIES, "nemotron_h")
    monkeypatch.setattr(ref, "first_steps", lambda *a, **k: pytest.fail(
        "the reference ran before the refusal"))
    rc, result, outcome = _run(overlay, seed=3)
    assert rc == 1 and result is None and outcome is None
    assert "nemotron_h" in capsys.readouterr().err


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)


def test_step_compiles_for_a_v5e_at_the_real_widths_and_fits(topo,
                                                             monkeypatch):
    """The cell's step (nine layers, 2 x 8,192 tokens, 16,384 ids) compiled
    for the described chip with what a v5e would answer (a flash kernel, the
    grouped-product kernels, 15.75 GiB): 986,254,848 parameters, the three
    grouped kernels in both branches of every ``E`` layer's choice of buffer
    at the width 1,856, the plan's kinds, and arguments plus temporaries
    that fit the chip and stand within the plan's margin of its footprint."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.ops.pallas import grouped
    from znicz_tpu.parallel import moe, transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh
    from znicz_tpu.parallel.plan import PLAN_MARGIN, step_footprint

    # the step asks jax.default_backend(), which is the CPU here, and the
    # described chip reports no memory
    monkeypatch.setattr(tfm, "_flash_eligible", lambda mesh, interp: True)
    monkeypatch.setattr(tfm, "_memory_limit", lambda mesh: int(HBM_USABLE))
    monkeypatch.setattr(moe, "_kernels_eligible", lambda interpret: True)
    cfg = benchlib.Roots().data("configs", CONFIG)
    traffic = benchlib.Roots().data("traffic", TRAFFIC)
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
    n_params = sum(math.prod(s.shape) for s in jax.tree.leaves(params))
    assert n_params == 986_254_848
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "seq")))
    mask = jax.ShapeDtypeStruct((b,), jnp.bool_,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(params, tok, tok, mask).compile()
    m = compiled.memory_analysis()
    live = m.argument_size_in_bytes + m.temp_size_in_bytes
    plan = tfm.checkpoint_plan(arch, b * t, 2, int(HBM_USABLE),
                               opts["loss_chunks"])
    reckoned = step_footprint(arch, b * t, 2, opts["loss_chunks"]) + \
        sum(plan.values())
    print(f"compiled step for a described v5e: arguments "
          f"{m.argument_size_in_bytes / 2 ** 30:.3f} GiB (donated), "
          f"temporaries {m.temp_size_in_bytes / 2 ** 30:.3f} GiB, together "
          f"{live / 2 ** 30:.3f} GiB of {HBM_USABLE / 2 ** 30:.2f}; "
          f"reckoned {reckoned / 2 ** 30:.3f} with {plan}")
    assert live < 0.9 * HBM_USABLE, f"{live / 2 ** 30:.2f} GiB: {m}"
    assert abs(live - reckoned) <= PLAN_MARGIN, (live, reckoned)
    assert plan["glu_wide"] == 4 * b * t * 3712 * 2
    assert plan["ssm_in"] == 4 * b * t * 10304 * 2
    text = compiled.as_text()
    for name, compact, full in ((grouped.ROWS_KERNEL_NAME, 2, 2),
                                (grouped.ROWS_T_KERNEL_NAME, 2, 2),
                                (grouped.WEIGHTS_KERNEL_NAME, 2, 2)):
        calls = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln and name in ln]
        # four E layers: two forward products a branch, and a backward
        # pass of two to the rows and two to the weights a branch (the
        # full branch takes its two forward products again)
        assert len(calls) >= 4 * (compact + full), (name, len(calls))
    assert " ragged-dot(" not in text          # none left to XLA's own
