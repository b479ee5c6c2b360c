"""CPU rehearsal of the ``solar_open2_train_ep32_t8192`` cell: ``run.py`` end
to end over a tiny overlay of its configuration and traffic (every mechanism
kept: delta-rule linear-attention layers with their convolutions, low-rank
pairs and gated head norm beside a gated position-free grouped-query layer,
routed experts beside a shared one in every layer, an untied head, rows of
several chunks), the traced run's per-layer metrics with the six metric files
this cell adds, the kernel counts' arithmetic against a hand count, the scopes
the new parts stand under, the control that must come out as not correct (the
reference in fp8), the refusal a program that cannot read the family gives
before the reference runs, the operation count, and a compile-only rehearsal
of the step at the real widths for a v5e that is described and not attached,
answering as a v5e for EVERY kernel gate the step passes (the flash kernels,
the grouped products' kernels, the convolution's kernels, the memory limit).

What ``BENCHMARK.json`` lists is read, not pinned: a later PR that appends
this cell to another metric's list, or adds a cell, breaks nothing here.
"""

import copy
import json
import math
import os
import re
import types

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import benchlib                                       # noqa: E402
import run                                            # noqa: E402
import tiny                                           # noqa: E402

CELL = "solar_open2_train_ep32_t8192"
CONFIG, TRAFFIC = "solar_open2_250b", "train_tokens_ep32_kda_t8192"
TINY_SOLAR = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 61, "num_hidden_layers": 3, "gqa_layers": [0],
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "n_routed_experts": 4, "router_width": 16,
    "experts_held": {"first": 4, "count": 4}, "num_experts_per_tok": 3,
    "layer_types": ["full_attention", "linear_attention",
                    "linear_attention"],
    "hyper": {"lr": 0.05},
}
#: rows of 80 positions: a whole chunk of 64 and a filled one
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 80,
               "k_steps": 2}
HBM_USABLE = 15.75 * 2 ** 30      # what the runtime leaves of 16 GiB
NEW_METRICS = ("kda_proj_device_ms_per_step", "kda_conv_device_ms_per_step",
               "kda_delta_device_ms_per_step", "kda_gate_device_ms_per_step",
               "kda_decay_mean", "kda_final_state_rms")


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("solar_overlay"))
    for kind, name, changes in (("configs", CONFIG, TINY_SOLAR),
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


def _listed(bench) -> set:
    return {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}


def test_cell_runs_end_to_end_tiny(overlay):
    rc, result, outcome = _run(overlay, seed=2147483711)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    assert {"train_samples_per_s", "setup_s"} <= set(result["metrics"])
    assert result["attempted"] > 0 and result["failed"] == 0
    readings = outcome["samples"]["readings"]
    assert {"loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff_gap",
            "kda_state_gap"} <= set(readings)
    # one builder copies the routed layers' counters and the linear layers'
    kda, moe = outcome["samples"]["kda"], outcome["samples"]["moe"]
    assert kda["layers"] == 2.0 and 0 < kda["decay_mean"] < 1
    assert 0 < kda["beta_mean"] < 2 and kda["final_state_rms"] > 0
    assert moe["pairs_held_per_step"] > 0
    # the first step's readings stand beside the reference's walk
    first = outcome["samples"]["kda_first_step"]
    assert first["program"]["final_state_rms"] == pytest.approx(
        first["reference"]["final_state_rms"], rel=0.02)
    assert first["program"]["decay_mean"] == pytest.approx(
        first["reference"]["decay_mean"], rel=1e-3)
    assert any("check kda_state_gap" in line and " ok " in line
               for line in outcome["lines"])


def test_traced_run_reports_every_metric_it_can_read_off_the_cpu(traced):
    rc, result, outcome = traced
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    bench = benchlib.benchmark_json(benchlib.Roots())
    listed = _listed(bench)
    assert set(NEW_METRICS) <= listed
    roots = benchlib.Roots()
    for name in listed:                 # each has its file and its reader
        spec = roots.data("metrics", name)
        roots.module("readers", spec["reader"])
        # the device-trace readers find no TPU plane on the CPU; what the
        # program counts is all there, with a number
        if spec["source"] == "program_counter":
            assert name in got, name
            assert result["metrics"][name]["value"] is not None, name
    assert 0 < result["metrics"]["kda_decay_mean"]["value"] < 1
    assert result["metrics"]["kda_final_state_rms"]["value"] > 0
    for name in NEW_METRICS:            # the six this cell adds list it
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) == \
        (1, CONFIG, TRAFFIC)
    (entry,) = [m for m in bench["end_to_end"]
                if m["name"] == "train_samples_per_s"]
    assert CELL in entry["workloads"]


def test_the_new_counter_reader_reads_a_rehearsals_samples(traced):
    """``kda_decay_mean`` and ``kda_final_state_rms`` from the counters the
    builder copied; a program without them (the parent's cells) gives the
    reader nothing to read, and it does not raise."""
    _, _, outcome = traced
    samples = outcome["samples"]
    roots = benchlib.Roots()
    for name, key in (("kda_decay_mean", "decay_mean"),
                      ("kda_final_state_rms", "final_state_rms")):
        spec = roots.data("metrics", name)
        reader = roots.module("readers", spec["reader"])
        assert reader.read(types.SimpleNamespace(
            samples=samples, metric=spec)) == samples["kda"][key]
        assert reader.read(types.SimpleNamespace(
            samples={}, metric=spec)) is None
        assert reader.read(types.SimpleNamespace(
            samples={"kda": {"layers": 3.0}}, metric=spec)) is None


def test_the_kernel_counts_are_hand_counts():
    """Attention: ONE full call a pass over the triangle's 33,558,528 pairs,
    64 query heads on 8 key/value heads of 128, and no window call (the
    file's ``layer_types`` has one ``full_attention`` entry and three that
    are no attention at all).  The grouped products: nine a layer over four
    sparse layers as run (``num_hidden_layers`` 4 less ``num_dense_layers``
    0), ``2 x pairs x 4,096 x 1,280`` operations each at the cell's mean
    load of 205 pairs an expert, 10 held: bytes set their least time (105 MB
    of weights against 21 GFLOP)."""
    from znicz_tpu.ops.pallas import attention as pattn, grouped

    roots = benchlib.Roots()
    cfg, traffic = roots.data("configs", CONFIG), roots.data("traffic",
                                                             TRAFFIC)
    calls = {c["pattern"]: c for c in roots.module(
        "kernels", "flash_attention_swa").calls_per_step(cfg, traffic)}
    assert set(calls) == {pattn.KVB_FWD_KERNEL_NAME,
                          pattn.KVB_DKV_KERNEL_NAME, pattn.KVB_DQ_KERNEL_NAME}
    full = calls[pattn.KVB_FWD_KERNEL_NAME]
    assert full["count"] == 1
    assert full["flops"] == 2 * 2.0 * 64 * 33_558_528 * 128
    assert full["bytes"] == 2 * 2.0 * 8192 * 64 * 128 + \
        2 * 2.0 * 8192 * 8 * 128 + 4.0 * 64 * 8192
    for c in calls.values():
        assert c["flops"] / 197e12 > c["bytes"] / 819e9

    pairs = 4 * 8192 * 8 * 10 / 320                       # a step, 4 layers
    (call,) = roots.module("kernels", "moe_gmm").calls_per_step(
        {**cfg, "moe_pairs_held_per_step": pairs}, traffic)
    assert call["count"] == 9 * 4
    assert call["flops"] == 2.0 * 2048 * 4096 * 1280
    assert call["bytes"] == 2.0 * (2048 * 5376 + 10 * 4096 * 1280)
    assert call["flops"] / 197e12 < call["bytes"] / 819e9
    for name in (grouped.ROWS_KERNEL_NAME, grouped.ROWS_T_KERNEL_NAME,
                 grouped.WEIGHTS_KERNEL_NAME):
        assert call["pattern"] in name


def test_the_step_books_the_new_parts_under_their_scopes():
    """A linear layer's norm, projection, gate, output product and residual
    sum stand under ``block<i>.kda``, its convolutions under
    ``block<i>.kda.conv`` and every step of the rule under
    ``block<i>.kda.delta``; the grouped-query layer under ``block<i>.attn``
    with its gate under ``.attn.gate``; every layer's experts under ``.moe``
    and its three; hardly an operation bare."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.observe import probe
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    def matches(metric, scope):
        params = benchlib.Roots().data("metrics", metric)["params"]
        return any(re.compile(p).fullmatch(scope)
                   for p in params.get("patterns", params.get("innermost")))

    assert matches("kda_proj_device_ms_per_step", "block1.kda")
    assert matches("kda_conv_device_ms_per_step", "block12.kda.conv")
    assert matches("kda_delta_device_ms_per_step", "block3.kda.delta")
    assert matches("kda_gate_device_ms_per_step", "block3.kda.gate")
    assert not matches("kda_proj_device_ms_per_step", "block1.kda.delta")
    assert not matches("kda_delta_device_ms_per_step", "block1.ssm.scan")
    cfg = {**benchlib.Roots().data("configs", CONFIG), **TINY_SOLAR}
    arch = tfm.arch_from_config(
        {k: cfg[k] for k in cfg["builders"]["lm_train_keys"]["model_keys"]})
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, jax.devices()[:1])
    step, _ = tfm.make_train_step(mesh, arch, lr=0.05, stats=True,
                                  loss_chunks=2)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        tfm.param_shapes(arch), is_leaf=lambda x: isinstance(x, tuple))
    tok = jax.ShapeDtypeStruct((2, 80), jnp.int32)
    text = step.lower(params, tok, tok).compile().as_text()
    _, scopes = probe.parse_scopes(text)
    seen = {probe_name.rstrip(")").rsplit("(", 1)[-1]
            for probe_name in scopes.values() if probe_name}
    assert {"embed", "ce", "update", "block0.attn", "block0.attn.gate",
            "block0.moe", "block0.moe.route", "block0.moe.experts",
            "block0.moe.shared", "block1.kda", "block1.kda.conv",
            "block1.kda.delta", "block2.kda", "block2.kda.delta",
            "block2.moe.experts"} <= seen
    assert "block0.kda" not in seen and "block1.attn" not in seen
    # the parts opened inside ``block<i>.kda`` stand in the rows' paths
    _, rows = probe.parse_scopes(text, rows=True)
    inner = {row.path[-1] for row in rows.values() if row.path}
    assert {"block1.kda.in", "block1.kda.gate", "block1.kda.out"} <= inner
    bare = [n for n, sc in scopes.items() if not sc]
    assert len(bare) < 0.02 * len(scopes), bare


def test_operation_count_is_the_issues_arithmetic():
    """Forward, a token (ISSUE 52): a linear mixer's products 137.7 M x 2
    less the taps, biases and gains that are no products (275.2 M) and the
    rule's 7 x 128 x 128 a head (7.3 M); the grouped-query sub-layer's five
    projections 218.1 M and its scores 134.2 M; the shared expert 31.5 M, the
    router 2.6 M, the routed experts' held share 7.9 M, the head 201.3 M; a
    step of 8,192 tokens 38.6 TFLOP, the three linear layers 20.8 of it and
    the rule itself 0.54 (1.4 %)."""
    roots = benchlib.Roots()
    cfg = roots.data("configs", CONFIG)
    ref = roots.module("reference", "solar_open2")
    traffic = roots.data("traffic", TRAFFIC)
    t, batch = int(traffic["seq_len"]), int(traffic["minibatch_size"])
    parts = ref.forward_flops_per_token(cfg, t)
    rule = 7.0 * 64 * 128 * 128
    assert rule == pytest.approx(7.34e6, rel=1e-3)
    assert parts["kda"] - rule == pytest.approx(275.2e6, rel=1e-3)
    proj = 2.0 * 4096 * 128 * (3 * 64 + 2 * 8)
    assert proj == pytest.approx(218.1e6, rel=1e-3)
    assert parts["gqa"] - proj == pytest.approx(134.2e6, rel=1e-3)
    assert parts["shared"] == pytest.approx(31.46e6, rel=1e-3)
    assert parts["router"] == pytest.approx(2.62e6, rel=1e-3)
    assert parts["routed"] == pytest.approx(7.86e6, rel=1e-3)
    assert parts["head"] == pytest.approx(201.3e6, rel=1e-3)
    step = batch * ref.train_flops_per_sample(cfg, t)
    assert step == pytest.approx(38.56e12, rel=1e-3)
    assert 3 * t * 3 * parts["kda"] == pytest.approx(20.8e12, rel=1e-2)
    assert 3 * t * 3 * rule / step == pytest.approx(0.014, rel=2e-2)


def test_fp8_control_fails_a_limit(overlay):
    rc, result, outcome = _run(overlay, seed=5, control=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    ref = benchlib.Roots().module("reference", "solar_open2")
    control = outcome["samples"]["control_readings"]
    assert "kda_state_gap" in control
    assert any(control[k] > ref.LIMITS[k] for k in control), control


def test_a_program_that_cannot_read_the_family_is_refused_at_once(
        overlay, monkeypatch, capsys):
    """What the parent commit does with this cell: ``arch_from_config``
    refuses the ``model_type`` by name, and the run ends with exit code 1
    and no result line before the reference has run."""
    from znicz_tpu.parallel import transformer as tfm

    ref = benchlib.Roots().module("reference", "solar_open2")
    monkeypatch.delitem(tfm._FAMILIES, "solar_open2")
    monkeypatch.setattr(ref, "first_steps", lambda *a, **k: pytest.fail(
        "the reference ran before the refusal"))
    rc, result, outcome = _run(overlay, seed=3)
    assert rc == 1 and result is None and outcome is None
    assert "solar_open2" in capsys.readouterr().err


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
    """The cell's step (four layers, 1 x 8,192 tokens, 24,576 ids) compiled
    for the described chip with what a v5e would answer at EVERY kernel gate
    the step passes (a flash kernel; the grouped-product kernels; the
    convolution's kernels; 15.75 GiB): 1,420,941,120 parameters, the plain
    blocked flash kernels once for the one grouped-query layer, the
    convolution's kernels once a linear layer a pass, the grouped kernels,
    no float32 array with two chunk-length axes beside 128 channels, and
    arguments plus temporaries that fit the chip (barely: 15.71 of 15.75
    GiB) and stand within the plan's margin of its footprint."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.ops.pallas import attention as pattn, grouped, ssm_conv
    from znicz_tpu.parallel import moe, ssm, transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh
    from znicz_tpu.parallel.plan import PLAN_MARGIN, step_footprint

    # the step asks jax.default_backend(), which is the CPU here, and the
    # described chip reports no memory
    monkeypatch.setattr(tfm, "_flash_eligible", lambda mesh, interp: True)
    monkeypatch.setattr(tfm, "_memory_limit", lambda mesh: int(HBM_USABLE))
    monkeypatch.setattr(moe, "_kernels_eligible", lambda interpret: True)
    monkeypatch.setattr(ssm, "_kernels_eligible", lambda interpret: True)
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
    assert n_params == 1_420_941_120
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
    # the compiler refuses what does not fit; its own report of the bytes
    # in use at the fullest reads 15.07 GiB where these two add up to 15.71
    assert live <= HBM_USABLE, f"{live / 2 ** 30:.2f} GiB: {m}"
    assert abs(live - reckoned) <= PLAN_MARGIN, (live, reckoned)
    text = compiled.as_text()

    def stands(name):
        return len(re.findall(
            rf'custom_call_target="tpu_custom_call"[^\n]*{name}\b', text))

    for name in (pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
                 pattn.KVB_DQ_KERNEL_NAME):
        assert stands(name) == 1, name
    assert stands(ssm_conv.FWD_KERNEL_NAME) >= 3
    assert stands(ssm_conv.BWD_KERNEL_NAME) == 3
    for name in (grouped.ROWS_KERNEL_NAME, grouped.ROWS_T_KERNEL_NAME,
                 grouped.WEIGHTS_KERNEL_NAME):
        assert stands(re.escape(name)) >= 4, name
    assert " ragged-dot(" not in text          # none left to XLA's own
    q = arch.kda_chunk              # no decay matrix a channel of every chunk
    assert not re.findall(rf"f32\[(?:\d+,)*{t // q},{q},{q},128\]", text)
