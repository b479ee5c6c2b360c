"""CPU rehearsal of the ``trinity_large_train_ep32_t8192`` cell: ``run.py``
end to end over a tiny overlay of its configuration and traffic (every
mechanism kept: window and full layers mixed, the rotary embedding on the
window layers alone, the gated output, four norms a layer, a dense layer and
sparse ones with a shared expert, the embeddings' multiplier, an untied head,
a window shorter than the row), the traced run's per-layer metrics with the
four metric files this cell adds, the kernel count's arithmetic against a
hand count, the scopes the new parts stand under, the control that must come
out as not correct (the reference in fp8), the refusal a program that cannot
read the family gives before the reference runs, the operation count, and a
compile-only rehearsal of the step at the real widths for a v5e that is
described and not attached, answering as a v5e for EVERY kernel gate the
step passes (the flash kernels and with them the in-place rotary kernel, the
grouped products' kernels, the memory limit).
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

CELL = "trinity_large_train_ep32_t8192"
CONFIG, TRAFFIC = "trinity_large_preview", "train_tokens_ep32_t8192"
TINY_TRINITY = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 61, "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "full_attention",
                    "sliding_attention"],
    "sliding_window": 12, "num_experts": 4, "router_width": 16,
    "experts_held": {"first": 4, "count": 4}, "num_experts_per_tok": 3,
    "hyper": {"lr": 0.05},
}
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 32,
               "k_steps": 2}
HBM_USABLE = 15.75 * 2 ** 30      # what the runtime leaves of 16 GiB
NEW_METRICS = ("attn_swa_device_ms_per_step", "attn_gate_device_ms_per_step",
               "attn_window_tile_share", "flash_attn_swa_roofline")


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("trinity_overlay"))
    for kind, name, changes in (("configs", CONFIG, TINY_TRINITY),
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
    # one builder copies the routed layers' counters and the window layers'
    attn, moe = outcome["samples"]["attn"], outcome["samples"]["moe"]
    assert attn == {"window_layers": 2.0, "window_tile_share": 1.0}
    assert moe["pairs_held_per_step"] > 0
    held = outcome["samples"]["config_as_run"]["moe_pairs_held_per_step"]
    assert held == moe["pairs_held_per_step"]


def test_traced_run_reports_every_metric_that_lists_the_cell(traced):
    rc, result, outcome = traced
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    # the device-trace readers find no TPU plane on the CPU; the program's
    # counters and spans are all there
    assert {"graph_ms_per_step", "train_step_rate_median",
            "attn_window_tile_share", "moe_expert_load_max_over_mean",
            "moe_compact_share", "moe_gmm_tile_fill"} <= got
    bench = benchlib.benchmark_json(benchlib.Roots())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "attn_device_ms_per_step", "mlp_device_ms_per_step",
        "ce_device_ms_per_step", "moe_route_device_ms_per_step",
        "moe_experts_device_ms_per_step", "shared_expert_device_ms_per_step",
        "moe_expert_load_max_over_mean", "moe_compact_share",
        "moe_gmm_tile_fill", "moe_gmm_roofline", *NEW_METRICS}
    for name in NEW_METRICS:            # the four this cell adds list it alone
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
    for name in listed:                 # each has its file and its reader
        spec = benchlib.Roots().data("metrics", name)
        benchlib.Roots().module("readers", spec["reader"])
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) == \
        (1, CONFIG, TRAFFIC)
    assert len(bench["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_new_metric_files_read_a_rehearsals_samples(traced):
    """``attn_window_tile_share`` from the counters the builder copied, and
    ``flash_attn_swa_roofline`` from a trace in which each of the six kernels
    took twice its least time: 50 %.  A program without the counter or the
    kernels (the parent's cells) gives them nothing to read."""
    _, _, outcome = traced
    samples = outcome["samples"]
    roots = benchlib.Roots()
    spec = roots.data("metrics", "attn_window_tile_share")
    reader = roots.module("readers", spec["reader"])
    assert reader.read(types.SimpleNamespace(samples=samples, metric=spec)) \
        == samples["attn"]["window_tile_share"]
    assert reader.read(types.SimpleNamespace(samples={}, metric=spec)) is None
    assert reader.read(types.SimpleNamespace(
        samples={"attn": {"window_layers": 4.0}}, metric=spec)) is None

    spec = roots.data("metrics", "flash_attn_swa_roofline")
    assert spec["params"] == {"kernel": "flash_attention_swa"}
    reader = roots.module("readers", spec["reader"])
    cfg, traffic = roots.data("configs", CONFIG), roots.data("traffic",
                                                             TRAFFIC)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    calls = roots.module("kernels", "flash_attention_swa").calls_per_step(
        cfg, traffic)
    least = {c["pattern"]: c["count"] * max(
        c["flops"] / peaks["bf16_flops"],
        c["bytes"] / peaks["hbm_bytes_per_s"]) for c in calls}
    steps = benchlib.traced_steps(samples)
    assert steps

    class Trace:
        missing: tuple = ()

        def matching_s(self, patterns):
            (pattern,) = patterns
            if pattern in self.missing:
                return 0.0, 0
            return 2.0 * least[pattern] * steps, steps

    rc = types.SimpleNamespace(samples=samples, metric=spec, trace=Trace(),
                               peaks=peaks, config=cfg, traffic=traffic,
                               roots=roots, log=lambda line: None)
    assert reader.read(rc) == pytest.approx(50.0)
    rc.trace.missing = ("flash_attention_kvb_swa_fwd",)   # the parent
    assert reader.read(rc) is None
    rc.trace = None                     # an untraced run: nothing to read
    assert reader.read(rc) is None


def test_the_kernel_counts_are_hand_counts():
    """Attention: four window calls a pass over the band's 25,167,872 pairs
    a head and one full call over the triangle's 33,558,528, 48 heads of
    128: forward 4 x 0.619 + 0.825, in all 11.5 TFLOP a step, every call
    compute-bound on a v5e.  The grouped products: nine a sparse layer over
    four sparse layers as run (``num_hidden_layers`` 5 less
    ``num_dense_layers`` 1), ``2 x pairs x 3,072 x 3,072`` operations each
    at the cell's mean load of 128 pairs an expert, 8 held: bytes set their
    least time (151 MB of weights against 19 GFLOP)."""
    from znicz_tpu.ops.pallas import attention as pattn, grouped

    roots = benchlib.Roots()
    cfg, traffic = roots.data("configs", CONFIG), roots.data("traffic",
                                                             TRAFFIC)
    calls = {c["pattern"]: c for c in roots.module(
        "kernels", "flash_attention_swa").calls_per_step(cfg, traffic)}
    assert set(calls) == {*pattn.KVB_SWA_KERNEL_NAMES.values(),
                          pattn.KVB_FWD_KERNEL_NAME,
                          pattn.KVB_DKV_KERNEL_NAME, pattn.KVB_DQ_KERNEL_NAME}
    swa, full = calls["flash_attention_kvb_swa_fwd"], \
        calls["flash_attention_kvb_fwd"]
    assert (swa["count"], full["count"]) == (4, 1)
    assert swa["flops"] == 2 * 2.0 * 48 * 25_167_872 * 128
    assert full["flops"] == 2 * 2.0 * 48 * 33_558_528 * 128
    assert swa["bytes"] == 2 * 2.0 * 8192 * 48 * 128 + \
        2 * 2.0 * 8192 * 8 * 128 + 4.0 * 48 * 8192
    total = sum(c["count"] * c["flops"] for c in calls.values())
    assert total == pytest.approx(11.5e12, rel=1e-2)
    for c in calls.values():
        assert c["flops"] / 197e12 > c["bytes"] / 819e9
    assert calls["flash_attention_kvb_swa_dkv"]["flops"] == 2 * swa["flops"]
    assert calls["flash_attention_kvb_swa_dq"]["flops"] == swa["flops"] / 2
    # a stack without window layers has no window call
    none = roots.module("kernels", "flash_attention_swa").calls_per_step(
        {**cfg, "layer_types": ["full_attention"] * 5}, traffic)
    assert {c["pattern"] for c in none} == {
        "flash_attention_kvb_fwd", "flash_attention_kvb_dkv",
        "flash_attention_kvb_dq"}

    pairs = 4 * 8192 * 4 * 8 / 256                        # a step, 4 layers
    (call,) = roots.module("kernels", "moe_gmm").calls_per_step(
        {**cfg, "moe_pairs_held_per_step": pairs}, traffic)
    assert call["count"] == 9 * 4
    assert call["flops"] == 2.0 * 1024 * 3072 * 3072
    assert call["bytes"] == 2.0 * (1024 * 6144 + 8 * 3072 * 3072)
    assert call["flops"] / 197e12 < call["bytes"] / 819e9
    for name in (grouped.ROWS_KERNEL_NAME, grouped.ROWS_T_KERNEL_NAME,
                 grouped.WEIGHTS_KERNEL_NAME):
        assert call["pattern"] in name


def test_the_step_books_the_new_parts_under_their_scopes():
    """A window layer's attention stands under ``block<i>.attn.swa`` and a
    full layer's under ``block<i>.attn``; every layer's gate under
    ``block<i>.attn.gate``; the dense layer's SwiGLU under ``.mlp``, the
    sparse ones' parts under ``.moe`` and its three; hardly an operation
    bare."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.observe import probe
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    def matches(metric, scope):
        spec = benchlib.Roots().data("metrics", metric)
        return any(re.compile(p).fullmatch(scope)
                   for p in spec["params"]["patterns"])

    assert matches("attn_swa_device_ms_per_step", "block0.attn.swa")
    assert matches("attn_gate_device_ms_per_step", "block12.attn.gate")
    assert not matches("attn_device_ms_per_step", "block0.attn.swa")
    assert not matches("attn_swa_device_ms_per_step", "block0.attn")
    cfg = {**benchlib.Roots().data("configs", CONFIG), **TINY_TRINITY}
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
    assert {"embed", "ce", "update", "block0.attn", "block0.attn.swa",
            "block0.attn.gate", "block0.mlp", "block1.attn",
            "block1.attn.gate", "block1.moe", "block1.moe.route",
            "block1.moe.experts", "block1.moe.shared", "block2.attn.swa",
            "block2.attn.gate", "block2.moe.experts"} <= seen
    assert "block1.attn.swa" not in seen and "block0.moe" not in seen
    bare = [n for n, sc in scopes.items() if not sc]
    assert len(bare) < 0.02 * len(scopes), bare


def test_operation_count_is_the_issues_arithmetic():
    """Forward, a token (ISSUE 48): the attention sub-layer's five
    projections 125.8 M, its scores 75.5 M on a window layer (the band's
    pairs) and 100.7 M on the full one, the dense SwiGLU 226.5 M, the shared
    expert 56.6 M, the routed experts' held share 7.1 M, the head 154.1 M; a
    step of 8,192 tokens 41 TFLOP, attention's scores 9.9 of it."""
    roots = benchlib.Roots()
    cfg = roots.data("configs", CONFIG)
    ref = roots.module("reference", "afmoe")
    traffic = roots.data("traffic", TRAFFIC)
    t, batch = int(traffic["seq_len"]), int(traffic["minibatch_size"])
    parts = ref.forward_flops_per_token(cfg, t)
    proj = 2.0 * 3072 * 128 * (3 * 48 + 2 * 8)
    assert proj == pytest.approx(125.8e6, rel=1e-3)
    assert parts["attn_window"] - proj == pytest.approx(75.5e6, rel=1e-3)
    assert parts["attn_full"] - proj == pytest.approx(100.7e6, rel=1e-3)
    assert parts["dense"] == pytest.approx(226.5e6, rel=1e-3)
    assert parts["shared"] == pytest.approx(56.6e6, rel=1e-3)
    assert parts["routed"] == pytest.approx(7.08e6, rel=1e-3)
    assert parts["head"] == pytest.approx(154.1e6, rel=1e-3)
    step = batch * ref.train_flops_per_sample(cfg, t)
    assert step == pytest.approx(41.1e12, rel=1e-2)
    scores = 3 * t * (4 * (parts["attn_window"] - proj) +
                      parts["attn_full"] - proj)
    assert scores == pytest.approx(9.9e12, rel=1e-2)


def test_fp8_control_fails_a_limit(overlay):
    rc, result, outcome = _run(overlay, seed=5, control=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    ref = benchlib.Roots().module("reference", "afmoe")
    control = outcome["samples"]["control_readings"]
    assert any(control[k] > ref.LIMITS[k] for k in control), control


def test_a_program_that_cannot_read_the_family_is_refused_at_once(
        overlay, monkeypatch, capsys):
    """What the parent commit does with this cell: ``arch_from_config``
    refuses the ``model_type`` by name, and the run ends with exit code 1
    and no result line before the reference has run."""
    from znicz_tpu.parallel import transformer as tfm

    ref = benchlib.Roots().module("reference", "afmoe")
    monkeypatch.delitem(tfm._FAMILIES, "afmoe")
    monkeypatch.setattr(ref, "first_steps", lambda *a, **k: pytest.fail(
        "the reference ran before the refusal"))
    rc, result, outcome = _run(overlay, seed=3)
    assert rc == 1 and result is None and outcome is None
    assert "afmoe" in capsys.readouterr().err


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
    """The cell's step (five layers, 1 x 8,192 tokens, 25,088 ids) compiled
    for the described chip with what a v5e would answer at EVERY kernel gate
    the step passes (a flash kernel, and with it the in-place rotary kernel
    of the window layers; the grouped-product kernels; 15.75 GiB):
    1,604,388,096 parameters, the windowed kernels once a window layer a pass
    and the plain blocked ones once for the full layer, the rotary kernel on
    the window layers alone, the grouped kernels, and arguments plus
    temporaries that fit the chip and stand within the plan's margin of its
    footprint."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.ops.pallas import attention as pattn, grouped
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
    assert n_params == 1_604_388_096
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
    text = compiled.as_text()

    def stands(name):
        return len(re.findall(
            rf'custom_call_target="tpu_custom_call"[^\n]*{name}\b', text))

    for pass_ in ("fwd", "dkv", "dq"):
        assert stands(pattn.KVB_SWA_KERNEL_NAMES[pass_]) == 4, pass_
    for name in (pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
                 pattn.KVB_DQ_KERNEL_NAME):
        assert stands(name) == 1, name
    for name in (grouped.ROWS_KERNEL_NAME, grouped.ROWS_T_KERNEL_NAME,
                 grouped.WEIGHTS_KERNEL_NAME):
        assert stands(re.escape(name)) >= 4, name
    assert " ragged-dot(" not in text          # none left to XLA's own
