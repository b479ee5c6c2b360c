"""CPU rehearsal of the ``ouro_train_pp8_t4096`` cell: ``run.py`` end to end
over a tiny overlay of its configuration and traffic (every mechanism kept:
two sandwich-normed layers run four times over the same weights, the exit
gate, four exit-weighted head passes), the traced run's per-layer metrics
with the builder kind, the reader and the kernel count this cell adds, the
control that must come out as not correct (the reference in fp8), two
planted faults that must too, the refusal a program that cannot read the
family gives before the reference runs, and a compile-only rehearsal of
the step at the real widths for a v5e that is described and not attached,
which reports the compiled step's memory.
"""

import copy
import json
import math
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import benchlib                                       # noqa: E402
import run                                            # noqa: E402
import tiny                                           # noqa: E402

CELL = "ouro_train_pp8_t4096"
TINY_OURO = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 61,
    "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
    "hyper": {"lr": 0.05},
}
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 32,
               "k_steps": 2}
HBM_USABLE = 15.75 * 2 ** 30      # what the runtime leaves of 16 GiB


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("ouro_overlay"))
    for kind, name, changes in (
            ("configs", "ouro_2_6b", TINY_OURO),
            ("traffic", "train_tokens_pp8_t4096", TINY_TOKENS)):
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


def test_cell_runs_end_to_end_tiny(overlay):
    rc, result, outcome = _run(overlay, seed=2147483711)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(outcome["samples"]["readings"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff_gap"}
    loop = outcome["samples"]["loop"]
    assert set(loop) == {"exit_step_mean", "exit_entropy", "loss_step1",
                         "loss_step2", "loss_step3", "loss_step4"}
    assert 1.0 <= loop["exit_step_mean"] <= 4.0
    assert 0.0 <= loop["exit_entropy"] <= math.log(4)
    assert any(ln.startswith("loop (last class pass)")
               for ln in outcome["lines"])
    # the unit's loss terms stay those of an MTP stack: builder
    # lm_train_keys reads ["mtp"] whenever they are not empty
    assert "moe" not in outcome["samples"]


def test_traced_run_reports_every_metric_that_lists_the_cell(overlay):
    rc, result, outcome = _run(overlay, seed=13, seconds=2.0, trace=1)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    # the device-trace readers find no TPU plane on the CPU; the program's
    # counters and spans are all there
    assert {"graph_ms_per_step", "train_step_rate_median",
            "loop_exit_step_mean", "loop_exit_entropy"} <= got
    bench = benchlib.benchmark_json(benchlib.Roots())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"attn_device_ms_per_step", "mlp_device_ms_per_step",
                      "ce_device_ms_per_step", "loop_exit_device_ms_per_step",
                      "loop_exit_step_mean", "loop_exit_entropy",
                      "flash_attn_mha_roofline"}
    for name in listed:                 # each has its file and its reader
        spec = benchlib.Roots().data("metrics", name)
        benchlib.Roots().module("readers", spec["reader"])


def test_the_step_books_every_loop_step_under_the_patterns_the_cell_lists():
    """The scanned body's operations carry the program's scopes, so the
    scope join sums the four loop steps under one name each; the new
    pattern reads ``loop.exit`` whole and nothing else does."""
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

    assert matches("loop_exit_device_ms_per_step", "loop.exit")
    assert not matches("ce_device_ms_per_step", "loop.exit")
    assert matches("attn_device_ms_per_step", "block5.attn")
    assert matches("mlp_device_ms_per_step", "block0.mlp")
    cfg = {**benchlib.Roots().data("configs", "ouro_2_6b"), **TINY_OURO}
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
    assert " while(" in text            # one body, not four copies
    _, scopes = probe.parse_scopes(text)
    seen = {probe_name.rstrip(")").rsplit("(", 1)[-1]
            for probe_name in scopes.values() if probe_name}
    assert {"embed", "ce", "loop.exit", "update", "block0.attn",
            "block0.mlp", "block1.attn", "block1.mlp"} <= seen
    bare = [n for n, sc in scopes.items() if not sc]
    assert len(bare) < 0.02 * len(scopes), bare


def test_kernel_counts_are_the_issues_arithmetic():
    """24 calls (6 layers x 4 loop steps) x 7 products of 2 * 2 * 16 *
    4096^2 * 128 / 2 operations: 58.6 ms at the chip's 197 TFLOP/s; every
    kernel compute-bound."""
    roots = benchlib.Roots()
    cfg = roots.data("configs", "ouro_2_6b")
    traffic = roots.data("traffic", "train_tokens_pp8_t4096")
    calls = roots.module("kernels", "flash_attention_loop").calls_per_step(
        cfg, traffic)
    from znicz_tpu.ops.pallas import attention as pattn
    assert [c["pattern"] for c in calls] == [
        pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
        pattn.KVB_DQ_KERNEL_NAME]
    assert pattn.form_of(4096, 128)[0] == "blocked"
    assert pattn.direct_layout(4096, 128)
    product = 2.0 * 2 * 16 * 4096 * 4096 * 128 / 2
    assert [c["flops"] / product for c in calls] == [2, 4, 1]
    assert all(c["count"] == 24 for c in calls)
    least = sum(c["count"] * c["flops"] for c in calls) / 197e12
    assert least == pytest.approx(0.0586, rel=5e-3)
    assert all(c["flops"] / 197e12 > c["bytes"] / 819e9 for c in calls)
    ref = roots.module("reference", "ouro")
    # 4 x 6 layer applications and four head passes over 49,152 ids
    assert ref.train_flops_per_sample(cfg, 4096) == pytest.approx(
        45.2e12, rel=0.01)


def test_fp8_control_fails_a_limit(overlay):
    rc, result, outcome = _run(overlay, seed=5, control=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    ref = benchlib.Roots().module("reference", "ouro")
    control = outcome["samples"]["control_readings"]
    assert any(control[k] > ref.LIMITS[k] for k in control), control


@pytest.mark.parametrize("fault", ["a loop step left out",
                                   "a row of every step left out"])
def test_a_planted_fault_comes_out_as_not_correct(overlay, monkeypatch,
                                                  fault):
    """The cell's own faults, planted in the program as they were read on
    the chip at the cell's size (PERF.md section 6, PR 34): the stack run
    three times where the model runs it four, and row 1 of every step
    masked out of the loss."""
    from builders import lm_train_keys
    from znicz_tpu.units.lm import TransformerLMStep

    if fault == "a loop step left out":
        keys = lm_train_keys.arch_config
        monkeypatch.setattr(lm_train_keys, "arch_config", lambda cfg: {
            **keys(cfg), "total_ut_steps": cfg["total_ut_steps"] - 1})
    else:
        monkeypatch.setattr(
            TransformerLMStep, "_stage_batch",
            lambda self, tokens, labels, count: self._put(
                tokens, labels, self._arange < min(count, 1)))
    rc, result, outcome = _run(overlay, seed=11)
    assert rc == 0 and result["correct"] is False, outcome["lines"]
    ref = benchlib.Roots().module("reference", "ouro")
    got = outcome["samples"]["readings"]
    assert all(got[k] > ref.LIMITS[k] for k in got), got


def test_a_program_that_cannot_read_the_family_is_refused_at_once(
        overlay, monkeypatch, capsys):
    """What the parent commit does with this cell: ``arch_from_config``
    refuses the ``model_type`` by name, and the run ends with exit code 1
    and no result line before the reference has run."""
    from znicz_tpu.parallel import transformer as tfm

    ref = benchlib.Roots().module("reference", "ouro")
    monkeypatch.delitem(tfm._FAMILIES, "ouro")
    monkeypatch.setattr(ref, "first_steps", lambda *a, **k: pytest.fail(
        "the reference ran before the refusal"))
    rc, result, outcome = _run(overlay, seed=3)
    assert rc == 1 and result is None and outcome is None
    assert "ouro" in capsys.readouterr().err


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
    """The cell's step (six layers x four loop steps, 2 x 4,096 tokens,
    49,152 ids) compiled for the described chip: one scanned body (each of
    the three blocked flash kernels six times in the text, not 24), the
    in-place row kernel for the rotary embedding, and arguments plus
    temporaries that fit the chip with room (13.7 GiB when the rotary
    embedding was XLA's f32 chain; 11.8 with the row kernel)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.ops.pallas import attention as pattn, rope as prope
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    # the step asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(tfm, "_flash_eligible", lambda mesh, interp: True)
    cfg = benchlib.Roots().data("configs", "ouro_2_6b")
    traffic = benchlib.Roots().data("traffic", "train_tokens_pp8_t4096")
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
    assert n_params == 509_661_185
    b, t = int(traffic["minibatch_size"]), int(traffic["seq_len"])
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "seq")))
    mask = jax.ShapeDtypeStruct((b,), jnp.bool_,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(params, tok, tok, mask).compile()
    m = compiled.memory_analysis()
    live = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"compiled step for a described v5e: arguments "
          f"{m.argument_size_in_bytes / 2 ** 30:.3f} GiB (donated), "
          f"temporaries {m.temp_size_in_bytes / 2 ** 30:.3f} GiB, together "
          f"{live / 2 ** 30:.3f} GiB of {HBM_USABLE / 2 ** 30:.2f}")
    assert live < 0.9 * HBM_USABLE, f"{live / 2 ** 30:.2f} GiB: {m}"
    text = compiled.as_text()
    for name in (pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
                 pattn.KVB_DQ_KERNEL_NAME):
        calls = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln and name in ln]
        assert len(calls) == arch.n_layers, (name, len(calls))
    assert prope.KERNEL_NAME in text and " while(" in text
