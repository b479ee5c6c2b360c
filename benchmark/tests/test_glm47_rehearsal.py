"""CPU rehearsal of the ``glm47_flash_train_ep8_t4096`` cell: ``run.py`` end
to end over a tiny overlay of its configuration and traffic (every mechanism
kept: latent attention in every layer, a dense and two sparse layers with
their shared expert, 4 of 16 experts held, top-2, an untied head, the MTP
module and its second loss term), the traced run's per-layer metrics with
the readers this cell adds, the control that must come out as not correct
(the reference in fp8), and the refusal a program that cannot read the
family gives before the reference runs.
"""

import copy
import json
import os

import pytest

import benchlib
import run
import tiny

CELL = "glm47_flash_train_ep8_t4096"
TINY_GLM = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 32, "vocab_size": 61, "num_hidden_layers": 3,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "router_width": 16,
    "experts_held": {"first": 4, "count": 4}, "hyper": {"lr": 0.05},
}
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 32,
               "k_steps": 2}


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("glm47_overlay"))
    for kind, name, changes in (
            ("configs", "glm_4_7_flash", TINY_GLM),
            ("traffic", "train_tokens_ep8_t4096", TINY_TOKENS)):
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
    readings = outcome["samples"]["readings"]
    assert set(readings) == {"loss_gap", "grad_norm_gap", "delta_norm_gap",
                             "grad_diff_gap"}
    moe = outcome["samples"]["moe"]
    # 64 tokens x top-2 over 16 experts, 4 held, 2 sparse layers + the MTP's
    assert 0 < moe["pairs_held_per_step"] < 3 * 64 * 2
    assert moe["load_max_over_mean"] >= 1.0
    # the weighted second term is a real share of the loss (12 rows are
    # soon learned by heart, the next token sooner than the second-next)
    assert 0.1 < moe["mtp_loss_share"] < 0.9
    assert any(ln.startswith("loss terms") for ln in outcome["lines"])


def test_traced_run_reports_every_metric_that_lists_the_cell(overlay):
    rc, result, outcome = _run(overlay, seed=13, seconds=2.0, trace=1)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    # the device-trace readers find no TPU plane on the CPU; the program's
    # counters and spans are all there
    assert {"graph_ms_per_step", "train_step_rate_median",
            "moe_expert_load_max_over_mean", "moe_compact_share",
            "moe_gmm_tile_fill", "mtp_loss_share"} <= got
    bench = benchlib.benchmark_json(benchlib.Roots())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"attn_device_ms_per_step", "mla_latent_device_ms_per_step",
            "shared_expert_device_ms_per_step", "mtp_device_ms_per_step",
            "flash_attn_mla_roofline", "mtp_loss_share"} <= listed
    assert "moe_gmm_roofline" not in listed
    for name in listed:                 # each has its file and its reader
        spec = benchlib.Roots().data("metrics", name)
        benchlib.Roots().module("readers", spec["reader"])


def test_the_new_scopes_match_the_new_patterns_whole():
    import re

    def matches(metric, scope):
        spec = benchlib.Roots().data("metrics", metric)
        return any(re.compile(p).fullmatch(scope)
                   for p in spec["params"]["patterns"])

    assert matches("mla_latent_device_ms_per_step", "block5.attn.latent")
    assert not matches("attn_device_ms_per_step", "block5.attn.latent")
    assert matches("attn_device_ms_per_step", "block5.attn")
    assert matches("shared_expert_device_ms_per_step", "block2.moe.shared")
    assert not matches("moe_route_device_ms_per_step", "block2.moe.shared")
    assert matches("mtp_device_ms_per_step", "mtp.proj")
    assert matches("mtp_device_ms_per_step", "mtp.ce")
    assert not matches("ce_device_ms_per_step", "mtp.ce")


def test_kernel_counts_are_the_issues_arithmetic():
    """6 layers x 7 products of 2 * 2 * 20 * 4096^2 * 256 / 2 operations:
    36.6 ms at the chip's 197 TFLOP/s; every kernel compute-bound."""
    roots = benchlib.Roots()
    cfg = roots.data("configs", "glm_4_7_flash")
    traffic = roots.data("traffic", "train_tokens_ep8_t4096")
    calls = roots.module("kernels", "flash_attention_mla").calls_per_step(
        cfg, traffic)
    from znicz_tpu.ops.pallas import attention as pattn
    assert [c["pattern"] for c in calls] == [
        pattn.KVB_FWD_KERNEL_NAME, pattn.KVB_DKV_KERNEL_NAME,
        pattn.KVB_DQ_KERNEL_NAME]
    product = 2.0 * 2 * 20 * 4096 * 4096 * 256 / 2
    assert [c["flops"] / product for c in calls] == [2, 4, 1]
    assert all(c["count"] == 6 for c in calls)
    least = sum(c["count"] * c["flops"] for c in calls) / 197e12
    assert least == pytest.approx(0.0366, rel=5e-3)
    assert all(c["flops"] / 197e12 > c["bytes"] / 819e9 for c in calls)


def test_fp8_control_fails_a_limit(overlay):
    rc, result, outcome = _run(overlay, seed=5, control=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    ref = benchlib.Roots().module("reference", "glm4_moe_lite")
    control = outcome["samples"]["control_readings"]
    assert any(control[k] > ref.LIMITS[k] for k in control), control


def test_a_program_that_cannot_read_the_family_is_refused_at_once(
        overlay, monkeypatch, capsys):
    """What the parent commit does with this cell: ``arch_from_config``
    refuses the ``model_type`` by name, and the run ends with exit code 1
    and no result line before the reference has run."""
    from znicz_tpu.parallel import transformer as tfm

    ref = benchlib.Roots().module("reference", "glm4_moe_lite")
    monkeypatch.delitem(tfm._FAMILIES, "glm4_moe_lite")
    monkeypatch.setattr(ref, "first_steps", lambda *a, **k: pytest.fail(
        "the reference ran before the refusal"))
    rc, result, outcome = _run(overlay, seed=3)
    assert rc == 1 and result is None and outcome is None
    assert "glm4_moe_lite" in capsys.readouterr().err
