"""CPU rehearsal of the ``lfm2_train_ep4_t4096`` cell: ``run.py`` end to end
over a tiny overlay of its configuration and traffic (every mechanism kept:
both mixers, a dense and three sparse layers, 4 of 16 experts held, top-2),
the traced run's new per-layer metrics, and the controls that must come out
as not correct: the reference in fp8, and a router that is not the model's.
"""

import copy
import json
import os

import pytest

import benchlib
import run
import tiny

CELL = "lfm2_train_ep4_t4096"
TINY_LFM2 = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 61,
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "num_hidden_layers": 4, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "router_width": 16,
    "experts_held": {"first": 4, "count": 4}, "hyper": {"lr": 0.05},
    "builders": {"lm_train_arch": {"loss_chunks": 2}},
}
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 32,
               "k_steps": 2}


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("lfm2_overlay"))
    for kind, name, changes in (
            ("configs", "lfm2_24b_a2b", TINY_LFM2),
            ("traffic", "train_tokens_ep4_t4096", TINY_TOKENS)):
        doc = copy.deepcopy(benchlib.load_json(
            os.path.join(tiny.BENCH_DIR, kind, name + ".json")))
        doc.update(changes)
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
    # 64 tokens x top-2 over 16 experts, 4 held, 3 sparse layers
    assert 0 < moe["pairs_held_per_step"] < 3 * 64 * 2
    assert moe["load_max_over_mean"] >= 1.0


def test_traced_run_reports_the_new_per_layer_metrics(overlay):
    rc, result, outcome = _run(overlay, seed=13, seconds=2.0, trace=1)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    assert {"graph_ms_per_step", "train_step_rate_median",
            "moe_expert_load_max_over_mean"} <= got
    # the epoch's one blocking read, and no other, inside the step unit
    names = [e["name"] for e in outcome["samples"]["program_spans"]]
    steps = sum(1 for e in outcome["samples"]["program_spans"]
                if e["name"] == "lm.dispatch")
    assert 0 < names.count("lm.loss_read") <= steps // 6 + 1


def test_fp8_control_fails_a_limit(overlay):
    rc, result, outcome = _run(overlay, seed=5, control=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    ref = benchlib.Roots().module("reference", "lfm2_moe")
    control = outcome["samples"]["control_readings"]
    assert any(control[k] > ref.LIMITS[k] for k in control), control


@pytest.mark.parametrize("wrong", ["softmax", "no_bias", "held_only"])
def test_a_router_that_is_not_the_models_is_not_correct(overlay,
                                                         monkeypatch, wrong):
    """Softmax for sigmoid, the selection bias left out, or weights
    normalised over the held experts only: each must fail a limit."""
    import jax.numpy as jnp

    from znicz_tpu.parallel import moe

    real = moe.route_top_k

    def softmax(scores_in, bias, top_k, score="sigmoid", norm_topk=True,
                scale=1.0):
        return real(scores_in, bias, top_k, "softmax", norm_topk, scale)

    def no_bias(scores_in, bias, top_k, score="sigmoid", norm_topk=True,
                scale=1.0):
        return real(scores_in, None, top_k, score, norm_topk, scale)

    def held_only(scores_in, bias, top_k, score="sigmoid", norm_topk=True,
                  scale=1.0):
        choice, w = real(scores_in, bias, top_k, score, False, scale)
        held = (choice >= 4) & (choice < 8)       # TINY_LFM2.experts_held
        w = w / (jnp.where(held, w, 0).sum(-1, keepdims=True) + 1e-6)
        return choice, w

    monkeypatch.setattr(moe, "route_top_k", locals()[wrong])
    rc, result, outcome = _run(overlay, seed=17)
    assert rc == 0 and result["correct"] is False, outcome["lines"]
    assert any("FAILED" in ln for ln in outcome["lines"])
