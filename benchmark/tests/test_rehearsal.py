"""CPU rehearsals: ``run.py`` end to end at tiny sizes for each builder kind
(Pallas interpreted or bypassed, four virtual devices for the data-parallel
cell), the refusal to run without a TPU, the controls and the broken timed
paths that must come out as not correct, and a dummy of each kind of
plug-in loaded from a temporary directory."""

import json
import os
import subprocess
import sys

import pytest

import benchlib
import limits
import run
import tiny

CELLS = ["alexnet_train", "cgpt_train_t2048", "cgpt_serve_chat",
         "alexnet_train_dp4"]


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    return tiny.write_overlay(str(tmp_path_factory.mktemp("overlay")))


def _run(overlay, workload, seed=7, seconds=1.0, trace=0, control=False):
    return run.execute(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)],
                       roots_extra=[overlay], allow_cpu=True, control=control)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end_tiny(overlay, workload, capsys):
    rc, result, outcome = _run(overlay, workload, seed=2147483700)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    assert result["failed"] == 0 and result["attempted"] > 0
    bench = benchlib.load_json(os.path.join(overlay, "BENCHMARK.json"))
    e2e, _ = run._cell_metrics(bench, workload)
    assert set(result["metrics"]) == {m["name"] for m in e2e}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["count"] == (4 if workload.endswith("dp4") else 1)
    # the last line of standard output is the result object, alone
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == result
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    if outcome["samples"]["kind"] == "train":
        assert len(outcome["samples"]["walls"]) >= 1


@pytest.mark.parametrize("workload", ["alexnet_train", "cgpt_serve_chat"])
def test_traced_run_reports_per_layer_metrics(overlay, workload):
    rc, result, _ = _run(overlay, workload, seed=11, seconds=2.0, trace=1)
    assert rc == 0 and result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    host_side = {"alexnet_train": {"graph_ms_per_step",
                                   "train_step_rate_median"},
                 "cgpt_serve_chat": {"serve_queue_ms_p95",
                                     "serve_batch_occupancy",
                                     "serve_prefill_ms_p50",
                                     "serve_decode_step_ms_p50",
                                     "loadgen_late_ms_p95"}}[workload]
    assert host_side <= set(result["metrics"])
    assert "setup_s" not in result["metrics"]


def test_command_line_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH_DIR, "run.py"),
         "--workload", "alexnet_train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tiny.CHECKOUT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


# -- the controls and the broken paths ------------------------------------------

@pytest.mark.parametrize("workload,seed", [("alexnet_train", 5),
                                           ("cgpt_train_t2048", 5),
                                           ("cgpt_serve_chat", 12)])
def test_control_in_the_precision_below_fails_a_limit(overlay, workload,
                                                      seed):
    """The reference computed in fp8, put in the program's place, must
    fail one of the cell's numbers; the sound program passes all.  (At
    these toy sizes a dozen served tokens or two sequences do not fail the
    chip-size limits under every seed; these seeds do.  The readings at
    the cells' own size are in PERF.md.)"""
    rc, result, outcome = _run(overlay, workload, seed=seed, control=True)
    assert rc == 0 and result["correct"] is True
    ref = benchlib.Roots().module(
        "reference", "alexnet" if workload.startswith("alexnet")
        else "gpt2_block")
    control = outcome["samples"]["control_readings"]
    assert any(control[k] > ref.LIMITS[k] for k in control), control
    sound = outcome["samples"]["readings"]
    assert all(sound[k] <= ref.LIMITS[k] for k in sound), sound


def test_step_that_returns_its_state_unchanged_is_not_correct(overlay,
                                                              monkeypatch):
    from znicz_tpu.parallel.step import FusedTrainStep

    monkeypatch.setattr(FusedTrainStep, "_apply_update",
                        lambda self, params, grads, hyper, bs: params)
    rc, result, outcome = _run(overlay, "alexnet_train", seed=31)
    assert rc == 0 and result["correct"] is False
    assert any("delta_norm_gap" in ln and "FAILED" in ln
               for ln in outcome["lines"])


def test_part_of_the_batch_left_out_is_not_correct(overlay, monkeypatch):
    from znicz_tpu.units.lm import TransformerLMStep

    real = TransformerLMStep._stage_batch

    def half(self, tokens, labels, count):
        return real(self, tokens, labels, max(1, count // 2))

    monkeypatch.setattr(TransformerLMStep, "_stage_batch", half)
    rc, result, outcome = _run(overlay, "cgpt_train_t2048", seed=37)
    assert rc == 0 and result["correct"] is False, outcome["lines"]


def test_token_altered_where_it_is_produced_is_not_correct(overlay,
                                                           monkeypatch):
    import numpy as np

    from znicz_tpu.serve.kvcache import TokenSampler

    monkeypatch.setattr(TokenSampler, "sample",
                        lambda self, logits: int(np.argmin(logits)))
    rc, result, outcome = _run(overlay, "cgpt_serve_chat", seed=41)
    assert rc == 0 and result["correct"] is False
    assert any("served_logit_gap" in ln and "FAILED" in ln
               for ln in outcome["lines"])


def test_limits_tool_reads_sound_and_control(overlay, capsys):
    rc = limits.main(["--workload", "cgpt_train_t2048", "--seeds", "3,4",
                      "--control", "1", "--seconds", "0.5"],
                     roots_extra=[overlay], allow_cpu=True)
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1]
                         .removeprefix("[limits] "))
    assert len(summary["grad_norm_gap"]["sound"]) == 2
    assert summary["grad_norm_gap"]["control_min"] > \
        summary["grad_norm_gap"]["sound_max"]


# -- new files only ---------------------------------------------------------------

def test_a_dummy_of_each_plug_in_loads_from_a_directory_of_its_own(
        overlay, tmp_path):
    """A later PR adds a configuration, a traffic mix, a per-layer metric
    with its reader and a kernel count as new files plus one entry each,
    and edits no file that is there."""
    root = str(tmp_path)
    base = benchlib.Roots([overlay])
    cfg = dict(base.data("configs", "cerebras_gpt_1.3b"), name="dummy_cfg")
    mix = dict(base.data("traffic", "train_tokens_t2048"), name="dummy_mix")
    for kind, name, doc in (("configs", "dummy_cfg", cfg),
                            ("traffic", "dummy_mix", mix),
                            ("metrics", "dummy_metric", {
                                "name": "dummy_metric", "unit": "ops",
                                "layer": "kernels",
                                "moves": "train_samples_per_s",
                                "source": "program_counter",
                                "reader": "dummy_reader", "params": {}})):
        os.makedirs(os.path.join(root, kind), exist_ok=True)
        with open(os.path.join(root, kind, name + ".json"), "w") as f:
            json.dump(doc, f)
    for kind, name, body in (
            ("kernels", "dummy_kernel",
             "def ops(cfg):\n    return 2.0 * cfg['n_embd']\n"),
            ("readers", "dummy_reader",
             "def read(rc):\n    return rc.roots.module('kernels', "
             "'dummy_kernel').ops(rc.config)\n")):
        os.makedirs(os.path.join(root, kind), exist_ok=True)
        with open(os.path.join(root, kind, name + ".py"), "w") as f:
            f.write(body)
    bench = benchlib.load_json(os.path.join(overlay, "BENCHMARK.json"))
    bench["workloads"].append({"name": "dummy_cell", "config": "dummy_cfg",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "rehearsal"})
    bench["end_to_end"][0]["workloads"].append("dummy_cell")
    bench["per_layer"].append({"name": "dummy_metric", "unit": "ops",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "kernels",
                               "moves": "train_samples_per_s",
                               "workloads": ["dummy_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, result, _ = run.execute(
        ["--workload", "dummy_cell", "--seed", "5", "--seconds", "1",
         "--trace", "1"], roots_extra=[root, overlay], allow_cpu=True)
    assert rc == 0 and result["correct"] is True
    assert result["metrics"]["dummy_metric"] == {"value": 128.0,
                                                 "unit": "ops"}
    assert "graph_ms_per_step" in result["metrics"]
