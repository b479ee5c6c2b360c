"""CPU rehearsal of the reader ISSUE 50 adds (``scope_pass``) and its seven
metrics: a traced run of a tiny state-space cell and of the tiny AlexNet
cell, after which every new metric the cell lists reads a number; a program
without ``probe.scope_table`` (every commit before the issue) reads as
nothing and raises nothing.

The CPU's profile has no device plane, so the operations the reader is
handed are made from the program's own table, one of a microsecond for
every instruction of the step: what is rehearsed is the join, the keys and
the metric files, not a time.  The arithmetic on hand-made operations is
pinned in ``tests/test_scope_table.py``, which the tier-1 command collects.
"""

import copy
import json
import os
import re
import types

import pytest

import benchlib
import run
import tiny

SSM_CELL = "granite4h_micro_train_pp4_t8192"
NEW = ["remat_device_ms_per_step", "bwd_device_ms_per_step",
       "lent_scope_device_share", "mixed_fusion_device_share",
       "ssm_in_device_ms_per_step", "ssm_gate_device_ms_per_step",
       "ssm_out_device_ms_per_step"]
TINY_GRANITE = {
    "hidden_size": 64, "shared_intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 61,
    "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
    "mamba_chunk_size": 8, "attention_multiplier": 0.125,
    "hyper": {"lr": 0.05},
}
TINY_TOKENS = {"n_rows": 12, "minibatch_size": 2, "seq_len": 32,
               "k_steps": 2}


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    root = tiny.write_overlay(str(tmp_path_factory.mktemp("overlay")))
    for kind, name, changes in (
            ("configs", "granite_4_0_h_micro", TINY_GRANITE),
            ("traffic", "train_tokens_pp4_t8192", TINY_TOKENS)):
        doc = copy.deepcopy(benchlib.load_json(
            os.path.join(tiny.BENCH_DIR, kind, name + ".json")))
        doc.update(changes)
        if kind == "configs":
            doc["builders"]["lm_train_keys"]["loss_chunks"] = 2
        with open(os.path.join(root, kind, name + ".json"), "w") as f:
            json.dump(doc, f)
    return root


def _listed(cell: str) -> list:
    bench = benchlib.benchmark_json(benchlib.Roots())
    return [m["name"] for m in run._cell_metrics(bench, cell)[1]
            if m["name"] in NEW]


def _hand_made(outcome, monkeypatch, module: str):
    """A reader's context over the run's samples whose chip 0 ran every
    instruction of ``module``'s table once, a microsecond each."""
    from znicz_tpu.observe import probe

    roots = benchlib.Roots()
    rows = probe.scope_table()[module]
    ops = [(1000 * i, 1000 * (i + 1), name, "")
           for i, name in enumerate(rows)]
    monkeypatch.setattr(roots.module("readers", "scope_device"),
                        "module_events",
                        lambda path, plane: [(0, 1000 * len(ops), module)])
    fake = types.SimpleNamespace(devices={"/device:TPU:0": ops},
                                 device_names=["/device:TPU:0"], host=[],
                                 path="")
    lines = []
    rc = types.SimpleNamespace(trace=fake, roots=roots, log=lines.append,
                               metric={}, samples=outcome["samples"])
    return rc, rows, lines


def _read(rc, name):
    rc.metric = rc.roots.data("metrics", name)
    return rc.roots.module("readers", rc.metric["reader"]).read(rc)


def test_the_metric_files_say_what_benchmark_json_says():
    roots = benchlib.Roots()
    entries = {m["name"]: m for m in
               benchlib.benchmark_json(roots)["per_layer"]}
    for name in NEW:
        spec, entry = roots.data("metrics", name), entries[name]
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert spec["reader"] == "scope_pass"
    assert _listed(SSM_CELL) == NEW
    assert _listed("alexnet_train") == _listed("alexnet_train_dp4") == NEW[2:4]
    assert _listed("ouro_train_pp8_t4096") == NEW[:4]


def test_a_traced_state_space_cell_reads_a_number_for_every_new_metric(
        overlay, monkeypatch):
    rc, result, outcome = run.execute(
        ["--workload", SSM_CELL, "--seed", "13", "--seconds", "2",
         "--trace", "1"], roots_extra=[overlay], allow_cpu=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    # no TPU plane on the CPU: no device metric, and nothing raised
    assert not set(result["metrics"]) & set(NEW)
    rc, rows, lines = _hand_made(outcome, monkeypatch, "jit_local_step")
    got = {name: _read(rc, name) for name in _listed(SSM_CELL)}
    assert all(isinstance(v, float) for v in got.values()), got
    steps = benchlib.traced_steps(outcome["samples"])
    per_step = 1e-3 / steps                # a microsecond a row, in ms
    by_way = {w: sum(1 for r in rows.values() if r.by_work[1] == w)
              for w in ("fwd", "remat", "bwd")}
    # a stack with state-space layers is checkpointed: all three passes
    assert min(by_way.values()) > 0 and sum(by_way.values()) == len(rows)
    assert got["remat_device_ms_per_step"] == pytest.approx(
        by_way["remat"] * per_step)
    assert got["bwd_device_ms_per_step"] == pytest.approx(
        by_way["bwd"] * per_step)
    for share in NEW[2:4]:
        assert 0.0 < got[share] < 100.0, share
    # the mixer's parts lie inside what ssm_proj_device_ms_per_step reads,
    # and leave it the norm and the residual sum
    parts = [got[f"ssm_{p}_device_ms_per_step"] for p in ("in", "gate", "out")]
    assert min(parts) > 0
    whole = _read(rc, "ssm_proj_device_ms_per_step")
    assert 0 < sum(parts) < whole
    rest = sum(1 for r in rows.values()
               if r.path and re.fullmatch(r"block\d+\.ssm", r.path[-1]))
    assert whole == pytest.approx(sum(parts) + rest * per_step)
    # the table is logged once, with a row a scope and the sum
    table = [ln for ln in lines if ln.startswith("passes: ")]
    assert any("block0.ssm" in ln for ln in table)
    assert sum("fwd + remat + bwd sum to" in ln for ln in table) == 1


def test_a_traced_alexnet_cell_reads_the_two_shares(overlay, monkeypatch):
    rc, result, outcome = run.execute(
        ["--workload", "alexnet_train", "--seed", "13", "--seconds", "2",
         "--trace", "1"], roots_extra=[overlay], allow_cpu=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    rc, rows, _ = _hand_made(outcome, monkeypatch, "jit__local_train_idx")
    for name in _listed("alexnet_train"):
        assert 0.0 <= _read(rc, name) < 100.0, name
    # the cell lists neither pass metric, but the reader would read them:
    # what is made again is the two checkpointed normalisations' alone
    assert 0.0 < _read(rc, "remat_device_ms_per_step") < \
        _read(rc, "bwd_device_ms_per_step")
    again = {r.path[0].split(".")[0] for r in rows.values()
             if r.by_work[1] == "remat" and r.how == "own"}
    assert again == {"norm"}


def test_a_program_without_the_table_reads_as_nothing(monkeypatch):
    from znicz_tpu.observe import probe

    roots = benchlib.Roots()
    monkeypatch.delattr(probe, "scope_table")
    fake = types.SimpleNamespace(devices={"/device:TPU:0": [(0, 1, "a", "")]},
                                 device_names=["/device:TPU:0"], host=[],
                                 path="")
    said = []
    rc = types.SimpleNamespace(
        trace=fake, roots=roots, log=said.append, metric={},
        samples={"kind": "train", "k": 2, "traced_windows": [0, 1]})
    for name in NEW:
        assert _read(rc, name) is None, name
    assert len(said) == 1 and "no scope table" in said[0]
    # and an untraced run has nothing to join at all
    rc.trace = None
    assert _read(rc, NEW[0]) is None
