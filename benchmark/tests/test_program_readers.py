"""The readers ISSUE 24 added (``scope_device``, ``program_spans``): their
metric files agree with ``BENCHMARK.json``, a traced rehearsal on the CPU
(no device plane, so no device metric) reads the host-side one and raises
nothing, and a program without the second sink or the scope map (every
commit before the issue) reads as nothing.  The arithmetic on hand-made
traces is pinned in ``tests/test_observe.py``, which the tier-1 command
collects."""

import types

import pytest

import benchlib
import run
import tiny

NEW = ["conv_device_ms_per_step", "fc_device_ms_per_step",
       "norm_pool_device_ms_per_step", "update_device_ms_per_step",
       "unscoped_device_share", "metrics_read_idle_ms_per_step",
       "step_host_ms_per_step", "unnamed_idle_share"]


@pytest.fixture(scope="module")
def overlay(tmp_path_factory):
    return tiny.write_overlay(str(tmp_path_factory.mktemp("overlay")))


def test_metric_files_agree_with_benchmark_json():
    roots = benchlib.Roots()
    bench = benchlib.benchmark_json(roots)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        spec, entry = roots.data("metrics", name), entries[name]
        for key in ("unit", "layer", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert hasattr(roots.module("readers", spec["reader"]), "read")
    grouped = [n for n in NEW if "workloads" in entries[n]]
    assert grouped == NEW[:3]


def test_traced_rehearsal_reads_the_host_side_metric(overlay):
    rc, result, outcome = run.execute(
        ["--workload", "alexnet_train", "--seed", "13", "--seconds", "2",
         "--trace", "1"], roots_extra=[overlay], allow_cpu=True)
    assert rc == 0 and result["correct"] is True, outcome["lines"]
    got = set(result["metrics"])
    assert "step_host_ms_per_step" in got
    assert result["metrics"]["step_host_ms_per_step"]["value"] > 0
    # no TPU plane on the CPU: no device metric, and nothing raised
    assert not got & set(NEW[:6])
    names = {e["name"] for e in outcome["samples"]["program_spans"]}
    assert {"workflow.step", "train.dispatch", "train.metrics_read"} <= names


def test_a_program_without_the_sinks_reads_as_nothing(monkeypatch):
    from znicz_tpu.observe import probe, trace

    roots = benchlib.Roots()
    monkeypatch.delattr(probe, "scope_map")
    tracer = trace.Tracer()
    del tracer.live_names
    monkeypatch.setattr(trace, "TRACER", tracer)
    fake = types.SimpleNamespace(devices={"/device:TPU:0": [(0, 1, "a", "")]},
                                 device_names=["/device:TPU:0"], host=[],
                                 path="")
    rc = types.SimpleNamespace(
        trace=fake, log=lambda msg: None, metric={},
        samples={"kind": "train", "k": 2, "traced_windows": [0, 1],
                 "program_spans": [], "step_unit": "s"})
    for name in NEW:
        rc.metric = roots.data("metrics", name)
        reader = roots.module("readers", rc.metric["reader"])
        assert reader.read(rc) is None, name
