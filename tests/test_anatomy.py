"""Step-time anatomy (ISSUE 20): the StepAnatomy accountant, MFU gauge
wiring, the per-rank straggler rule, goodput note plumbing, and the
bench perf-regression sentinel (synthetic 20% cliff flagged; the real
recorded r04->r05 pair passes).  The split-dispatch producers went with
ISSUE 24 (the train planes feed ``znicz_anatomy_step_seconds`` from the
dispatch cadence; tests/test_observe.py).
"""

import importlib.util
import json
import os

import pytest

from znicz_tpu.observe import probe, registry
from znicz_tpu.observe.anatomy import TRAIN_PHASES, StepAnatomy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _flat(**kw):
    return registry.REGISTRY.snapshot_flat(skip_zero=False, **kw)


# -- the accountant ----------------------------------------------------------

def test_step_anatomy_stamps_and_pretouch(monkeypatch):
    """Stamps charge cursor->now per phase (deterministic via injected
    nows), every child exists at construction, and finish() emits the
    step counter + MFU from the registered analytic FLOPs."""
    monkeypatch.setenv("ZNICZ_TPU_PEAK_FLOPS", "1e9")
    anat = StepAnatomy("anat_unit", TRAIN_PHASES)
    # pre-touch: all children live at 0 before any step
    flat = _flat()
    assert flat['znicz_anatomy_steps_total{plane="anat_unit"}'] == 0.0
    for phase in TRAIN_PHASES:
        assert flat['znicz_anatomy_phase_seconds_count'
                    f'{{plane="anat_unit",phase="{phase}"}}'] == 0.0
    assert flat['znicz_anatomy_mfu{plane="anat_unit"}'] == 0.0

    anat.set_flops(2e8)                  # with peak 1e9: mfu = 0.2/wall
    t0 = anat.begin()
    anat.stamp("zero_gather", now=t0 + 0.10)
    anat.stamp("grad", now=t0 + 0.60)
    anat.stamp("collective", now=t0 + 0.75)
    anat.stamp("update", now=t0 + 0.80)
    wall = anat.finish()
    flat = _flat()
    assert flat['znicz_anatomy_phase_seconds_sum'
                '{plane="anat_unit",phase="zero_gather"}'] == \
        pytest.approx(0.10)
    assert flat['znicz_anatomy_phase_seconds_sum'
                '{plane="anat_unit",phase="grad"}'] == pytest.approx(0.50)
    assert flat['znicz_anatomy_phase_seconds_sum'
                '{plane="anat_unit",phase="collective"}'] == \
        pytest.approx(0.15)
    assert flat['znicz_anatomy_steps_total{plane="anat_unit"}'] == 1.0
    # finish() measures the REAL wall (the injected nows are in its
    # future, so the measured step is tiny) — the MFU gauge still set
    assert wall >= 0.0
    assert flat['znicz_anatomy_mfu{plane="anat_unit"}'] > 0.0


def test_observe_phase_respects_probe_gate():
    probe.set_enabled(False)
    try:
        before = _flat().get(
            'znicz_anatomy_phase_seconds_count'
            '{plane="gated",phase="stage"}', 0.0)
        probe.anatomy_phase("gated", "stage", 0.5)
        after = _flat().get(
            'znicz_anatomy_phase_seconds_count'
            '{plane="gated",phase="stage"}', 0.0)
        assert after == before           # disabled plane records nothing
    finally:
        probe.set_enabled(True)
    probe.anatomy_phase("gated", "stage", 0.5)
    assert _flat()['znicz_anatomy_phase_seconds_count'
                   '{plane="gated",phase="stage"}'] == before + 1.0


# -- goodput plumbing --------------------------------------------------------

def test_goodput_note_and_ratio():
    base = probe.goodput_totals()
    probe.goodput_pretouch(range(2))
    probe.goodput_note("productive", 0, 3.0)
    probe.goodput_note("idle", 1, 1.0)
    probe.goodput_note("productive", 0, -0.5)     # non-positive: ignored
    totals = probe.goodput_totals()
    assert totals["productive"] == pytest.approx(base["productive"] + 3.0)
    assert totals["idle"] == pytest.approx(base["idle"] + 1.0)
    with pytest.raises(ValueError, match="category"):
        probe.goodput_note("wasted", 0, 1.0)
    flat = _flat()
    spent = sum(totals.values())
    assert flat["znicz_goodput_ratio"] == \
        pytest.approx(totals["productive"] / spent)


# -- straggler rule ----------------------------------------------------------

def test_rank_straggler_rule_trips_deterministically():
    """ISSUE 20 acceptance: per-rank step-seconds spread — exactly the
    delayed rank's rule trips on deterministic tower ticks."""
    from znicz_tpu.observe import federation as fed
    from znicz_tpu.observe.registry import Registry

    regs = []
    for _ in range(3):
        r = Registry()
        r.histogram("znicz_anatomy_step_seconds", "step wall",
                    labelnames=("plane",), buckets=(0.05, 0.2, 1.0))
        regs.append(r)
    agg = fed.FleetAggregator(min_refresh_s=0.0)
    for i, r in enumerate(regs):
        agg.add_source(i, r.render_prometheus)
    rules = fed.add_straggler_rules(agg, spread=1.5, window_s=60.0,
                                    min_count=4)
    try:
        assert [r.name for r in rules] == \
            [f"rank_straggler[{i}]" for i in range(3)]
        ts = 5000.0
        for r in regs:
            r.get("znicz_anatomy_step_seconds").labels(plane="fused")
        agg.tower.observe_now(ts=ts)
        for _ in range(8):
            for i, r in enumerate(regs):
                r.get("znicz_anatomy_step_seconds") \
                    .labels(plane="fused") \
                    .observe(0.5 if i == 2 else 0.1)
        agg.tower.observe_now(ts=ts + 5)
        agg.tower.observe_now(ts=ts + 10)
        assert [r.trips > 0 for r in rules] == [False, False, True], \
            [(r.name, r.trips, r.last_value) for r in rules]
        # a healthy spread never trips: continue with uniform steps
        for _ in range(8):
            for r in regs:
                r.get("znicz_anatomy_step_seconds") \
                    .labels(plane="fused").observe(0.1)
        agg.tower.observe_now(ts=ts + 80)     # old spread aged out
        agg.tower.observe_now(ts=ts + 85)
        assert rules[2].trips == 1            # no re-trip once healthy
    finally:
        agg.close()


# -- bench sentinel ----------------------------------------------------------

def _sentinel():
    spec = importlib.util.spec_from_file_location(
        "bench_sentinel", os.path.join(REPO, "tools",
                                       "bench_sentinel.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _round_file(tmp_path, name, value, rc=0,
                metric="fc_train_samples_per_sec", unit="samples/sec"):
    doc = {"n": 1, "cmd": "bench", "rc": rc, "parsed": None,
           "tail": json.dumps({"metric": metric, "value": value,
                               "unit": unit, "vs_baseline": 1.0})}
    path = tmp_path / name
    path.write_text(json.dumps(doc))
    return str(path)


def test_sentinel_flags_synthetic_regression(tmp_path, capsys):
    sentinel = _sentinel()
    old = _round_file(tmp_path, "old.json", 1000.0)
    new = _round_file(tmp_path, "new.json", 800.0)   # -20% throughput
    assert sentinel.main([old, new]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "fc_train_samples_per_sec" in out
    # report-only always exits 0; an improvement or within-band move
    # never fails
    assert sentinel.main([old, new, "--report-only"]) == 0
    better = _round_file(tmp_path, "better.json", 1050.0)
    assert sentinel.main([old, better]) == 0
    # a wider band tolerates the same cliff
    assert sentinel.main([old, new, "--band", "0.25"]) == 0


def test_sentinel_orientation_and_one_sided(tmp_path):
    sentinel = _sentinel()
    assert sentinel.lower_is_better("serve_latency_p95", "seconds")
    assert not sentinel.lower_is_better("train_samples_per_sec",
                                        "samples/sec")
    # time-like metric regresses UP
    old = _round_file(tmp_path, "o.json", 1.0, metric="step_seconds",
                      unit="seconds")
    new = _round_file(tmp_path, "n.json", 1.3, metric="step_seconds",
                      unit="seconds")
    assert sentinel.main([old, new]) == 1
    # one-sided metrics report but never fail
    findings = sentinel.compare(
        {"only_old": {"value": 5.0, "unit": "samples/sec"}},
        {"only_new": {"value": 7.0, "unit": "samples/sec"}})
    kinds = {f["metric"]: f["kind"] for f in findings}
    assert kinds == {"only_old": "dropped", "only_new": "new"}


def test_sentinel_passes_real_recorded_rounds():
    """The recorded BENCH_r04 -> BENCH_r05 pair is an improvement and
    must pass the default band."""
    r04 = os.path.join(REPO, "BENCH_r04.json")
    r05 = os.path.join(REPO, "BENCH_r05.json")
    if not (os.path.exists(r04) and os.path.exists(r05)):
        pytest.skip("recorded bench rounds not present")
    sentinel = _sentinel()
    assert sentinel.main([r04, r05]) == 0
