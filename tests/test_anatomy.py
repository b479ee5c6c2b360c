"""Step-time anatomy (ISSUE 20): the phase hook's gate, the per-rank
straggler rule and goodput note plumbing.  The split-dispatch producers
went with ISSUE 24 (the train planes feed ``znicz_anatomy_step_seconds``
from the dispatch cadence; tests/test_observe.py), the ``StepAnatomy``
accountant with ISSUE 37 (the stall watch: tests/test_stall_watch.py).
"""

import pytest

from znicz_tpu.observe import probe, registry


def _flat(**kw):
    return registry.REGISTRY.snapshot_flat(skip_zero=False, **kw)


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
