"""Step-time anatomy (ISSUE 20): the StepAnatomy accountant, the
split-dispatch fused/transformer producers' numerics parity, phase-sum
vs step-wall reconciliation, MFU gauge wiring, the per-rank straggler
rule, goodput note plumbing, and the bench perf-regression sentinel
(synthetic 20% cliff flagged; the real recorded r04->r05 pair passes).
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from znicz_tpu.core import prng
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


# -- fused producer (dp + shard_params + int8) -------------------------------

def _run_fused(anatomy: bool, seed: int = 31):
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    prng.seed_all(seed)
    w = build_fused(max_epochs=2, layers=(32,), minibatch_size=16,
                    n_train=96, n_valid=32,
                    mesh=data_parallel_mesh(4), optimizer="adam",
                    shard_params=True, anatomy=anatomy,
                    quantized_collectives={"mode": "int8",
                                           "error_feedback": True})
    w.initialize(device=XLADevice())
    w.run()
    hist = [h["metric_validation"] for h in w.decision.metrics_history]
    w.stop()
    return hist


def test_anatomy_phase_sum_matches_step_wall(monkeypatch):
    """ISSUE 20 acceptance: on the forced multi-device CPU mesh a
    dp+shard_params+int8 anatomy run attributes per-phase seconds
    summing to within 10% of the measured step wall, counts its steps,
    and reads a nonzero MFU against the pinned nominal peak."""
    monkeypatch.setenv("ZNICZ_TPU_PEAK_FLOPS", "1e12")
    base = _flat()
    base_phase = {k: v for k, v in base.items() if k.startswith(
        'znicz_anatomy_phase_seconds_sum{plane="fused"')}
    base_step = base.get(
        'znicz_anatomy_step_seconds_sum{plane="fused"}', 0.0)
    base_steps = base.get('znicz_anatomy_steps_total{plane="fused"}',
                          0.0)
    hist = _run_fused(anatomy=True)
    assert len(hist) == 2
    flat = _flat()
    phase_sum = sum(
        v - base_phase.get(k, 0.0) for k, v in flat.items()
        if k.startswith('znicz_anatomy_phase_seconds_sum{plane="fused"'))
    step_sum = flat['znicz_anatomy_step_seconds_sum{plane="fused"}'] \
        - base_step
    steps = flat['znicz_anatomy_steps_total{plane="fused"}'] - base_steps
    assert steps == 12                   # 2 epochs x 96/16 minibatches
    assert step_sum > 0.0
    assert abs(phase_sum - step_sum) <= 0.10 * step_sum, \
        (phase_sum, step_sum)
    # every train phase genuinely charged (shard_params => zero_gather,
    # int8 => the quantized collective dispatch)
    for phase in TRAIN_PHASES:
        assert flat['znicz_anatomy_phase_seconds_count'
                    f'{{plane="fused",phase="{phase}"}}'] >= steps
    assert flat['znicz_anatomy_mfu{plane="fused"}'] > 0.0
    # the families are live on the scrape surface and rank-label into
    # the fleet-merged view
    prom = registry.REGISTRY.render_prometheus()
    assert "znicz_anatomy_mfu" in prom
    assert "znicz_goodput_productive_seconds_total" in prom
    from znicz_tpu.observe import federation as fed
    agg = fed.FleetAggregator(min_refresh_s=0.0)
    agg.add_source(3, registry.REGISTRY.render_prometheus)
    try:
        merged = agg.snapshot_flat(skip_zero=False)
        assert any(k.startswith("znicz_anatomy_step_seconds_sum")
                   and 'rank="3"' in k for k in merged)
    finally:
        agg.close()


def test_anatomy_numerics_track_fused_path():
    """The split-dispatch programs compute the same training run as the
    fused single-program path to float tolerance (XLA fuses and
    reassociates differently across the program cuts, so bit-exactness
    is NOT the contract — closeness is)."""
    hist_fused = _run_fused(anatomy=False)
    hist_anat = _run_fused(anatomy=True)
    assert len(hist_anat) == len(hist_fused)
    # validation error percent per epoch: identical up to at most one
    # boundary sample flipping on ~1e-7 loss differences
    np.testing.assert_allclose(hist_anat, hist_fused,
                               atol=100.0 / 32 + 1e-9)


def test_anatomy_rejects_accumulation():
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    prng.seed_all(5)
    w = build_fused(max_epochs=1, layers=(16,), minibatch_size=16,
                    n_train=64, n_valid=16,
                    mesh=data_parallel_mesh(2), anatomy=True,
                    accumulate_steps=2)
    with pytest.raises(ValueError, match="accumulate"):
        w.initialize(device=XLADevice())
    w.stop()


# -- transformer producer ----------------------------------------------------

def test_transformer_anatomy_loss_parity(cpu_devices, monkeypatch):
    """The transformer anatomy step applies the TRUE batch-mean
    gradient (local grads + one explicit psum, the quantized-collectives
    semantics — see the make_train_step docstring), so its reference is
    a SINGLE-SHARD full-batch run, which it must match to float
    tolerance — NOT the multi-shard exact path, whose AD-transposed
    per-replica grads follow a different (documented) trajectory.  All
    four phases and the MFU gauge populate."""
    import jax
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("ZNICZ_TPU_PEAK_FLOPS", "1e12")
    prng.seed_all(7)
    gen = prng.get()
    n_layers, d, heads, ff, vocab = 1, 16, 2, 32, 11
    params = tfm.init_params(gen, n_layers, d, heads, ff, vocab)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, vocab, (4, 8)).astype(np.int32)
    labels = ((tokens + 1) % vocab).astype(np.int32)
    meshes = {
        "plain": make_mesh({"data": 1, "seq": 1, "model": 1}),
        "anatomy": make_mesh({"data": 2, "seq": 1, "model": 1}),
    }

    losses = {}
    for name, anatomy in (("plain", False), ("anatomy", True)):
        step, _ = tfm.make_train_step(meshes[name], n_layers, d, heads,
                                      ff, vocab, lr=0.1, anatomy=anatomy)
        p = {k: (v if not isinstance(v, list) else
                 [dict(b) for b in v]) for k, v in params.items()}
        run = []
        for _ in range(5):
            p, loss = step(p, tokens, labels)
            run.append(float(jax.device_get(loss)))
        losses[name] = run
    np.testing.assert_allclose(losses["anatomy"], losses["plain"],
                               rtol=2e-4)
    assert losses["anatomy"][-1] < losses["anatomy"][0]
    flat = _flat()
    for phase in ("grad", "collective", "update"):
        assert flat['znicz_anatomy_phase_seconds_count'
                    f'{{plane="transformer",phase="{phase}"}}'] >= 5
    assert flat['znicz_anatomy_mfu{plane="transformer"}'] > 0.0


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
