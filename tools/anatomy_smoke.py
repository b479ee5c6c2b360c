"""Step-anatomy smoke for tools/t1.sh (ISSUE 20): on a forced 4-device
CPU mesh, a dp(4)+shard_params+int8-collectives anatomy run must (a)
pre-touch every ``znicz_anatomy_*`` child at init (the PR 11 delta-rule
lesson: a family that first appears mid-run trips fleet rules as a fake
spike, or never), (b) attribute per-phase seconds whose sum lands within
10% of the measured step wall time, (c) read a nonzero
``znicz_anatomy_mfu`` (peak FLOPs pinned via $ZNICZ_TPU_PEAK_FLOPS —
the honest CPU-fallback denominator, docs/OBSERVABILITY.md), and (d)
trip the per-rank straggler rule for exactly the one artificially
delayed rank in a deterministic-tick fleet fixture.  Also asserts
``znicz_goodput_*`` pre-touch materializes every category child at 0.

``ZNICZ_TPU_COMPILE_CACHE=off``: a CPU smoke has no use for a persistent
cache.  (The segfault this pin was once blamed on was never reproduced:
PR 21 ran ``chip_smoke.py`` twice against one directory on the v5e with
the cache on, threaded server phase included — 387 hits on the second
run, no crash.)
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("ZNICZ_TPU_COMPILE_CACHE", "off")
# nominal peak so the MFU gauge has a denominator on CPU (peak_flops()
# is honestly None here; the figure is only meaningful RELATIVE to the
# pinned nominal — docs/OBSERVABILITY.md spells the caveat out)
os.environ.setdefault("ZNICZ_TPU_PEAK_FLOPS", "1e12")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

N_DEV = 4


def fail(msg: str) -> None:
    print(f"anatomy_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def check_anatomy_run():
    """(b)+(c) on the real fused workflow, (a) asserted at init."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.observe import registry
    from znicz_tpu.observe.anatomy import TRAIN_PHASES
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    prng.seed_all(31)
    w = build_fused(max_epochs=2, layers=(32,), minibatch_size=16,
                    n_train=96, n_valid=32,
                    mesh=data_parallel_mesh(N_DEV), optimizer="adam",
                    shard_params=True, anatomy=True,
                    quantized_collectives={"mode": "int8",
                                           "error_feedback": True})
    w.initialize(device=XLADevice())

    # (a) pre-touch: every anatomy child of the fused plane must exist
    # at init, BEFORE any step ran, so fleet delta rules see a baseline
    flat = registry.REGISTRY.snapshot_flat(skip_zero=False)
    if flat.get('znicz_anatomy_steps_total{plane="fused"}') != 0.0:
        fail("znicz_anatomy_steps_total not pre-touched at 0 at init")
    for phase in TRAIN_PHASES:
        key = ('znicz_anatomy_phase_seconds_count'
               f'{{plane="fused",phase="{phase}"}}')
        if flat.get(key) != 0.0:
            fail(f"phase child {phase!r} not pre-touched at init "
                 f"(missing key {key})")
    if flat.get('znicz_anatomy_mfu{plane="fused"}') != 0.0:
        fail("znicz_anatomy_mfu not pre-touched at 0 at init")

    w.run()
    flat = registry.REGISTRY.snapshot_flat(skip_zero=False)
    phase_sum = sum(
        v for k, v in flat.items()
        if k.startswith('znicz_anatomy_phase_seconds_sum{plane="fused"'))
    step_sum = flat.get('znicz_anatomy_step_seconds_sum{plane="fused"}',
                        0.0)
    steps = flat.get('znicz_anatomy_steps_total{plane="fused"}', 0.0)
    mfu = flat.get('znicz_anatomy_mfu{plane="fused"}', 0.0)
    w.stop()
    if steps <= 0:
        fail("anatomy run counted no steps")
    if step_sum <= 0:
        fail("anatomy run measured no step wall time")
    # (b) the phases must tile the step: unattributed time past 10%
    # means a dispatch point lost its stamp
    if abs(phase_sum - step_sum) > 0.10 * step_sum:
        fail(f"phase seconds {phase_sum:.4f} vs step wall "
             f"{step_sum:.4f}: {abs(phase_sum / step_sum - 1):.1%} "
             f"apart (> 10%)")
    if not (0.0 < mfu):
        fail(f"znicz_anatomy_mfu is {mfu} with "
             f"$ZNICZ_TPU_PEAK_FLOPS={os.environ['ZNICZ_TPU_PEAK_FLOPS']}")
    return phase_sum, step_sum, steps, mfu


def check_goodput_pretouch():
    """(a) for the goodput families: every category child per rank at
    0, ratio gauge present."""
    from znicz_tpu.observe import probe, registry

    probe.goodput_pretouch(range(2))
    flat = registry.REGISTRY.snapshot_flat(skip_zero=False)
    for cat in ("productive", "lost", "snapshot", "idle"):
        for rank in (0, 1):
            key = f'znicz_goodput_{cat}_seconds_total{{rank="{rank}"}}'
            if flat.get(key) != 0.0:
                fail(f"goodput child not pre-touched: {key}")
    if "znicz_goodput_ratio" not in flat:
        fail("znicz_goodput_ratio gauge not pre-touched")


def check_straggler_rule():
    """(d) deterministic ticks: 3 synthetic rank registries, rank 2
    delayed 5x — exactly its rule must trip."""
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
        tripped = [r.trips > 0 for r in rules]
        if tripped != [False, False, True]:
            fail(f"straggler rule trip pattern {tripped}, expected "
                 f"only the delayed rank 2 "
                 f"(last_values {[r.last_value for r in rules]})")
    finally:
        agg.close()


def main() -> None:
    phase_sum, step_sum, steps, mfu = check_anatomy_run()
    check_goodput_pretouch()
    check_straggler_rule()
    print(f"anatomy_smoke: OK — {int(steps)} steps, phase seconds "
          f"{phase_sum:.4f} vs step wall {step_sum:.4f} "
          f"({abs(phase_sum / step_sum - 1):.2%} apart), mfu {mfu:.3e} "
          f"vs nominal peak, goodput children pre-touched, straggler "
          f"rule tripped only for the delayed rank")


if __name__ == "__main__":
    main()
