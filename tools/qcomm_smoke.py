"""Quantized-collectives smoke for tools/t1.sh (ISSUE 18): on a forced
4-device CPU mesh, (a) a ``quantized_collectives={"mode": "off"}`` run
must produce the BIT-IDENTICAL seeded metric history to a build that
never passed the config (the off path compiles today's program), (b) an
int8+error-feedback shard_params run must read a ~4x compression ratio
from the ``znicz_qcomm_*`` counters on BOTH collectives (gradient psum
and ZeRO gather; int8 payload + f32 chunk scales ≈ 3.98x), train to a
finite history, and publish a nonzero residual norm.

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

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

N_DEV = 4


def fail(msg: str) -> None:
    print(f"qcomm_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def run_once(quantized_collectives, shard_params: bool = False):
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.observe import registry
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    prng.seed_all(31)
    w = build_fused(max_epochs=2, layers=(32,), minibatch_size=16,
                    n_train=96, n_valid=32,
                    mesh=data_parallel_mesh(N_DEV), optimizer="adam",
                    shard_params=shard_params,
                    quantized_collectives=quantized_collectives)
    w.initialize(device=XLADevice())
    w.run()
    hist = [h["metric_validation"] for h in w.decision.metrics_history]

    def counters(coll):
        wire = registry.REGISTRY.get("znicz_qcomm_bytes_on_wire_total") \
            .labels(unit="FusedStep", collective=coll).get()
        exact = registry.REGISTRY.get("znicz_qcomm_bytes_exact_total") \
            .labels(unit="FusedStep", collective=coll).get()
        return wire, exact

    stats = {coll: counters(coll) for coll in ("grad_psum", "zero_gather")}
    residual = registry.REGISTRY.get("znicz_qcomm_residual_norm") \
        .labels(unit="FusedStep").get()
    w.stop()
    return hist, stats, residual


def main() -> None:
    hist_base, stats_base, _ = run_once(None)
    if any(v for wire_exact in stats_base.values() for v in wire_exact):
        fail(f"baseline run incremented qcomm counters: {stats_base}")

    hist_off, stats_off, _ = run_once({"mode": "off"})
    if hist_off != hist_base:
        fail(f"mode=off diverged from baseline: {hist_off} != {hist_base}")
    if any(v for wire_exact in stats_off.values() for v in wire_exact):
        fail(f"mode=off incremented qcomm counters: {stats_off}")

    qc = {"mode": "int8", "error_feedback": True}
    hist_q, stats_q, residual = run_once(qc, shard_params=True)
    if len(hist_q) != len(hist_base):
        fail(f"int8 run history length {len(hist_q)} != {len(hist_base)}")
    ratios = {}
    for coll, (wire, exact) in stats_q.items():
        if wire <= 0 or exact <= 0:
            fail(f"{coll}: counters not live (wire={wire}, exact={exact})")
        ratios[coll] = exact / wire
        # int8 payload + one f32 scale per balanced chunk: ~3.98x; the
        # window catches both a broken codec (~1x) and a miscounted
        # exact figure (>4x is impossible for int8+scales)
        if not 3.5 <= ratios[coll] <= 4.0:
            fail(f"{coll}: compression ratio {ratios[coll]:.3f} outside "
                 f"[3.5, 4.0] (wire={wire:.0f}, exact={exact:.0f})")
    if residual <= 0:
        fail(f"error-feedback residual norm not published: {residual}")
    print(f"qcomm_smoke: OK — mode=off history identical over "
          f"{len(hist_base)} epochs; int8 ratios "
          f"grad_psum {ratios['grad_psum']:.2f}x, "
          f"zero_gather {ratios['zero_gather']:.2f}x; "
          f"residual norm {residual:.3e}")


if __name__ == "__main__":
    main()
