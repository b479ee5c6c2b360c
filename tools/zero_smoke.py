"""ZeRO shard_params smoke for tools/t1.sh (ISSUE 15): on a forced
4-device CPU mesh, a dp(4)+shard_params(adam) run must (a) read per-chip
``znicz_zero_param_bytes + znicz_zero_opt_state_bytes`` at ~1/4 of the
replicated run's figure (padding epsilon allowed), (b) report nonzero
on-demand gather traffic, and (c) produce the SAME seeded metric history
as the replicated run — the memory win with the numerics pinned, end to
end through the real workflow loop.

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
    print(f"zero_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def run_once(shard_params: bool):
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.observe import registry
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    prng.seed_all(31)
    w = build_fused(max_epochs=2, layers=(32,), minibatch_size=16,
                    n_train=96, n_valid=32,
                    mesh=data_parallel_mesh(N_DEV), optimizer="adam",
                    shard_params=shard_params)
    w.initialize(device=XLADevice())
    w.run()
    hist = [h["metric_validation"] for h in w.decision.metrics_history]

    def gauge(name):
        return registry.REGISTRY.get(name).labels(unit="FusedStep").get()

    bytes_per_chip = (gauge("znicz_zero_param_bytes") +
                      gauge("znicz_zero_opt_state_bytes"))
    gathered = gauge("znicz_zero_gathered_bytes_total")
    n_sharded = sum(1 for leaf in w.step._params
                    for k in leaf if w.step._leaf_sharded(k))
    w.stop()
    return hist, bytes_per_chip, gathered, n_sharded


def main() -> None:
    hist_rep, bytes_rep, gathered_rep, _ = run_once(False)
    if bytes_rep <= 0:
        fail(f"replicated run reports {bytes_rep} state bytes")
    if gathered_rep != 0:
        fail(f"replicated run counted {gathered_rep} gathered bytes")

    hist_sp, bytes_sp, gathered_sp, n_sharded = run_once(True)
    if hist_sp != hist_rep:
        fail(f"seeded metric history diverged: shard_params {hist_sp} "
             f"!= replicated {hist_rep}")
    if gathered_sp <= 0:
        fail("shard_params run counted no gathered bytes")
    # acceptance: per-chip bytes <= 1/n of replicated + padding epsilon
    # (at most n-1 padded f32 elements per sharded leaf)
    eps = 4 * (N_DEV - 1) * n_sharded
    if bytes_sp > bytes_rep / N_DEV + eps:
        fail(f"per-chip bytes {bytes_sp} > replicated/{N_DEV} "
             f"({bytes_rep / N_DEV:.0f}) + padding eps {eps}")
    print(f"zero_smoke: OK — per-chip state {int(bytes_sp)}B vs "
          f"replicated {int(bytes_rep)}B (<= 1/{N_DEV} + {eps}B pad), "
          f"gathered {int(gathered_sp)}B on demand, seeded history "
          f"identical over {len(hist_sp)} epochs")


if __name__ == "__main__":
    main()
