#!/bin/bash
# Tier-1 verify — the driver's pytest command, flag for flag (the
# `commands` of its test record), then the smoke legs.  Run from anywhere:
#   bash tools/t1.sh
# Exit code is pytest's unless a smoke fails; DOTS_PASSED echoes the
# passed-test count.
cd "$(dirname "$0")/.." || exit 1
if ! python -c "import pytest" 2>/dev/null; then
    echo "tools/t1.sh: pytest is not importable in this Python" \
         "($(command -v python || echo 'python not found')) — install it" \
         "or activate the right environment" >&2
    exit 2
fi
# ALLOW_MULTIPLE_LIBTPU_LOAD=1 lets the six workers describe a TPU
# topology side by side HERE, where there is no chip; never send this
# line to the machine with the chip
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
# The smokes below pin ZNICZ_TPU_COMPILE_CACHE=off in their own workers:
# a CPU smoke has no use for a persistent cache.  The segfault those
# pins were once blamed on did not reproduce: PR 21 ran chip_smoke.py
# twice against one directory on the v5e with the cache ON, threaded
# server phase included — 387 hits and 0 misses on the second run, no
# crash (CHANGES.md).
# ISSUE 5+6 smoke: the telemetry scrape surfaces must actually serve —
# boot a WebStatus, hit /metrics + /trace.json + /timeseries.json, and
# round-trip a flight artifact through `python -m znicz_tpu flight`
# (jax-free, milliseconds)
if ! timeout -k 5 60 python tools/metrics_smoke.py; then
    echo "tools/t1.sh: telemetry scrape smoke FAILED (see metrics_smoke" \
         "lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 6 static pass: every znicz_* metric family used in znicz_tpu/
# must be in the docs/OBSERVABILITY.md catalogue, and vice versa
if ! timeout -k 5 60 python tools/check_metric_catalogue.py; then
    echo "tools/t1.sh: metric catalogue check FAILED (see" \
         "check_metric_catalogue lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 7 smoke: zero-JIT serve boot — export an AOT package, boot the
# real serve CLI in a fresh jax-on-CPU process, scrape /metrics, assert
# the engine compile counter is 0 (docs/COMPILE.md)
if ! timeout -k 5 240 env JAX_PLATFORMS=cpu python tools/aot_smoke.py; then
    echo "tools/t1.sh: AOT zero-JIT serve smoke FAILED (see aot_smoke" \
         "lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 10 smoke: generative serving — boot the real `generate --serve`
# CLI from an exported LM package in a fresh process, stream a short
# generation over HTTP (ndjson tokens + exactly one terminal line),
# assert the znicz_generate_* metric families are live
# (docs/SERVING.md "Generative serving")
if ! timeout -k 5 240 env JAX_PLATFORMS=cpu python tools/generate_smoke.py; then
    echo "tools/t1.sh: generative serving smoke FAILED (see" \
         "generate_smoke lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 12 smoke: speculative decoding exactness — two fresh-process
# boots from one draft-carrying LM package must stream BYTE-IDENTICAL
# greedy text with speculation on vs off, and the spec/pages metric
# families must be live (docs/SERVING.md "Speculative decoding")
if ! timeout -k 5 300 env JAX_PLATFORMS=cpu python tools/generate_smoke.py --speculative; then
    echo "tools/t1.sh: speculative decoding smoke FAILED (see" \
         "generate_smoke lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 11 smoke: fleet telemetry — boot 2 real generate workers with
# rank env, aggregate their /metrics.prom into one rank-labeled fleet
# view, assert a fleet rule evaluates over the merged series and the
# merged Perfetto trace carries request phase spans from both ranks
# (docs/OBSERVABILITY.md "Fleet telemetry")
if ! timeout -k 5 300 env JAX_PLATFORMS=cpu python tools/fleet_smoke.py; then
    echo "tools/t1.sh: fleet telemetry smoke FAILED (see fleet_smoke" \
         "lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 13 smoke: serving fleet — the real `fleet` CLI boots a router
# + 2 real generate workers from one LM package, streams through the
# router under threaded traffic, performs one rolling weight update via
# POST /rollout, and asserts zero lost requests + fleet convergence on
# the new fingerprint + steady-state compile delta 0
# (docs/SERVING.md "Fleet topology")
if ! timeout -k 5 400 env JAX_PLATFORMS=cpu python tools/fleet_router_smoke.py; then
    echo "tools/t1.sh: serving-fleet router smoke FAILED (see" \
         "fleet_router_smoke lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 14 smoke: train-while-serve — the real `learn` CLI closes the
# whole loop in fresh processes: 2 serve workers feed the spool, 1
# supervised trainer consumes it and publishes, the bridge rolls the
# fleet; asserts an adopted publish + fleet-wide new fingerprint +
# closed router ledger (docs/LEARNING.md)
if ! timeout -k 5 700 env JAX_PLATFORMS=cpu python tools/learn_smoke.py; then
    echo "tools/t1.sh: train-while-serve smoke FAILED (see learn_smoke" \
         "lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 15 smoke: ZeRO shard_params — dp(4)+shard_params(adam) on a
# forced 4-device CPU mesh must read per-chip znicz_zero_* bytes at
# ~1/4 of the replicated run's with an identical seeded metric history
# (docs/TUNING.md "ZeRO modes")
if ! timeout -k 5 240 env JAX_PLATFORMS=cpu python tools/zero_smoke.py; then
    echo "tools/t1.sh: ZeRO shard_params smoke FAILED (see zero_smoke" \
         "lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 18 smoke: quantized collectives — on a forced 4-device CPU
# mesh, mode=off must reproduce the baseline seeded history
# bit-identically and an int8+error-feedback shard_params run must read
# ~4x compression from the znicz_qcomm_* counters on both collectives
# (docs/TUNING.md "Quantized collectives")
if ! timeout -k 5 240 env JAX_PLATFORMS=cpu python tools/qcomm_smoke.py; then
    echo "tools/t1.sh: quantized-collectives smoke FAILED (see" \
         "qcomm_smoke lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
# ISSUE 9 smoke: elastic kill-and-resume — 2 CPU worker processes, the
# snapshot writer SIGKILL'd at a seeded step, fleet resumes at world
# size 1; asserts completion + >= 1 flight artifact + resumes counter
# (docs/RESILIENCE.md "Elastic multi-process")
if ! timeout -k 5 300 env JAX_PLATFORMS=cpu python tools/elastic_smoke.py; then
    echo "tools/t1.sh: elastic kill-and-resume smoke FAILED (see" \
         "elastic_smoke lines above)" >&2
    [ $rc -eq 0 ] && rc=1
fi
exit $rc
