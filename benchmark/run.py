"""The benchmark's one command:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips.  It fails at once, with no result
line, unless jax finds a TPU with as many chips as the cell asks for.
Everything about a cell is data: ``BENCHMARK.json`` names the workload, and
this file finds ``configs/<config>.json``, ``traffic/<traffic>.json`` (which
names its builder and generator), ``metrics/<metric>.json`` and the
``readers/``, ``kernels/``, ``reference/``, ``builders/`` and ``generators/``
modules they name, by name.  See README.md beside this file.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
a few seconds of the steady window and reports its per-layer metrics and
the breakdown.  ``--sweep r1,r2,...`` (serving cells; never part of a
check) offers each rate for ``--seconds`` in one process and prints whether
the queue grew: how ``rate_rps`` in a traffic file was found.

The last line of standard output is the one JSON object of the contract.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # as near to process start as Python gets

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
for _p in (CHECKOUT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchlib import BenchmarkError, Roots, benchmark_json, peaks_for  # noqa: E402


class Context:
    """What a builder is handed, and the window bookkeeping it calls."""

    def __init__(self, roots, workload, config, traffic, args, devices,
                 t_start: float, control: bool = False) -> None:
        self.roots, self.workload = roots, workload
        #: limits.py alone sets this: the builder also reads the control
        #: (the reference in the precision below) against the reference
        self.control = control
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace = bool(args.trace)
        self.sweep = [float(r) for r in args.sweep.split(",")] \
            if args.sweep else None
        self.chips = int(workload["chips"])
        self.devices = devices
        self.t_start = t_start
        self.excluded_s = 0.0
        self.setup_s = None
        self.t_open = None
        self.compiled_in_window = None
        self.tracing = False
        self.trace_dir = os.path.join(CHECKOUT, ".data", "cache",
                                      "bench_trace", workload["name"])
        self.trace_window_s = None
        self._cache_before = None

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", flush=True)

    def exclude(self, seconds: float) -> None:
        """Time before the window that is not set-up: the reference."""
        self.excluded_s += float(seconds)

    def open_window(self) -> None:
        from znicz_tpu import compilecache

        self._cache_before = compilecache.stats()
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.t_start - self.excluded_s

    def close_window(self) -> None:
        from znicz_tpu import compilecache

        after = compilecache.stats()
        self.compiled_in_window = (
            after["hits"] - self._cache_before["hits"] +
            after["misses"] - self._cache_before["misses"])
        self.window_s = time.perf_counter() - self.t_open

    def trace_start(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True
        self._t_trace = time.perf_counter()

    def trace_stop(self) -> None:
        import jax

        self.trace_window_s = time.perf_counter() - self._t_trace
        jax.profiler.stop_trace()
        self.tracing = False


class ReaderContext:
    """What a metric's reader is handed."""

    def __init__(self, ctx: Context, samples: dict, trace, peaks,
                 memory_peak_bytes: int) -> None:
        self.roots = ctx.roots
        self.config = samples.get("config_as_run", ctx.config)
        self.traffic = ctx.traffic
        self.workload, self.chips = ctx.workload, ctx.chips
        self.samples, self.trace = samples, trace
        self.trace_window_s = ctx.trace_window_s
        self.peaks = peaks
        self.memory_peak_bytes = memory_peak_bytes
        self.setup_s = ctx.setup_s
        self.log = ctx.log
        self.metric: dict = {}


def _cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """The end-to-end and per-layer entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def _read_metrics(entries: list, rctx: ReaderContext) -> dict:
    out = {}
    for entry in entries:
        name = entry["name"]
        if name == "setup_s":
            out[name] = {"value": rctx.setup_s, "unit": "s"}
            continue
        spec = rctx.roots.data("metrics", name)
        if spec["unit"] != entry["unit"]:
            raise BenchmarkError(
                f"metric {name}: BENCHMARK.json says unit {entry['unit']}, "
                f"metrics/{name}.json says {spec['unit']}")
        rctx.metric = spec
        value = rctx.roots.module("readers", spec["reader"]).read(rctx)
        if value is None:
            rctx.log(f"metric {name}: the reader found nothing to read")
            continue
        out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        peaks.append(int((stats or {}).get("peak_bytes_in_use", 0)))
    return max(peaks)


def main(argv=None, roots_extra: list | None = None,
         allow_cpu: bool = False) -> int:
    """``allow_cpu`` exists for the rehearsal tests alone (they drive the
    whole run tiny on the CPU); the command line never sets it, so no run
    prints a device metric without a TPU."""
    return execute(argv, roots_extra, allow_cpu)[0]


def execute(argv=None, roots_extra: list | None = None,
            allow_cpu: bool = False, control: bool = False):
    """One run; ``(exit code, result line's object, builder's outcome)``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    t_start = _T0 if argv is None else time.perf_counter()

    roots = Roots(roots_extra)
    bench = benchmark_json(roots)
    try:
        workload = next(w for w in bench["workloads"]
                        if w["name"] == args.workload)
    except StopIteration:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json "
              f"(has {[w['name'] for w in bench['workloads']]})",
              file=sys.stderr)
        return 2, None, None
    config = roots.data("configs", workload["config"])
    traffic = roots.data("traffic", workload["traffic"])

    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu" and not allow_cpu:
        print(f"run.py: no TPU: jax found {devices}; nothing ran",
              file=sys.stderr)
        return 1, None, None
    if len(devices) < int(workload["chips"]):
        print(f"run.py: {args.workload} needs {workload['chips']} chips, "
              f"jax found {len(devices)}", file=sys.stderr)
        return 1, None, None
    devices = devices[:int(workload["chips"])]
    peaks = None if d0.platform != "tpu" else peaks_for(d0.device_kind, roots)

    from znicz_tpu import compilecache

    cache_dir = compilecache.configure()
    ctx = Context(roots, workload, config, traffic, args, devices, t_start,
                  control)
    ctx.log(f"{args.workload}: config {workload['config']} traffic "
            f"{workload['traffic']} seed {ctx.seed} seconds {ctx.seconds:g} "
            f"trace {int(ctx.trace)} on {len(devices)} x {d0.device_kind!r}; "
            f"compile cache {cache_dir}")

    try:
        outcome = roots.module("builders", traffic["builder"]).run(ctx)
    except BenchmarkError as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 1, None, None
    if outcome.get("sweep"):
        return 0, None, outcome
    for line in outcome["lines"]:
        ctx.log(line)
    compiled = ctx.compiled_in_window
    ctx.log(f"check programs compiled or loaded inside the window: "
            f"{compiled} (limit 0) {'ok' if compiled == 0 else 'FAILED'}")
    correct = bool(outcome["correct"]) and compiled == 0

    memory_peak = _memory_peak(devices)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    trace = None
    if ctx.trace:
        from trace_reduce import Trace, newest_xplane

        trace = Trace(newest_xplane(ctx.trace_dir))
        device["busy_s"] = trace.busy_s()
        device["window_s"] = ctx.trace_window_s
    rctx = ReaderContext(ctx, outcome["samples"], trace, peaks, memory_peak)
    e2e, layer = _cell_metrics(bench, args.workload)
    result = {"correct": correct, "attempted": int(outcome["attempted"]),
              "failed": int(outcome["failed"]),
              "metrics": _read_metrics(layer if ctx.trace else e2e, rctx),
              "device": device}
    if trace is not None:
        result["breakdown"] = trace.breakdown()
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    ctx.log(f"setup_s {ctx.setup_s:.3f} (reference and checks excluded: "
            f"{ctx.excluded_s:.1f} s), window {ctx.window_s:.2f} s")
    print(json.dumps(result), flush=True)
    return 0, result, outcome


if __name__ == "__main__":
    sys.exit(main())
