"""Bucketed batch execution engine — the device half of the serving
plane.

XLA recompiles a jitted function for every new input shape, so a naive
server that forwards whatever batch size arrived compiles continuously
under real traffic (batch 3, then 7, then 5, ...).  The engine instead
pads every batch up to a small fixed set of bucket shapes — powers of
two up to ``max_batch`` — so warmup compiles each bucket exactly once
and steady-state serving triggers **zero** recompiles.  An explicit
``compile_count`` / ``run_count`` pair makes that property assertable
(tests/test_serve.py checks ``compile_count`` stays flat after
warmup) instead of inferred from wall-clock jitter.

Backends: ``utils.export.ExportedForward`` (jitted JAX), ``native.infer
.NativeForward`` (C++ runtime, no JAX in the serving path — declares
``static_shapes = False`` so the engine skips padding entirely), or any
``array -> array`` callable.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from znicz_tpu import observe
from znicz_tpu.core.logger import Logger
from znicz_tpu.resilience.faults import fault_hook


def bucket_sizes(max_batch: int) -> tuple:
    """Powers of two up to ``max_batch``; ``max_batch`` itself is always
    the final bucket so one compile covers the full admission range."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    sizes = []
    b = 1
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(max_batch)
    return tuple(sizes)


def load_backend(path: str, prefer_native: bool = False,
                 aot: bool = True):
    """Load a utils/export.py forward package as an engine backend:
    the C++ ``NativeForward`` when requested and buildable (the no-JAX
    serving path), else the jitted ``ExportedForward``.  ``aot=False``
    ignores embedded ahead-of-time executables (the ``--no-aot`` serve
    flag); with the default, a fingerprint-matching package boots with
    zero JIT compiles."""
    if prefer_native:
        from znicz_tpu.native import infer

        if infer.available():
            return infer.NativeForward(path)
    from znicz_tpu.utils.export import ExportedForward

    return ExportedForward(path, aot=aot)


class BatchEngine(Logger):
    """Serve ``model(x) -> y`` at a fixed set of batch shapes.

    ``model``: an ``ExportedForward``, ``NativeForward``, a path to a
    forward package (.npz), or any callable over a float32 batch array.
    ``input_shape`` is taken from the model when it carries one.
    ``run()`` is thread-safe (jit dispatch is not reentrant-safe); the
    micro-batcher funnels through a single worker anyway, but direct
    callers (PredictionServer compat) may be concurrent.
    """

    def __init__(self, model, max_batch: int = 64,
                 input_shape=None) -> None:
        super().__init__()
        if isinstance(model, str):
            model = load_backend(model)
        self.model = model
        self.max_batch = int(max_batch)
        self.buckets = bucket_sizes(self.max_batch)
        #: jitted backends compile per shape -> pad to buckets; backends
        #: that declare static_shapes=False (native C++) run any batch
        self.static_shapes = bool(getattr(model, "static_shapes", True))
        shape = input_shape if input_shape is not None else \
            getattr(model, "input_shape", None)
        self.input_shape = tuple(shape) if shape is not None else None
        self.meta = dict(getattr(model, "meta", {}) or {})
        self.compile_count = 0      # buckets materialized (first-run pads)
        self.aot_count = 0          # buckets served by AOT executables
        self.run_count = 0          # batches executed
        self.rows_served = 0
        self._seen_buckets: set = set()
        self._lock = threading.Lock()
        # compile-latency plane (ISSUE 7): serve boot is a primary
        # compile site — no-op for jax-free backends (native C++)
        from znicz_tpu import compilecache
        compilecache.ensure()

    # -- shape policy --------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        if n < 1:
            raise ValueError("empty batch")
        if n > self.max_batch:
            raise ValueError(f"batch {n} > max_batch {self.max_batch} "
                             "(the micro-batcher chunks oversize requests)")
        if not self.static_shapes:
            return n
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_batch

    def warmup(self, input_shape=None) -> int:
        """Run one zero batch per bucket so every serving shape is
        compiled (or its AOT executable validated) before traffic
        arrives; returns the compile count — 0 on a full ahead-of-time
        boot.  Boot cost is one greppable summary line: bucket count,
        total seconds, compiled vs AOT split, persistent-cache hits."""
        shape = input_shape if input_shape is not None else self.input_shape
        if shape is None:
            raise ValueError("warmup needs input_shape (the model does "
                             "not declare one)")
        self.input_shape = tuple(shape)
        if not self.static_shapes:
            # native path: no per-shape compilation; one probe run
            # validates the package end to end
            self.run(np.zeros((1,) + self.input_shape, np.float32))
            return 0
        from znicz_tpu.observe import probe as _probe

        hits0, _misses0 = _probe.compile_cache_stats()
        t0 = time.perf_counter()
        for b in self.buckets:
            self.run(np.zeros((b,) + self.input_shape, np.float32))
        dt = time.perf_counter() - t0
        hits, _misses = _probe.compile_cache_stats()
        self.info(f"warmup: {len(self.buckets)} buckets in {dt:.2f}s — "
                  f"{self.compile_count} compiled, {self.aot_count} "
                  f"aot-precompiled, {hits - hits0} persistent-cache "
                  f"hits")
        return self.compile_count

    # -- execution -----------------------------------------------------------
    def run(self, x) -> np.ndarray:
        """Execute one batch: pad to the bucket shape, run the model,
        slice the answer back to the true row count."""
        # chaos hook (site "serve.run"): injected crashes/hangs exercise
        # the batcher's error propagation and the server's 5xx path
        fault_hook("serve.run", engine=self)
        x = np.ascontiguousarray(x, np.float32)
        if x.ndim == 1:
            x = x[None]
        if self.input_shape is not None and x.shape[1:] != self.input_shape:
            raise ValueError(f"input shape {x.shape[1:]} != model input "
                             f"{self.input_shape}")
        n = x.shape[0]
        bucket = self.bucket_for(n)
        if bucket != n:
            pad = np.zeros((bucket - n,) + x.shape[1:], np.float32)
            x = np.concatenate([x, pad], axis=0)
        compiled = False
        with self._lock:
            if self.static_shapes and bucket not in self._seen_buckets:
                self._seen_buckets.add(bucket)
                if bucket in getattr(self.model, "precompiled_buckets",
                                     ()):
                    # ahead-of-time executable: materializing it is a
                    # deserialized-program first run, NOT a compile —
                    # the zero-JIT boot contract (compile_count == 0)
                    # is asserted on exactly this distinction
                    self.aot_count += 1
                    self.debug(f"bucket {bucket} from AOT executable "
                               f"({self.aot_count} precompiled)")
                else:
                    self.compile_count += 1
                    compiled = True
                    self.debug(f"compiling bucket {bucket} "
                               f"({self.compile_count}/{len(self.buckets)})")
            t0 = time.perf_counter()
            y = np.asarray(self.model(x))
            dt = time.perf_counter() - t0
            self.run_count += 1
            self.rows_served += n
        if compiled and observe.enabled():
            # shared telemetry plane: a bucket materializing after warmup
            # is the steady-state-recompile smell tests/test_serve.py
            # asserts against — make it scrapeable and visible on the
            # timeline, and record how long the cold bucket cost (the
            # compile-latency baseline, znicz_compile_seconds +
            # compile.cold span)
            observe.counter("znicz_serve_engine_compiles_total",
                            "engine buckets compiled").inc()
            observe.instant("serve.compile", bucket=bucket)
            observe.compile_observed("BatchEngine", dt, bucket=bucket)
        return y[:n]

    def stats(self) -> dict:
        """Engine-side counters, merged into ``GET /metrics``."""
        with self._lock:
            return {
                "max_batch": self.max_batch,
                "buckets": list(self.buckets),
                "static_shapes": self.static_shapes,
                "compile_count": self.compile_count,
                "aot_count": self.aot_count,
                "run_count": self.run_count,
                "rows_served": self.rows_served,
            }

    def close(self) -> None:
        close = getattr(self.model, "close", None)
        if callable(close):
            close()
