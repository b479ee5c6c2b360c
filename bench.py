"""Benchmark harness — one JSON result line per scenario.

Measures all five BASELINE.md configs: MNIST-FC and AlexNet training
throughput (flagship, re-emitted as the final line), CIFAR ConvRELU and
Deconv-AE throughput, Kohonen SOM throughput, and MNIST-conv wall-clock
to 99% validation accuracy over the IDX file pipeline.  Throughput lines
carry ``mfu`` (analytic FLOPs model vs the chip's dense bf16 peak).
``vs_baseline`` is the cross-round trend — current value over the newest
driver-recorded ``BENCH_r*.json`` for the same metric (the reference
published no absolute numbers; BASELINE.json :: published == {}).  1.0
means "no prior round measured this metric".

One process for each chip: this PARENT never imports jax — it only
spawns children and relays their JSON lines — so the one child that
needs the chip (``--child tpu``) can claim it.  Keep it that way: a
parent that touches jax holds the chip, and that child then fails or
hangs.  The other children are CPU scenarios and run under
``JAX_PLATFORMS=cpu``.

There is no fallback: when the chip child fails (no chip, a phase that
raised, a time-out) the parent prints what landed and exits non-zero.
The chip child runs the cheap FC bench FIRST, flushing a full result
line the moment it exists, then the AlexNet flagship.

The driver reads the LAST JSON line — the best number available; every
earlier line is a complete valid result on its own.
"""

import contextlib
import functools
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

#: wall-clock budgets (seconds).
#: Env-overridable for testing and driver tuning.
TPU_TIMEOUT = int(os.environ.get("BENCH_TPU_TIMEOUT", 780))
CPU_TIMEOUT = int(os.environ.get("BENCH_CPU_TIMEOUT", 300))


def _enable_compile_cache():
    # ISSUE 7: routed through the compile-latency plane so cache
    # hits/misses land in znicz_compile_cache_{hits,misses}_total and
    # every scenario line can report its compile-cost delta.  The
    # directory is the plane's own ($JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.data/cache/jax).
    import jax  # noqa: F401 — ensure() only configures once jax exists
    from znicz_tpu import compilecache

    compilecache.configure(min_compile_time_s=0.0)


@contextlib.contextmanager
def _maybe_profile():
    """jax trace around the timed region when BENCH_PROFILE names a
    directory; exception-safe so a mid-loop device failure never leaves
    a trace open."""
    import jax

    profile_dir = os.environ.get("BENCH_PROFILE")
    if not profile_dir:
        yield
        return
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        try:
            from znicz_tpu.utils.profiling import summarize_trace
            for r in summarize_trace(profile_dir, top=12):
                print(f"# prof {r['total_ms']:9.2f} ms x{r['count']:<4} "
                      f"{r['op'][:100]}", file=sys.stderr)
        except Exception as exc:  # noqa: BLE001 — summary is best-effort
            print(f"# prof summary unavailable: {exc!r}", file=sys.stderr)


def _throughput(step, x, labels, K: int = 8, reps: int = 3) -> float:
    """Shared timing protocol: K minibatches per dispatch via the step's
    ``train_steps`` scan (amortizes the per-call dispatch latency),
    inputs staged ON DEVICE first (the role of a real input pipeline),
    fenced by a device->host metric read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    batch = x.shape[0]
    # one h2d of the base batch; the K rolled copies are built ON DEVICE
    # by a gather (np.roll(x, k)[i] == x[(i-k) % batch]) — at the r5 K
    # values host-side np.stack would peak at ~1.6 GB and push ~1 GB
    # over H2D before timing starts
    xd, yd = jnp.asarray(x), jnp.asarray(labels)
    idx = jnp.asarray((np.arange(batch)[None, :] -
                       np.arange(K)[:, None]) % batch)
    xs = xd[idx]                          # (K, batch, ...)
    ys = yd[idx]                          # roll on the batch axis only —
    ms = jnp.ones((K, batch), bool)       # labels may be image targets
    jax.device_get(xs[0, 0, 0])          # fence the staging transfers

    metrics = step.train_steps(xs, ys, ms)      # compile + warm
    float(jax.device_get(metrics["loss"]))
    with _maybe_profile():
        t0 = time.perf_counter()
        for _ in range(reps):
            metrics = step.train_steps(xs, ys, ms)
        float(jax.device_get(metrics["loss"]))  # fences the whole chain
        dt = time.perf_counter() - t0
    return batch * K * reps / dt


@functools.lru_cache(maxsize=1)
def _prev_round_values() -> dict:
    """metric -> newest driver-recorded result dict from BENCH_r*.json —
    ``vs_baseline`` reports the cross-round trend (the reference published
    no absolute numbers; BASELINE.json :: published == {})."""
    import glob

    vals = {}
    for path in sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        for line in str(doc.get("tail", "")).splitlines():
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(r, dict) and "metric" in r and "value" in r:
                vals[r["metric"]] = r                   # later rounds win
    return vals


#: compile-cost baseline for per-line deltas (ISSUE 7): totals as of the
#: previous _emit, so each scenario line carries ITS OWN compile bill
_compile_base = None


def _compile_totals():
    """Lifetime compile-cost totals: summed ``znicz_compile_seconds``
    (cold trace+compile+run wall time of wrapped programs and engine
    buckets) plus the persistent-cache hit/miss counters."""
    try:
        from znicz_tpu.observe import REGISTRY, compile_cache_stats
        snap = REGISTRY.snapshot_flat(skip_zero=False)
        cold = sum(v for k, v in snap.items()
                   if k.startswith("znicz_compile_seconds_sum"))
        hits, misses = compile_cache_stats()
        return {"cold_seconds": cold, "cache_hits": hits,
                "cache_misses": misses}
    except Exception as exc:  # noqa: BLE001 — telemetry must not cost
        print(f"# compile totals unavailable: {exc!r}", file=sys.stderr)
        return None


def _emit(metric: str, value: float, forwards=None, batch: int = 0,
          unit: str = "samples/sec", lower_is_better: bool = False,
          trend_valid: bool = True, **extra) -> dict:
    """Flush one complete result line (mfu only when on real TPU and the
    workflow has MXU-countable forwards).  ``vs_baseline`` is oriented so
    >1 always means improvement (prev/value for time-like metrics); 0.0
    marks a run that is not comparable (``trend_valid=False``, e.g. the
    wall-clock run gave up before the target), and prior non-comparable
    runs are likewise never used as the trend base."""
    import jax
    from znicz_tpu.utils import flops

    prev_entry = _prev_round_values().get(metric)
    trend = 1.0
    if not trend_valid:
        trend = 0.0
    elif prev_entry and prev_entry.get("reached_target", True) and \
            float(prev_entry["value"]) > 0:
        prev = float(prev_entry["value"])
        trend = round(prev / value, 3) if lower_is_better \
            else round(value / prev, 3)
    out = {"metric": metric, "value": round(value, 1), "unit": unit,
           "vs_baseline": trend, **extra}
    if forwards is not None and jax.default_backend() != "cpu":
        m = flops.mfu(value, forwards, batch)
        if m is not None:
            out["mfu"] = round(m, 4)
    # ISSUE 5: every scenario line carries the child's telemetry-plane
    # snapshot (compact name{labels} -> value; zero series dropped) so a
    # recorded bench artifact shows recompiles/stalls/step counts
    # without rerunning anything
    try:
        from znicz_tpu.observe import REGISTRY
        snap = REGISTRY.snapshot_flat()
        if snap:
            out["registry"] = snap
    except Exception as exc:  # noqa: BLE001 — telemetry must not cost
        print(f"# registry snapshot unavailable: {exc!r}",  # the line
              file=sys.stderr)
    # ISSUE 7 satellite: every line records the compile cost IT paid —
    # cold compile seconds + persistent-cache hit/miss deltas since the
    # previous line, so BENCH_r06 onward separates compile bill from
    # throughput without rerunning anything
    global _compile_base
    cur = _compile_totals()
    if cur is not None:
        base = _compile_base or {k: 0 for k in cur}
        out["compile"] = {
            "cold_seconds": round(cur["cold_seconds"] -
                                  base["cold_seconds"], 3),
            "cache_hits": cur["cache_hits"] - base["cache_hits"],
            "cache_misses": cur["cache_misses"] - base["cache_misses"]}
        _compile_base = cur
    print(json.dumps(out), flush=True)
    return out


# ---------------------------------------------------------------------------
# child: claims the device once, benches cheapest-first, flushes each line
# ---------------------------------------------------------------------------

def bench_fc(batch=1024, layers=(4096, 4096), K=256, reps=3):
    # K=256 amortizes the per-dispatch overhead (ROADMAP Queue 1 item 4);
    # staging 256×3 MB ≈ 820 MB, well inside HBM
    import numpy as np
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import TPUDevice
    from znicz_tpu.models.mnist_fc import build_fused

    t0 = time.time()
    prng.seed_all(7)
    # bf16 momentum storage: at this batch the f32 w+v update traffic
    # rivals the matmul time (docs/TUNING.md); math stays f32, and the
    # state_dtype convergence/resume pins cover the narrowing
    w = build_fused(max_epochs=1, layers=layers, minibatch_size=batch,
                    n_train=2 * batch, n_valid=0,
                    optimizer_config={"state_dtype": "bfloat16"})
    w.initialize(device=TPUDevice())
    print(f"# fc: initialized in {time.time() - t0:.1f}s", file=sys.stderr)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 28, 28)).astype(np.float32)
    labels = rng.integers(0, 10, batch).astype(np.int32)
    sps = _throughput(w.step, x, labels, K, reps)
    _emit(f"mnist_fc{layers[0]}_train_samples_per_sec_per_chip", sps,
          w.forwards, batch, state_dtype="bfloat16")


def bench_alexnet(batch=128, K=16, reps=3):
    # K=16 amortizes the per-dispatch overhead (ROADMAP Queue 1 item 4);
    # staging 16×79 MB ≈ 1.3 GB
    import numpy as np
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import TPUDevice
    from znicz_tpu.models.alexnet import build

    t0 = time.time()
    prng.seed_all(7)
    # loader dataset is minimal (8 samples): the bench stages its own
    # device-resident batches below; the loader only satisfies initialize()
    # bf16 momentum storage: the 62M-param SGD update moves ~1.2 GB/step
    # of f32 state; the narrow velocity halves its share (docs/TUNING.md)
    w = build(max_epochs=1, minibatch_size=batch, n_classes=1000,
              input_size=227, n_train=8, n_valid=0,
              loader_config={"n_classes": 8},
              optimizer_config={"state_dtype": "bfloat16"})
    w.initialize(device=TPUDevice())
    print(f"# alexnet: initialized in {time.time() - t0:.1f}s",
          file=sys.stderr)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 227, 227, 3)).astype(np.float32)
    labels = rng.integers(0, 1000, batch).astype(np.int32)
    sps = _throughput(w.step, x, labels, K, reps)
    flagship = _emit("alexnet_b128_train_samples_per_sec_per_chip", sps,
                     w.forwards, batch, state_dtype="bfloat16")
    if os.environ.get("BENCH_ALEXNET_B256"):
        # ceiling probe (watcher-budget only — the driver's default
        # child budget must not pay this extra compile): 2x batch shows
        # what the conv stack sustains when fixed costs amortize, the
        # same A/B CIFAR runs at b2048.  AFTER the flagship emit so a
        # hang here can never lose the trend-tracked b128 line, and
        # named so main()'s "alexnet" flagship filter cannot pick it
        del w
        prng.seed_all(7)
        w2 = build(max_epochs=1, minibatch_size=2 * batch, n_classes=1000,
                   input_size=227, n_train=8, n_valid=0,
                   loader_config={"n_classes": 8},
                   optimizer_config={"state_dtype": "bfloat16"})
        w2.initialize(device=TPUDevice())
        x2 = rng.normal(size=(2 * batch, 227, 227, 3)).astype(np.float32)
        l2 = rng.integers(0, 1000, 2 * batch).astype(np.int32)
        _emit("ceiling_alexnet_b256_train_samples_per_sec_per_chip",
              _throughput(w2.step, x2, l2, max(K // 2, 4), reps),
              w2.forwards, 2 * batch, state_dtype="bfloat16")
    return flagship


def bench_cifar(batch=512, K=64, reps=3):
    """BASELINE.md config 2: CIFAR-10 ConvRELU + MaxPooling + GDConv.

    Two batch sizes: b512 is the cross-round continuity config; most of
    its wall step was per-dispatch overhead (ROADMAP Queue 1 item 4: 32
    tiny param/momentum copies + dispatch latency), so K rises 16→64 to
    amortize it; the 4x batch line shows
    what the conv path sustains when the MXU work amortizes the
    elementwise soup (K=16 there keeps staging at ~400 MB)."""
    import numpy as np
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import TPUDevice
    from znicz_tpu.models.cifar_conv import build

    for b, k in ((batch, K), (4 * batch, max(K // 4, 2))):
        # (b512, K=64) and (b2048, K=16): equal samples per dispatch,
        # so the fixed cost amortizes identically and the A/B isolates
        # the per-sample compute efficiency
        t0 = time.time()
        prng.seed_all(7)
        w = build(max_epochs=1, minibatch_size=b, n_train=b, n_valid=0,
                  loader_name="synthetic_image",
                  loader_config={"n_classes": 10})
        w.initialize(device=TPUDevice())
        print(f"# cifar b{b}: initialized in {time.time() - t0:.1f}s",
              file=sys.stderr)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(b, 32, 32, 3)).astype(np.float32)
        labels = rng.integers(0, 10, b).astype(np.int32)
        sps = _throughput(w.step, x, labels, k, reps)
        _emit(f"cifar_convrelu_b{b}_train_samples_per_sec_per_chip", sps,
              w.forwards, b)


def bench_deconv_ae(batch=64, K=64, reps=3):
    # K=64: dispatch overhead dominates this sub-millisecond step at K=8;
    # staging 64×3 MB ≈ 200 MB
    """BASELINE.md config 4 at ImagenetAE-representative scale: 64x64x3
    input, 64/128-kernel strided conv encoder, mirrored deconv decoder.
    (The r1-r3 32x32x1/32-kernel toy measured model smallness, not the
    deconv path — VERDICT r3 weak #3.)"""
    import numpy as np
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import TPUDevice
    from znicz_tpu.models.autoencoder import build_deep

    t0 = time.time()
    prng.seed_all(7)
    w = build_deep(max_epochs=1, minibatch_size=batch,
                   sample_shape=(64, 64, 3), n_kernels=(64, 128),
                   n_train=batch, n_valid=0)
    w.initialize(device=TPUDevice())
    print(f"# deconv_ae: initialized in {time.time() - t0:.1f}s",
          file=sys.stderr)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 64, 64, 3)).astype(np.float32)
    sps = _throughput(w.step, x, x, K, reps)   # identity targets (MSE)
    _emit(f"deconv_ae64_b{batch}_train_samples_per_sec_per_chip", sps,
          w.forwards, batch)


def bench_transformer(batch=8, seq=2048, d=512, n_layers=6, heads=8,
                      vocab=32000, K=4, reps=3):
    """Beyond-parity headline: decoder-transformer training throughput
    (ring-attention-capable stack on a 1-chip mesh), tokens/sec/chip.
    ``attention`` reports which core the step was built with (flash when
    the platform, mesh and shape allow it); a flash kernel that fails to
    lower fails the phase."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from znicz_tpu.core import prng
    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    t0 = time.time()
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1})
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    labels = jnp.asarray(np.roll(np.asarray(tokens), -1, axis=1))
    from znicz_tpu.ops.pallas.attention import supported as flash_ok
    attention = "flash" if (tfm._flash_eligible(mesh, False) and
                            flash_ok(seq, d // heads)) else "xla"
    prng.seed_all(7)
    params = tfm.init_params(prng.get(), n_layers, d, heads, 4 * d, vocab)
    # loss_chunks=16: the (16384, 32000) f32 logits are ~2 GB and the CE
    # stack multiplies that through log_softmax + AD residuals — chunked
    # remat keeps one 1024-token chunk live (docs/TUNING.md).  A flash
    # kernel that does not lower fails this phase; nothing retries with
    # XLA attention under the flash metric's name.
    step, _ = tfm.make_train_step(mesh, n_layers, d, heads, 4 * d,
                                  vocab, lr=1e-3, donate=True,
                                  loss_chunks=16)
    params, loss = step(params, tokens, labels)   # compile + warm
    float(jax.device_get(loss))
    print(f"# transformer ({attention}): initialized in "
          f"{time.time() - t0:.1f}s", file=sys.stderr)
    with _maybe_profile():
        t0 = time.perf_counter()
        for _ in range(K * reps):
            params, loss = step(params, tokens, labels)
        float(jax.device_get(loss))
        dt = time.perf_counter() - t0
    tps = batch * seq * K * reps / dt
    # MFU via the standard 6*N*T estimate (params N dominated by matmuls)
    n_params = sum(int(np.prod(np.shape(p)))
                   for p in jax.tree.leaves(params))
    from znicz_tpu.utils import flops as flops_mod
    peak = flops_mod.peak_flops()
    extra = {}
    if peak and jax.default_backend() != "cpu":
        extra["mfu"] = round(6.0 * n_params * tps / peak, 4)
        # the embedding LOOKUP does no matmul FLOPs (gather fwd /
        # scatter-add bwd), so 6N with emb included over-credits ~1.4x
        # at this vocab/d; report the matmul-only figure alongside for
        # honest accounting (the r3 gate tracks "mfu")
        extra["mfu_matmul_only"] = round(
            6.0 * (n_params - vocab * d) * tps / peak, 4)
    _emit(f"transformer_l{n_layers}d{d}s{seq}_train_tokens_per_sec_per_chip",
          tps, unit="tokens/sec", attention=attention, **extra)


def bench_pallas_parity():
    """VERDICT r3 item 4: every Pallas kernel family executed COMPILED
    (interpret=False) on the real chip against its oracle — one
    ``pallas_hw_parity`` line, per-kernel ok/FAIL, lowering failure is a
    FAIL (never a silent fallback)."""
    from znicz_tpu.utils.pallas_hw import run_parity

    t0 = time.time()
    kernels = run_parity(interpret=False)
    n_ok = sum(1 for v in kernels.values() if v == "ok")
    print(f"# pallas_hw_parity: {n_ok}/{len(kernels)} in "
          f"{time.time() - t0:.1f}s", file=sys.stderr)
    _emit("pallas_hw_parity_kernels_ok", float(n_ok), unit="kernels",
          total=len(kernels), kernels=kernels)


def bench_kohonen(n_train=4000, minibatch=500, epochs=3):
    """BASELINE.md config 5: Kohonen SOM winner-take-all training.  The
    SOM trainer is its own accelerated unit (not a FusedTrainStep); runs
    in epoch-scan mode (one compiled dispatch per class pass), so this
    measures the scanned unit-graph hot loop end to end."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import TPUDevice
    from znicz_tpu.core.config import root
    from znicz_tpu.models.kohonen import build

    t0 = time.time()
    prev_scan = root.common.engine.get("scan_epoch", False)
    root.common.engine.scan_epoch = True
    try:
        # warm-up: one throwaway epoch compiles the SOM kernels (same
        # shapes), matching the compile-then-time protocol of _throughput
        prng.seed_all(7)
        warm = build(max_epochs=1, shape=(16, 16), minibatch_size=minibatch,
                     n_train=n_train, sample_shape=(16,), min_delta=0.0)
        warm.initialize(device=TPUDevice())
        warm.run()
        prng.seed_all(7)
        w = build(max_epochs=epochs, shape=(16, 16),
                  minibatch_size=minibatch, n_train=n_train,
                  sample_shape=(16,), min_delta=0.0)
        w.initialize(device=TPUDevice())
        print(f"# kohonen: initialized+warmed in {time.time() - t0:.1f}s",
              file=sys.stderr)
        t0 = time.perf_counter()
        w.run()
        # the run's last device work is async; fence on the weights read
        w.trainer.weights.map_read()
        dt = time.perf_counter() - t0
    finally:
        root.common.engine.scan_epoch = prev_scan
    _emit("kohonen_som256_train_samples_per_sec_per_chip",
          n_train * epochs / dt,
          # 16 KB weight table, ~KB-scale per-step traffic: the SOM is
          # dispatch-latency-bound, not MXU/HBM-bound — scan mode exists
          # to collapse dispatches
          bound="dispatch-latency", scan_mode=True)


def bench_mnist_wallclock(n_train=6000, n_valid=1000, target_pct=1.0,
                          max_epochs=25):
    """BASELINE.md headline metric: MNIST-conv wall-clock to 99% validation
    accuracy over the IDX file pipeline (synthesized digits stand in for
    the undownloadable real files; same byte format, same loader path)."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import TPUDevice
    from znicz_tpu.core.config import root
    from znicz_tpu.models.mnist_conv import build

    t0 = time.time()
    prng.seed_all(7)
    target = int(n_valid * target_pct / 100.0)
    # one compiled scan per class pass — per-minibatch dispatch latency
    # leaves the wall-clock entirely
    prev_scan = root.common.engine.get("scan_epoch", False)
    root.common.engine.scan_epoch = True
    w = build(max_epochs=max_epochs, minibatch_size=200, n_train=n_train,
              n_valid=n_valid)
    w.decision.target_metric = target
    try:
        w.initialize(device=TPUDevice())
        print(f"# mnist_wallclock: initialized in {time.time() - t0:.1f}s",
              file=sys.stderr)
        t0 = time.perf_counter()
        w.run()
        wall = time.perf_counter() - t0
    finally:
        root.common.engine.scan_epoch = prev_scan
    hist = w.decision.metrics_history
    reached = hist[-1]["metric_validation"] <= target
    _emit("mnist_conv_wallclock_to_99pct_sec", wall, unit="s",
          lower_is_better=True, trend_valid=bool(reached),
          epochs=len(hist),
          final_validation_errors=int(hist[-1]["metric_validation"]),
          reached_target=bool(reached),
          # accuracy is against SYNTHESIZED stand-in digits (no network
          # in the sandbox) — pipeline-valid, not comparable to the
          # reference's published accuracy on real MNIST bytes
          synthesized_data=True)


def bench_serve(duration_s=4.0, clients=8, max_batch=32):
    """serve/ plane scenario: threaded clients hammer the in-process
    micro-batcher + bucketed engine (CPU — this measures the serving
    machinery, not the chip) and the line reports sustained QPS with the
    p95 request latency and observed coalescing from the serving
    metrics.  Zero steady-state recompiles is asserted, not assumed."""
    import threading

    import numpy as np
    import jax
    import jax.numpy as jnp

    from znicz_tpu.serve import BatchEngine, MicroBatcher

    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.normal(0, 0.1, (64, 256)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(0, 0.1, (256, 16)).astype(np.float32))

    @jax.jit
    def mlp(x):
        return jnp.tanh(x @ w1) @ w2

    engine = BatchEngine(mlp, max_batch=max_batch, input_shape=(64,))
    engine.warmup()
    compiles = engine.compile_count
    batcher = MicroBatcher(engine, max_wait_ms=2.0, max_queue=512,
                           default_timeout_s=60.0)
    stop_at = time.perf_counter() + duration_s
    errors = []

    def client(cid):
        crng = np.random.default_rng(cid)
        x = crng.normal(size=(1, 64)).astype(np.float32)
        try:
            while time.perf_counter() < stop_at:
                batcher.predict(x)
        except Exception as exc:  # noqa: BLE001 — surface below
            errors.append(repr(exc))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 60)
    elapsed = time.perf_counter() - t0
    batcher.stop()
    if errors:
        raise RuntimeError(f"serve bench clients failed: {errors[:3]}")
    if engine.compile_count != compiles:
        raise RuntimeError(
            f"steady-state recompiled: {compiles} -> {engine.compile_count}")
    snap = batcher.metrics.snapshot()
    sizes = {int(k): v for k, v in snap["batch_size_histogram"].items()}
    mean_batch = sum(k * v for k, v in sizes.items()) / \
        max(sum(sizes.values()), 1)
    _emit("serve_engine_qps", snap["completed"] / elapsed,
          unit="requests/sec",
          p95_latency_ms=snap["latency"]["p95_ms"],
          p50_latency_ms=snap["latency"]["p50_ms"],
          clients=clients, mean_coalesced_batch=round(mean_batch, 2),
          max_coalesced_batch=max(sizes) if sizes else 0,
          compile_count=engine.compile_count, cpu=True)


def bench_generate(slots=4, max_len=128, n_requests=16, max_new=24,
                   n_layers=2, d=64, heads=4, ff=128, vocab=64):
    """Generative serving scenario (ISSUE 10): seeded mixed-length
    requests stream through the KV-cache continuous batcher (CPU — this
    measures the decode plane's machinery) and the line reports
    sustained tokens/sec with TTFT p50/p95 from the generate metrics.
    Steady-state compile delta == 0 after warmup is asserted AFTER the
    line lands — a broken zero-recompile contract must fail the
    scenario loudly, not ride a JSON field nobody greps."""
    import numpy as np

    from znicz_tpu.parallel.transformer import init_params
    from znicz_tpu.serve import ContinuousBatcher, KVDecoder

    params = init_params(np.random.default_rng(7), n_layers, d, heads,
                         ff, vocab)
    decoder = KVDecoder(params, heads=heads, max_len=max_len,
                        batch=slots)
    decoder.warmup()
    compiles_after_warmup = decoder.compile_count
    batcher = ContinuousBatcher(decoder, max_queue=n_requests,
                                default_timeout_s=120.0)
    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    streams = []
    for i in range(n_requests):
        prompt = rng.integers(0, vocab,
                              size=int(rng.integers(4, 32))).tolist()
        streams.append(batcher.submit(
            prompt, max_new_tokens=max_new, temperature=0.8, top_k=8,
            seed=i))
    total_tokens = sum(len(s.result(timeout_s=300)) for s in streams)
    elapsed = time.perf_counter() - t0
    batcher.stop()
    snap = batcher.metrics.snapshot()
    compile_delta = decoder.compile_count - compiles_after_warmup
    _emit("generate_tokens_per_sec", total_tokens / elapsed,
          unit="tokens/sec",
          ttft_p50_ms=snap["ttft"]["p50_ms"],
          ttft_p95_ms=snap["ttft"]["p95_ms"],
          requests=n_requests, slots=slots,
          completed=snap["completed"],
          steady_state_compile_delta=compile_delta, cpu=True)
    assert snap["completed"] == n_requests, \
        (f"generate ledger broke: {snap['completed']} of {n_requests} "
         f"requests completed ({snap})")
    assert compile_delta == 0, \
        (f"steady-state decode recompiled: {compiles_after_warmup} -> "
         f"{decoder.compile_count}")


def bench_generate_longtail(slots=8, page=16, max_len=256, n_layers=2,
                            d=48, heads=4, ff=96, vocab=64,
                            arena_pages=73, spec_k=4):
    """Long-tail mix arm (ISSUE 12): short+long greedy requests through
    three decode planes over identical traffic — the PR 10 contiguous
    shared-bucket baseline, the block-paged arena, and paged +
    speculative (1-layer truncated draft).  The line reports tokens/sec
    for all three, the slot ceiling and peak cache bytes at the paged
    arena's resident-row budget, and the speculation acceptance rate.

    Methodology: each arm runs the traffic once to PRIME its compiled
    shapes (only the shapes this traffic actually dispatches — no full
    warmup sweep), then once timed; the steady-state compile delta over
    the timed pass is asserted 0 AFTER the line lands.  Exactness rides
    along: the speculative stream must be token-identical to plain
    paged decode (the ISSUE pin), and paged-vs-contiguous agreement is
    reported."""
    import numpy as np

    from znicz_tpu.parallel.transformer import init_params
    from znicz_tpu.serve import (ContinuousBatcher, KVDecoder,
                                 PagedKVDecoder, truncate_draft)

    params = init_params(np.random.default_rng(7), n_layers, d, heads,
                         ff, vocab)
    rng = np.random.default_rng(2)
    reqs = []
    for _ in range(16):                  # the short majority
        plen = int(rng.integers(4, 12))
        reqs.append((rng.integers(0, vocab, size=plen).tolist(), 16))
    for _ in range(4):                   # the long tail
        reqs.append((rng.integers(0, vocab, size=16).tolist(), 176))
    reqs = [reqs[i] for i in rng.permutation(len(reqs))]

    def run(decoder, draft=None):
        batcher = ContinuousBatcher(decoder, max_queue=len(reqs),
                                    default_timeout_s=600.0,
                                    draft=draft, spec_k=spec_k)
        t0 = time.perf_counter()
        streams = [batcher.submit(p, max_new_tokens=m)
                   for p, m in reqs]
        outs = [s.result(timeout_s=600) for s in streams]
        elapsed = time.perf_counter() - t0
        snap = batcher.metrics.snapshot()
        bucket = batcher._bucket        # contiguous shared-cache rows
        batcher.stop()
        assert snap["completed"] == len(reqs), \
            (f"long-tail ledger broke: {snap['completed']} of "
             f"{len(reqs)} completed ({snap})")
        # peak concurrently-live slots off the step-counter intervals
        # (deterministic — no wall-clock sampling)
        events = sorted([(s.first_token_step, 1) for s in streams] +
                        [(s.finish_step, -1) for s in streams])
        peak = cur = 0
        for _, delta in events:
            cur += delta
            peak = max(peak, cur)
        tokens = sum(len(o) for o in outs)
        return outs, tokens / elapsed, snap, peak, bucket

    row_bytes = n_layers * heads * (d // heads) * 2 * 4  # K+V, f32

    contig = KVDecoder(params, heads=heads, max_len=max_len,
                       batch=slots)
    run(contig)                                          # prime
    c0 = contig.compile_count
    outs_c, tps_c, snap_c, _, bucket_c = run(contig)
    delta_c = contig.compile_count - c0

    pdec = PagedKVDecoder(params, heads=heads, max_len=max_len,
                          batch=slots, page=page,
                          arena_pages=arena_pages)
    run(pdec)                                            # prime
    p0 = pdec.compile_count
    outs_p, tps_p, snap_p, peak_slots, _ = run(pdec)
    delta_p = pdec.compile_count - p0

    draft = PagedKVDecoder(truncate_draft(params, 1), heads=heads,
                           max_len=max_len, batch=slots, page=page)
    run(pdec, draft=draft)                               # prime
    s0 = pdec.compile_count + draft.compile_count
    outs_s, tps_s, snap_s, _, _ = run(pdec, draft=draft)
    delta_s = pdec.compile_count + draft.compile_count - s0

    judged = snap_s["spec_accepted"] + snap_s["spec_rejected"]
    arena_rows = (arena_pages - 1) * page
    _emit("generate_longtail_tokens_per_sec", tps_p,
          unit="tokens/sec",
          contiguous_tokens_per_sec=round(tps_c, 1),
          paged_speedup=round(tps_p / tps_c, 3),
          spec_tokens_per_sec=round(tps_s, 1),
          spec_speedup=round(tps_s / tps_c, 3),
          spec_acceptance_rate=round(
              snap_s["spec_accepted"] / judged, 3) if judged else 0.0,
          ttft_p50_ms=snap_p["ttft"]["p50_ms"],
          ttft_p95_ms=snap_p["ttft"]["p95_ms"],
          slot_ceiling_paged=peak_slots,
          slot_ceiling_contiguous=arena_rows // bucket_c,
          peak_cache_bytes_paged=pdec.ledger.peak_used * page *
          row_bytes,
          peak_cache_bytes_contiguous=slots * bucket_c * row_bytes,
          paged_matches_contiguous=outs_p == outs_c,
          requests=len(reqs), slots=slots, page=page,
          arena_pages=arena_pages,
          steady_state_compile_delta=delta_c + delta_p + delta_s,
          cpu=True)
    # the speculation exactness pin and the zero-recompile contract
    # fail the scenario loudly AFTER the line lands
    assert outs_s == outs_p, \
        "speculative greedy decode diverged from plain paged decode"
    assert delta_c == delta_p == delta_s == 0, \
        (f"steady-state recompiled: contiguous {delta_c}, paged "
         f"{delta_p}, speculative {delta_s}")


def bench_fleet(n_requests=24, max_new=8, flood_clients=8):
    """Serving-fleet scenario (ISSUE 13), over REAL worker processes:

    - **router overhead**: the same greedy generation is timed straight
      against one worker and then through the fleet router — the line's
      headline is the routed p95 (stable, trendable) and the
      ``overhead_*`` fields carry the direct-vs-routed deltas the
      ISSUE asks for;
    - **autoscaler reaction**: a thread flood saturates the single
      worker's admission queue until the fleet saturation rule
      breaches, and the second line reports breach-to-new-worker-READY
      wall time (boot + warmup + readiness gate — the real scale-up
      latency an SLO burn-down sees).

    The zero-lost ledger and the scale-up itself are asserted AFTER the
    lines land."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from znicz_tpu.fleet import Autoscaler, FleetRouter, WorkerPool
    from znicz_tpu.parallel.transformer import init_params
    from znicz_tpu.utils.export import export_lm

    tmp = tempfile.mkdtemp(prefix="znicz_bench_fleet_")
    pool = router = None
    try:
        charmap = list("abcdefghijklmnopqrstuvwxyz .,!?")
        params = init_params(np.random.default_rng(11), 2, 32, 4, 64,
                             len(charmap))
        pkg = os.path.join(tmp, "lm.npz")
        export_lm(params, pkg, heads=4, charmap=charmap,
                  name="bench_lm")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   ZNICZ_TPU_COMPILE_CACHE="off")
        pool = WorkerPool(
            pkg, plane="generate", env=env,
            worker_args=("--slots", "2", "--max-len", "64"),
            run_dir=os.path.join(tmp, "fleet"))
        w0 = pool.spawn()
        if not pool.wait_all_ready(timeout_s=240):
            raise RuntimeError(f"fleet worker never ready: "
                               f"{pool.snapshot()}")
        pool.start_probes()

        def timed(base: str, n: int) -> np.ndarray:
            lats = []
            for i in range(n + 3):
                body = _json.dumps({"prompt": "ab",
                                    "max_tokens": max_new,
                                    "timeout_s": 60}).encode()
                req = urllib.request.Request(
                    base + "/generate", data=body,
                    headers={"Content-Type": "application/json"})
                t0 = time.perf_counter()
                with urllib.request.urlopen(req, timeout=120) as r:
                    lines = [_json.loads(raw) for raw in r]
                dt = time.perf_counter() - t0
                if not lines or not lines[-1].get("done") or \
                        "error" in lines[-1]:
                    raise RuntimeError(f"bench stream did not "
                                       f"complete: {lines}")
                if i >= 3:              # 3 primes per arm, same shape
                    lats.append(dt)
            return np.asarray(lats) * 1000.0

        direct = timed(w0.base, n_requests)
        router = FleetRouter(pool)
        port = router.start()
        base = f"http://127.0.0.1:{port}"
        routed = timed(base, n_requests)
        _emit("fleet_router_p95_ms", float(np.percentile(routed, 95)),
              unit="ms", lower_is_better=True,
              direct_p95_ms=round(float(np.percentile(direct, 95)), 2),
              overhead_p95_ms=round(float(np.percentile(routed, 95) -
                                          np.percentile(direct, 95)),
                                    2),
              overhead_p50_ms=round(float(np.percentile(routed, 50) -
                                          np.percentile(direct, 50)),
                                    2),
              requests=n_requests, cpu=True)

        # -- autoscaler reaction: flood one worker, time breach->ready
        scaler = Autoscaler(pool, min_workers=1, max_workers=2,
                            queue_high=3.0, breach_for_s=0.25,
                            cooldown_s=5.0, idle_down_s=3600.0)
        stop_flood = threading.Event()
        flood_errors: list = []

        def flood() -> None:
            import urllib.error

            body = _json.dumps({"prompt": "ab", "max_tokens": 48,
                                "timeout_s": 120}).encode()
            while not stop_flood.is_set():
                req = urllib.request.Request(
                    base + "/generate", data=body,
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=180) as r:
                        for _ in r:
                            pass
                except urllib.error.HTTPError as exc:
                    exc.read()
                    if exc.code != 503:     # backpressure is the
                        flood_errors.append(  # EXPECTED overload answer
                            f"HTTP {exc.code}")
                    time.sleep(0.1)
                except Exception as exc:  # noqa: BLE001 — surfaced
                    flood_errors.append(repr(exc))   # after the line
                    time.sleep(0.1)

        threads = [threading.Thread(target=flood, daemon=True)
                   for _ in range(flood_clients)]
        for t in threads:
            t.start()
        t0 = time.monotonic()
        while scaler.last_reaction_s is None and \
                time.monotonic() - t0 < 240:
            scaler.tick()
            time.sleep(0.25)
        reaction = scaler.last_reaction_s
        stop_flood.set()
        for t in threads:
            t.join(timeout=240)
        scaler.stop()
        snap = router.snapshot()
        _emit("fleet_autoscale_reaction_sec",
              float(reaction if reaction else 0.0), unit="seconds",
              lower_is_better=True,
              trend_valid=reaction is not None,
              workers=pool.worker_count(), scale_ups=scaler.scale_ups,
              router_ledger={k: snap[k] for k in
                             ("admitted", "completed", "failed",
                              "rejected", "client_gone")},
              cpu=True)
        # asserted AFTER the lines land (the scenario contract)
        assert reaction is not None and reaction > 0.0, \
            "autoscaler never reacted to the queue-saturation breach"
        assert pool.worker_count() == 2 and pool.ready_count() == 2, \
            f"scale-up did not land: {pool.snapshot()}"
        assert snap["admitted"] == snap["completed"] + \
            snap["failed"] + snap["client_gone"], \
            f"router ledger does not close: {snap}"
        assert not flood_errors, \
            f"flood clients failed hard: {flood_errors[:3]}"
    finally:
        if router is not None:
            router.stop()
        if pool is not None:
            pool.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_train_while_serve(n_requests=16, max_new=8):
    """Train-while-serve scenario (ISSUE 14), over REAL processes: the
    same greedy generation is timed through the fleet router with the
    trainer IDLE, with the trainer CO-RESIDENT (supervised, consuming
    the live feedback spool on the same box), and MID-ROLLOUT (while
    the publish-triggered zero-downtime update replaces workers) —
    the three serving-latency regimes the continuous-learning loop
    creates.  A second line reports publish-to-adopted latency (the
    manifest wall stamp to fleet convergence).  Ledger equality and
    steady-state compile delta 0 are asserted AFTER the lines land."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from znicz_tpu.fleet import FleetRouter, WorkerPool
    from znicz_tpu.fleet.rollout import RollingUpdate
    from znicz_tpu.learn.publish import latest_manifest
    from znicz_tpu.parallel.transformer import init_params
    from znicz_tpu.resilience.elastic import run_elastic
    from znicz_tpu.resilience.supervisor import SupervisorPolicy
    from znicz_tpu.utils.export import export_lm

    tmp = tempfile.mkdtemp(prefix="znicz_bench_learn_")
    pool = router = None
    trainer_box: dict = {}
    try:
        charmap = list("abcdefgh .,!?")
        params = init_params(np.random.default_rng(11), 2, 32, 4, 64,
                             len(charmap))
        pkg = os.path.join(tmp, "lm.npz")
        export_lm(params, pkg, heads=4, charmap=charmap,
                  name="bench_lm")
        spool = os.path.join(tmp, "spool")
        pub = os.path.join(tmp, "publish")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   ZNICZ_TPU_COMPILE_CACHE="off")
        pool = WorkerPool(
            pkg, plane="generate", env=env,
            worker_args=("--slots", "2", "--max-len", "64",
                         "--feedback-spool", spool),
            run_dir=os.path.join(tmp, "fleet"))
        pool.spawn()
        pool.spawn()
        if not pool.wait_all_ready(timeout_s=240):
            raise RuntimeError(f"fleet workers never ready: "
                               f"{pool.snapshot()}")
        pool.start_probes()
        router = FleetRouter(pool)
        rollout = RollingUpdate(pool)
        router.attach_rollout(rollout)
        base = f"http://127.0.0.1:{router.start()}"

        def one_request() -> float:
            body = _json.dumps({"prompt": "ab", "max_tokens": max_new,
                                "timeout_s": 60}).encode()
            req = urllib.request.Request(
                base + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=120) as r:
                lines = [_json.loads(raw) for raw in r]
            if not lines or not lines[-1].get("done") or \
                    "error" in lines[-1]:
                raise RuntimeError(f"bench stream did not complete: "
                                   f"{lines}")
            return time.perf_counter() - t0

        def timed(n: int) -> np.ndarray:
            return np.asarray([one_request()
                               for _ in range(n + 3)][3:]) * 1000.0

        # -- arm 1: trainer idle (also seeds the feedback spool) -----
        idle = timed(n_requests)

        # -- arm 2: trainer co-resident ------------------------------
        trainer_argv = [
            "znicz_tpu/learn/trainer_workflow.py",
            "-o", f"root.learn.spool_dir={spool}",
            "-o", f"root.learn.package={pkg}",
            "-o", f"root.learn.publish_dir={pub}",
            "-o", "root.learn.publish_every=4",
            "-o", "root.learn.max_epochs=4",
            "-o", "root.learn.records_per_epoch=6",
            "-o", "root.learn.seq_len=8",
            "-o", "root.learn.minibatch_size=4",
            "-o", "root.learn.wait_timeout_s=300",
            "--random-seed", "11"]

        def train() -> None:
            try:
                trainer_box["report"] = run_elastic(
                    trainer_argv, os.path.join(tmp, "snaps"),
                    workers=1, spmd=False, env=env,
                    run_dir=os.path.join(tmp, "trainer"),
                    policy=SupervisorPolicy(max_restarts=1))
            except Exception as exc:  # noqa: BLE001 — surfaced below
                trainer_box["error"] = exc

        trainer = threading.Thread(target=train, daemon=True)
        trainer.start()
        time.sleep(2.0)               # past the trainer's jax boot
        co = timed(n_requests)

        # -- arm 3: mid-rollout (publish-triggered) ------------------
        deadline = time.monotonic() + 300
        manifest = None
        while time.monotonic() < deadline:
            if "error" in trainer_box:
                raise RuntimeError(f"trainer failed: "
                                   f"{trainer_box['error']!r}")
            manifest = latest_manifest(pub)
            if manifest is not None:
                break
            one_request()             # keep the spool fed meanwhile —
            time.sleep(0.2)           # THROTTLED: an unthrottled loop
            #                           starves the co-resident trainer
            #                           of the box (the learn smoke
            #                           lesson) and the publish never
            #                           comes
        if manifest is None:
            raise RuntimeError("trainer never published")
        rollout.start(manifest["package"])
        roll_lats = []
        while rollout.rolling and len(roll_lats) < 400:
            roll_lats.append(one_request())
        report = rollout.join()
        adopted_s = max(0.0, time.time() - float(manifest["ts"]))
        roll = np.asarray(roll_lats) * 1000.0 if roll_lats else \
            np.asarray([0.0])
        _emit("train_while_serve_p95_ms",
              float(np.percentile(co, 95)), unit="ms",
              lower_is_better=True,
              idle_p95_ms=round(float(np.percentile(idle, 95)), 2),
              rollout_p95_ms=round(float(np.percentile(roll, 95)), 2),
              co_resident_overhead_p50_ms=round(
                  float(np.percentile(co, 50) -
                        np.percentile(idle, 50)), 2),
              rollout_requests=len(roll_lats),
              requests=n_requests, cpu=True)
        _emit("learn_publish_to_adopted_sec", adopted_s,
              unit="seconds", lower_is_better=True,
              trend_valid=report.get("state") == "done",
              epoch=manifest.get("epoch"), cpu=True)
        # asserted AFTER the lines land (the scenario contract)
        assert report.get("state") == "done", \
            f"publish-triggered rollout failed: {report}"
        trainer.join(timeout=240)
        assert trainer_box.get("report") is not None and \
            trainer_box["report"].completed, \
            f"trainer did not complete: {trainer_box}"
        snap = router.snapshot()
        assert snap["admitted"] == snap["completed"] + \
            snap["failed"] + snap["client_gone"], \
            f"router ledger does not close: {snap}"
        pool.probe_once()
        shas = {(w.fingerprint or {}).get("sha256")
                for w in pool.workers()}
        assert shas == {manifest["fingerprint"]["sha256"]}, \
            f"fleet not converged on the published package: " \
            f"{pool.snapshot()}"
        # steady state: fresh traffic compiles nothing
        def compile_counts():
            out = []
            for w in pool.workers():
                with urllib.request.urlopen(w.base + "/metrics",
                                            timeout=15) as r:
                    out.append(_json.loads(r.read())["decoder"]
                               ["compile_count"])
            return out

        before = compile_counts()
        for _ in range(3):
            one_request()
        assert before == compile_counts(), \
            "steady-state decode recompiled after the adoption"
    finally:
        if router is not None:
            router.stop()
        if pool is not None:
            pool.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_input_pipeline(epochs=3, minibatch=256, n_train=10240,
                         n_valid=2560, hidden=512, reps=2):
    """Input-pipeline scenario (ISSUE 4): sync vs prefetch=2 through the
    REAL Workflow.run loop on the mnist_fc shape (CPU by design — it
    measures the prefetch/staging machinery, not the chip).  Dataset
    pinning is disabled so every step ships its minibatch — the path the
    pipeline overlaps; the line reports samples/sec for both modes and
    the per-stage stall breakdown.  The bit-exactness contract is
    ASSERTED after the line flushes: a determinism break still lands the
    result but fails the scenario loudly (nonzero child exit)."""
    import time as _time

    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.core.config import root
    from znicz_tpu.standard_workflow import StandardWorkflow

    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": hidden},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    ]
    loader_cfg = {"n_classes": 10, "sample_shape": (28, 28),
                  "n_train": n_train, "n_valid": n_valid,
                  "minibatch_size": minibatch, "spread": 2.5, "noise": 1.0}

    def run_once(depth):
        prng.seed_all(7)
        w = StandardWorkflow(
            name=f"pipe{depth or 0}", layers=layers,
            loss_function="softmax", loader_name="synthetic_classifier",
            loader_config=loader_cfg,
            decision_config={"max_epochs": epochs},
            pipeline_config={"depth": depth} if depth else None)
        w.initialize(device=XLADevice())
        t0 = _time.perf_counter()
        w.run()
        dt = _time.perf_counter() - t0
        hist = w.decision.metrics_history
        stats = w.input_pipeline.stats.snapshot() if depth else None
        w.stop()
        return (n_train + n_valid) * epochs / dt, hist, stats

    prev_limit = root.common.engine.get("dataset_on_device_max_bytes",
                                        1 << 30)
    root.common.engine.dataset_on_device_max_bytes = 0
    try:
        # sync first: its compiles also warm the persistent cache, so any
        # residual compile bias favors neither mode by the best-of-reps
        sync_sps, sync_hist = 0.0, None
        for _ in range(reps):
            sps, sync_hist, _ = run_once(None)
            sync_sps = max(sync_sps, sps)
        pre_sps, pre_hist, pre_stats = 0.0, None, None
        for _ in range(reps):
            sps, pre_hist, stats = run_once(2)
            if sps > pre_sps:
                pre_sps, pre_stats = sps, stats
    finally:
        root.common.engine.dataset_on_device_max_bytes = prev_limit
    _emit("input_pipeline_mnist_fc_prefetch2_samples_per_sec", pre_sps,
          cpu=True, sync_samples_per_sec=round(sync_sps, 1),
          speedup=round(pre_sps / sync_sps, 3),
          bit_exact=pre_hist == sync_hist,
          prefetch_depth=2, epochs=epochs,
          stalls={k: pre_stats[k] for k in
                  ("serve_s", "stage_s", "producer_starved_s",
                   "consumer_starved_s", "barrier_s")},
          bytes_staged=pre_stats["bytes_staged"],
          bound=pre_stats["bound"])
    # AFTER the emit so the throughput line always lands: a determinism
    # break must fail the scenario loudly, not ride a JSON field nobody
    # greps
    assert pre_hist == sync_hist, \
        "prefetched metric history diverged from the synchronous run"


def bench_zero_sharding(epochs=3, minibatch=32, n_train=640, n_valid=0,
                        hidden=128):
    """ZeRO shard_params scenario (ISSUE 15), CPU by design on a forced
    8-virtual-device platform (it measures the sharding machinery +
    accounting, not the chip; the child sets the platform before jax
    boots): the SAME seeded adam workflow runs replicated vs
    shard_params across dp mesh sizes, recording per-chip persistent
    state bytes (the znicz_zero_* gauges) and wall-clock throughput.
    The line lands first; the memory contract (per-chip bytes <= 1/n +
    padding) and the seeded-history parity are ASSERTED after it
    flushes, so a violation still records the measurement but fails the
    scenario loudly (nonzero child exit)."""
    import time as _time

    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.observe import registry
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    def gauge(name):
        return registry.REGISTRY.get(name).labels(unit="FusedStep").get()

    def run_once(n_dev, shard_params):
        prng.seed_all(31)
        w = build_fused(max_epochs=epochs, layers=(hidden,),
                        minibatch_size=minibatch, n_train=n_train,
                        n_valid=n_valid, mesh=data_parallel_mesh(n_dev),
                        optimizer="adam", shard_params=shard_params)
        w.initialize(device=XLADevice())
        t0 = _time.perf_counter()
        w.run()
        dt = _time.perf_counter() - t0
        hist = [dict(h) for h in w.decision.metrics_history]
        bytes_per_chip = int(gauge("znicz_zero_param_bytes") +
                             gauge("znicz_zero_opt_state_bytes"))
        n_sharded = sum(1 for leaf in w.step._params
                        for k in leaf if w.step._leaf_sharded(k))
        w.stop()
        sps = (n_train + n_valid) * epochs / dt
        return sps, bytes_per_chip, hist, n_sharded

    matrix, violations = {}, []
    headline_sps = 0.0
    for n_dev in (2, 4, 8):
        rep_sps, rep_bytes, rep_hist, _ = run_once(n_dev, False)
        sp_sps, sp_bytes, sp_hist, n_sharded = run_once(n_dev, True)
        matrix[f"dp{n_dev}"] = {
            "replicated": {"samples_per_sec": round(rep_sps, 1),
                           "state_bytes_per_chip": rep_bytes},
            "shard_params": {"samples_per_sec": round(sp_sps, 1),
                             "state_bytes_per_chip": sp_bytes},
            "mem_ratio": round(sp_bytes / rep_bytes, 4),
            "hist_equal": sp_hist == rep_hist,
        }
        eps = 4 * (n_dev - 1) * n_sharded
        if sp_bytes > rep_bytes / n_dev + eps:
            violations.append(f"dp{n_dev}: {sp_bytes}B > "
                              f"{rep_bytes}/{n_dev}+{eps}B")
        if sp_hist != rep_hist:
            violations.append(f"dp{n_dev}: seeded history diverged")
        if n_dev == 8:
            headline_sps = sp_sps
    _emit("zero_shard_params_dp8_samples_per_sec", headline_sps,
          cpu=True, mesh_sizes=matrix,
          mem_ratio_dp8=matrix["dp8"]["mem_ratio"])
    # AFTER the emit so the measurement always lands: a broken memory
    # contract or history divergence must fail the scenario loudly
    assert not violations, "; ".join(violations)


def bench_quantized_collectives(epochs=3, minibatch=32, n_train=640,
                                n_valid=0, hidden=128):
    """Quantized-collectives scenario (ISSUE 18), CPU by design on the
    same forced 8-virtual-device platform as bench_zero_sharding (it
    measures the codec + accounting machinery, not the chip): the SAME
    seeded adam shard_params workflow runs exact vs int8+error-feedback
    across dp mesh sizes, recording bytes-on-wire vs step time and the
    seeded loss trajectory.  ``znicz_zero_gathered_bytes_total`` is
    recorded before (exact) and after (quantized) so the artifact holds
    the regather traffic the codec compressed.  The line lands first;
    the wire contract (int8 <= 0.27x exact on BOTH collectives) and the
    trajectory band are ASSERTED after it flushes."""
    import time as _time

    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.observe import registry
    from znicz_tpu.parallel.mesh import data_parallel_mesh

    def counter(name, **labels):
        return registry.REGISTRY.get(name).labels(
            unit="FusedStep", **labels).get()

    def run_once(n_dev, qc):
        # the counters are process-cumulative: snapshot before the run
        # so each cell reports ITS traffic, not the session total
        gathered0 = counter("znicz_zero_gathered_bytes_total")
        qcomm0 = {coll: (counter("znicz_qcomm_bytes_on_wire_total",
                                 collective=coll),
                         counter("znicz_qcomm_bytes_exact_total",
                                 collective=coll))
                  for coll in ("grad_psum", "zero_gather")}
        prng.seed_all(31)
        w = build_fused(max_epochs=epochs, layers=(hidden,),
                        minibatch_size=minibatch, n_train=n_train,
                        n_valid=n_valid, mesh=data_parallel_mesh(n_dev),
                        optimizer="adam", shard_params=True,
                        quantized_collectives=qc)
        w.initialize(device=XLADevice())
        t0 = _time.perf_counter()
        w.run()
        dt = _time.perf_counter() - t0
        hist = [h["metric_train"] for h in w.decision.metrics_history]
        gathered = int(counter("znicz_zero_gathered_bytes_total") -
                       gathered0)
        qcomm = {coll: (int(counter("znicz_qcomm_bytes_on_wire_total",
                                    collective=coll) - qcomm0[coll][0]),
                        int(counter("znicz_qcomm_bytes_exact_total",
                                    collective=coll) - qcomm0[coll][1]))
                 for coll in ("grad_psum", "zero_gather")}
        w.stop()
        sps = (n_train + n_valid) * epochs / dt
        return sps, hist, gathered, qcomm

    matrix, violations = {}, []
    headline_sps = 0.0
    for n_dev in (2, 4, 8):
        ex_sps, ex_hist, ex_gathered, _ = run_once(n_dev, None)
        q_sps, q_hist, q_gathered, qcomm = run_once(
            n_dev, {"mode": "int8", "error_feedback": True})
        matrix[f"dp{n_dev}"] = {
            "exact": {"samples_per_sec": round(ex_sps, 1),
                      "zero_gathered_bytes": ex_gathered,
                      "train_err_history": ex_hist},
            "int8_ef": {"samples_per_sec": round(q_sps, 1),
                        "zero_gathered_bytes": q_gathered,
                        "train_err_history": q_hist},
            "wire_ratio": {coll: round(wire / max(exact, 1), 4)
                           for coll, (wire, exact) in qcomm.items()},
        }
        for coll, (wire, exact) in qcomm.items():
            if not 0 < wire <= 0.27 * exact:
                violations.append(f"dp{n_dev}/{coll}: wire {wire}B > "
                                  f"0.27x exact {exact}B")
        # the seeded trajectory band: int8+EF may differ from exact by
        # quantization noise, never by a broken reduction — pin each
        # epoch's train-error count within 5% of the train set
        band = 0.05 * n_train
        for e, (a, b) in enumerate(zip(ex_hist, q_hist)):
            if abs(a - b) > band:
                violations.append(f"dp{n_dev}: epoch {e} train err "
                                  f"{b} vs exact {a} (band {band:.0f})")
        if n_dev == 8:
            headline_sps = q_sps
    _emit("qcomm_int8_dp8_samples_per_sec", headline_sps,
          cpu=True, mesh_sizes=matrix,
          wire_ratio_dp8=matrix["dp8"]["wire_ratio"])
    # AFTER the emit so the measurement always lands: a broken wire
    # contract or trajectory divergence must fail the scenario loudly
    assert not violations, "; ".join(violations)


def bench_metrics_overhead(epochs=3, minibatch=128, n_train=2560,
                           n_valid=640, hidden=256, pairs=20):
    """ISSUE 5 scenario: the telemetry plane's cost on the REAL
    Workflow.run loop (CPU by design — it measures the instrumentation
    machinery, not the chip).  Runs the same seeded mnist_fc-shaped
    workflow with probes+tracer enabled vs ``observe.set_enabled(False)``
    (the bare pre-ISSUE-5 walk).  ISSUE 6 raised the instrumented arm's
    load: it now also carries an attached watchtower (step-boundary
    registry sampling + the full five-rule SLO catalogue) so the <2%
    bound covers sampler + rule engine, not just probes + tracer.
    ISSUE 11 raised it again: the instrumented arm additionally runs a
    fleet MetricsExporter (the worker-side half of metric federation —
    periodic registry render + atomic file rewrite, exactly what an
    elastic rank pays under a supervising aggregator), so the bound
    covers the federation plane's per-worker cost too.

    Protocol, forced by this box's load profile: scheduler theft on the
    shared sandbox swings individual runs ±10-40% (sampled runs sit at
    ~24k sps with sporadic dips to ~14k), and theft only ever SLOWS a
    run down — so per-run throughput is a one-sided underestimate of
    the machine's capability.  The scenario interleaves many short
    bare/inst runs and alternates which arm goes first to cancel order
    bias.  The r05-era protocol compared the arms at their best-of-N
    (max) throughput; by ISSUE 6 the theft profile had worsened to the
    point where individual runs swing 2x+ and the two arms' maxima land
    on DIFFERENT theft luck (the best-of-N overhead measured -9.6%,
    +3.3%, +8.6% and +11.5% across identical reruns while the median
    flipped sign) — max no longer converges.  The asserted estimator is
    now the QUIETEST-QUARTILE pair median: a pair whose two adjacent
    runs were BOTH fast had theft touch neither arm, so its
    instrumented/bare ratio is the trustworthy one — rank pairs by
    combined runtime, keep the quietest quarter (>= 3 pairs), take the
    median ratio.  Best-of-N and the all-pair median ride along as
    diagnostics.  The line lands first; the <2% overhead contract and
    the bit-exact metric-history contract are ASSERTED after it
    flushes, so a violation still records the measurement but fails the
    scenario loudly (nonzero child exit)."""
    import statistics
    import tempfile
    import time as _time

    from znicz_tpu import observe
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.observe import federation as _fed
    from znicz_tpu.observe import watchtower as _wt
    from znicz_tpu.standard_workflow import StandardWorkflow

    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": hidden},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    ]
    loader_cfg = {"n_classes": 10, "sample_shape": (28, 28),
                  "n_train": n_train, "n_valid": n_valid,
                  "minibatch_size": minibatch, "spread": 2.5,
                  "noise": 1.0}

    mx_path = os.path.join(tempfile.gettempdir(),
                           f"znicz_bench_fleet_{os.getpid()}.json")

    def run_once(enabled):
        observe.set_enabled(enabled)
        prng.seed_all(7)
        w = StandardWorkflow(
            name="overhead", layers=layers, loss_function="softmax",
            loader_name="synthetic_classifier", loader_config=loader_cfg,
            decision_config={"max_epochs": epochs})
        w.initialize(device=XLADevice())
        exporter = None
        if enabled:
            # ISSUE 6: the instrumented arm pays for the whole plane —
            # step-boundary sampling + the full rule catalogue evaluated
            # on every sample.  Occasional trips (recompile_storm sees
            # the 40 re-initializing runs sharing one registry as a
            # storm) are part of the measured load; trips never touch
            # the metric history, so bit_exact still must hold.
            tower = _wt.Watchtower()
            for make_rule in (_wt.step_latency_regression,
                              _wt.serve_queue_saturation,
                              _wt.nan_guard_trip_rate,
                              _wt.recompile_storm,
                              _wt.pipeline_consumer_starvation):
                tower.add_rule(make_rule())
            tower.attach(w)
            # ISSUE 11: plus the worker-side federation exporter at the
            # elastic supervisor's default cadence
            exporter = _fed.start_metrics_export(mx_path, interval_s=1.0)
        t0 = _time.perf_counter()
        w.run()
        dt = _time.perf_counter() - t0
        hist = w.decision.metrics_history
        w.stop()
        if exporter is not None:
            exporter.stop()
        return (n_train + n_valid) * epochs / dt, hist

    try:
        run_once(True)                   # warm the compile cache once
        run_once(False)
        ratios, bare, inst = [], [], []
        inst_hist = bare_hist = None
        for i in range(pairs):
            if i % 2:                    # alternate order: [b,s] / [s,b]
                s, inst_hist = run_once(True)
                b, bare_hist = run_once(False)
            else:
                b, bare_hist = run_once(False)
                s, inst_hist = run_once(True)
            bare.append(b)
            inst.append(s)
            ratios.append(s / b)
    finally:
        observe.set_enabled(True)
        with contextlib.suppress(OSError):
            os.remove(mx_path)
    bare_sps = max(bare)
    inst_sps = max(inst)
    best_of_n_pct = (1.0 - inst_sps / bare_sps) * 100.0
    # quietest-quartile estimator (see docstring): rank pairs by the
    # pair's combined wall time (1/sps + 1/sps), keep the least-stolen
    # quarter, judge the median instrumented/bare ratio there
    by_quiet = sorted(zip((1.0 / b + 1.0 / s
                           for b, s in zip(bare, inst)), ratios))
    quiet = [r for _, r in by_quiet[:max(3, pairs // 4)]]
    overhead_pct = (1.0 - statistics.median(quiet)) * 100.0
    _emit("metrics_overhead_instrumented_samples_per_sec", inst_sps,
          cpu=True, bare_samples_per_sec=round(bare_sps, 1),
          overhead_pct=round(overhead_pct, 3),
          quiet_pairs=len(quiet),
          best_of_n_overhead_pct=round(best_of_n_pct, 3),
          median_overhead_pct=round(
              (1.0 - statistics.median(ratios)) * 100.0, 3),
          bit_exact=inst_hist == bare_hist, epochs=epochs, pairs=pairs,
          ratio_spread=[round(min(ratios), 3), round(max(ratios), 3)])
    # AFTER the emit so the measurement always lands: a broken contract
    # must fail the scenario loudly, not ride a JSON field nobody greps
    assert inst_hist == bare_hist, \
        "instrumented metric history diverged from the bare run"
    assert overhead_pct < 2.0, \
        f"instrumentation overhead {overhead_pct:.2f}% >= 2%"


def bench_compile_probe():
    """One cold-or-warm boot measurement (the ``compile_probe`` child of
    the ``compile_latency`` scenario): whether it is cold or warm is
    decided by the cache directory the parent points
    ``$JAX_COMPILATION_CACHE_DIR`` at.  Measures the two boot paths the
    tentpole targets — the flagship training step's first dispatch
    (trace + compile + run) and the serve engine's full bucket sweep —
    and prints ONE JSON line with wall seconds + compile-cost counters."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models.mnist_fc import build_fused
    from znicz_tpu.observe import compile_cache_stats
    from znicz_tpu.serve import BatchEngine

    # flagship-shaped training step (scaled to probe size: the number
    # that matters is the RATIO between two identical probes)
    prng.seed_all(7)
    w = build_fused(max_epochs=1, layers=(512, 512), minibatch_size=128,
                    n_train=256, n_valid=0)
    w.initialize(device=XLADevice())
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(1, 128, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (1, 128)).astype(np.int32))
    ms = jnp.ones((1, 128), bool)
    t0 = time.perf_counter()
    metrics = w.step.train_steps(xs, ys, ms)
    float(jax.device_get(metrics["loss"]))
    step_s = time.perf_counter() - t0

    # serve bucket sweep: an MLP big enough that XLA compile time
    # dominates the warm path's load-from-cache + run
    w1 = jnp.asarray(rng.normal(0, 0.1, (256, 512)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(0, 0.1, (512, 512)).astype(np.float32))
    w3 = jnp.asarray(rng.normal(0, 0.1, (512, 16)).astype(np.float32))

    @jax.jit
    def mlp(x):
        return jnp.tanh(jnp.tanh(x @ w1) @ w2) @ w3

    engine = BatchEngine(mlp, max_batch=32, input_shape=(256,))
    t0 = time.perf_counter()
    engine.warmup()
    serve_s = time.perf_counter() - t0
    hits, misses = compile_cache_stats()
    print(json.dumps({"probe": "compile", "step_first_dispatch_s":
                      round(step_s, 3), "serve_warmup_s": round(serve_s, 3),
                      "serve_buckets": len(engine.buckets),
                      "cache_hits": hits, "cache_misses": misses}),
          flush=True)


def _run_compile_probe(cache_dir: str) -> dict:
    """Run one ``compile_probe`` child against ``cache_dir``; returns
    its JSON line.  A fresh process per probe is the point: the in-
    process jit/trace caches must not exist, so the only warmth is the
    persistent cache."""
    # the probe's own temporary directory REPLACES whatever cache
    # directory this process was given: cold must mean cold
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.pop("ZNICZ_TPU_COMPILE_CACHE", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "compile_probe"], capture_output=True, text=True,
        timeout=CPU_TIMEOUT, env=env, cwd=REPO)
    for line in reversed((proc.stdout or "").strip().splitlines()):
        if line.startswith("{"):
            doc = json.loads(line)
            if doc.get("probe") == "compile":
                return doc
    tail = (proc.stderr or "").strip().splitlines()[-3:]
    raise RuntimeError(f"compile_probe produced no result "
                       f"(rc={proc.returncode}): {' | '.join(tail)}")


def bench_compile_latency():
    """ISSUE 7 scenario: cold-process vs warm-cache boot (CPU by design
    — it measures the compile-latency plane's machinery, not the chip).
    Two identical probe children share one FRESH cache directory: the
    first pays every compile cold and populates the cache, the second
    pays trace + cache-load only.  A third leg exports a forward
    package with AOT executables and boots the serve engine from them,
    pinning ``compile_count == 0``.  The line lands first; the
    acceptance contracts (warm serve sweep <= 50% of cold, zero-compile
    AOT boot) are ASSERTED after it flushes."""
    import shutil
    import tempfile

    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.serve import BatchEngine
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.utils.export import ExportedForward, export_forward

    cache_dir = tempfile.mkdtemp(prefix="znicz_cc_bench_")
    pkg_dir = tempfile.mkdtemp(prefix="znicz_aot_bench_")
    try:
        cold = _run_compile_probe(cache_dir)
        warm = _run_compile_probe(cache_dir)

        # AOT leg: export -> precompile -> engine boot with zero compiles
        prng.seed_all(23)
        w = StandardWorkflow(
            name="AotBench", loss_function="softmax",
            layers=[{"type": "all2all_tanh",
                     "->": {"output_sample_shape": 64}},
                    {"type": "softmax", "->": {"output_sample_shape": 10}}],
            loader_name="synthetic_classifier",
            loader_config={"n_classes": 10, "sample_shape": (32,),
                           "n_train": 64, "n_valid": 0,
                           "minibatch_size": 32},
            decision_config={"max_epochs": 1})
        w.initialize(device=XLADevice())
        w.run()
        pkg = os.path.join(pkg_dir, "aot_bench.npz")
        export_forward(w, pkg, aot_max_batch=16)
        t0 = time.perf_counter()
        engine = BatchEngine(ExportedForward(pkg), max_batch=16)
        engine.warmup()
        aot_boot_s = time.perf_counter() - t0
        ratio = warm["serve_warmup_s"] / max(cold["serve_warmup_s"], 1e-9)
        _emit("compile_latency_warm_serve_boot_seconds",
              warm["serve_warmup_s"], unit="s", lower_is_better=True,
              cpu=True, warm_over_cold=round(ratio, 3),
              cold_serve_warmup_s=cold["serve_warmup_s"],
              serve_buckets=cold["serve_buckets"],
              step_first_dispatch_s={"cold": cold["step_first_dispatch_s"],
                                     "warm": warm["step_first_dispatch_s"]},
              cache_misses={"cold": cold["cache_misses"],
                            "warm": warm["cache_misses"]},
              cache_hits={"cold": cold["cache_hits"],
                          "warm": warm["cache_hits"]},
              aot_boot_s=round(aot_boot_s, 3),
              aot_boot_compile_count=engine.compile_count,
              aot_buckets=engine.aot_count)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(pkg_dir, ignore_errors=True)
    # AFTER the emit so the measurement always lands: a broken contract
    # must fail the scenario loudly, not ride a JSON field nobody greps
    assert engine.compile_count == 0, \
        f"AOT boot compiled {engine.compile_count} buckets (want 0)"
    assert ratio <= 0.5, \
        (f"warm serve bucket sweep at {ratio:.2f}x of cold "
         f"(want <= 0.5): persistent cache is not paying for itself")


def child_main(mode: str) -> None:
    if mode != "tpu":
        # every other mode is a CPU scenario: pinned here, before jax is
        # imported, so neither this child nor any worker it spawns can
        # claim the chip by accident
        os.environ["JAX_PLATFORMS"] = "cpu"
    if mode == "pipeline":
        # input-pipeline scenario: CPU by design (measures the prefetch
        # + staging machinery through the real run loop)
        _enable_compile_cache()
        bench_input_pipeline()
        return
    if mode == "serve":
        # serving-plane scenario: CPU by design (the parent pins
        # JAX_PLATFORMS=cpu), measures batcher+engine machinery
        _enable_compile_cache()
        bench_serve()
        return
    if mode == "generate":
        # generative-serving scenario: CPU by design (measures the
        # KV-cache decode + continuous-batching machinery)
        _enable_compile_cache()
        bench_generate()
        bench_generate_longtail()
        return
    if mode == "fleet":
        # serving-fleet scenario (ISSUE 13): router overhead +
        # autoscaler reaction over real worker subprocesses; the bench
        # child itself only routes (CPU, no model math in-process)
        _enable_compile_cache()
        bench_fleet()
        return
    if mode == "train_while_serve":
        # continuous-learning scenario (ISSUE 14): serving p95 with
        # the trainer idle vs co-resident vs mid-rollout, plus
        # publish-to-adopted latency — real worker + trainer
        # subprocesses; the bench child itself only routes
        _enable_compile_cache()
        bench_train_while_serve()
        return
    if mode == "metrics_overhead":
        # telemetry-plane scenario: CPU by design (measures the
        # observe instrumentation through the real run loop)
        _enable_compile_cache()
        bench_metrics_overhead()
        return
    if mode == "zero_sharding":
        # ZeRO shard_params scenario: a FORCED 8-virtual-device CPU
        # platform (must land in the env before the first jax backend
        # init) so dp mesh sizes 2/4/8 exercise the real sharded layout
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=8").strip()
        _enable_compile_cache()
        bench_zero_sharding()
        return
    if mode == "quantized_collectives":
        # quantized-collectives scenario: the same forced 8-virtual-
        # device CPU platform as zero_sharding (dp 2/4/8 int8 codec
        # matrix; the flag must land before the first jax backend init)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=8").strip()
        _enable_compile_cache()
        bench_quantized_collectives()
        return
    if mode == "compile_latency":
        # compile-latency scenario: orchestrates two compile_probe
        # children over a fresh shared cache dir + an AOT boot leg
        _enable_compile_cache()
        bench_compile_latency()
        return
    if mode == "compile_probe":
        # one boot measurement; the cache dir arrives via
        # $JAX_COMPILATION_CACHE_DIR (set by the compile_latency parent)
        from znicz_tpu import compilecache

        compilecache.configure(min_compile_time_s=0.0)
        bench_compile_probe()
        return
    _enable_compile_cache()
    bench_fc()
    flagship = bench_alexnet()
    # remaining phases, round-4 evidence first (compiled Pallas parity,
    # flash transformer): every line above already landed, so a timeout
    # truncates the least-critical tail
    for phase in (bench_pallas_parity, bench_transformer, bench_cifar,
                  bench_deconv_ae, bench_kohonen,
                  bench_mnist_wallclock):
        try:
            phase()
        except Exception as exc:
            # every earlier line is already flushed; the failure itself
            # is the child's exit code, not a comment on stderr
            print(f"# {phase.__name__} failed: {exc!r}", file=sys.stderr)
            sys.stdout.flush()
            raise
    # the driver reads the LAST line as the headline: re-emit the flagship
    print(json.dumps(flagship), flush=True)


# ---------------------------------------------------------------------------
# parent orchestration
# ---------------------------------------------------------------------------

def _run_child(mode: str, timeout: int, platform=None):
    """Run a bench child; return (json lines parsed, note)."""
    env = dict(os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    stdout, note = "", None
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", mode],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=REPO)
        stdout = proc.stdout or ""
        if proc.returncode != 0:
            tail = (proc.stderr or "").strip().splitlines()[-3:]
            note = f"{mode}: rc={proc.returncode} {' | '.join(tail)}"[:300]
    except subprocess.TimeoutExpired as exc:
        stdout = exc.stdout or ""
        if isinstance(stdout, bytes):
            stdout = stdout.decode(errors="replace")
        note = f"{mode}: timeout after {timeout}s"
    results = []
    for line in stdout.strip().splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                results.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return results, note


def _sentinel_report(results, label: str) -> None:
    """ISSUE 20: advisory perf-regression check for one scenario's
    fresh lines against the last recorded round (report-only — the
    hard gate is ``tools/bench_sentinel.py`` between recorded
    ``BENCH_r*.json`` artifacts; here a cliff just gets called out on
    stderr the moment the scenario lands instead of one round later)."""
    rows = {str(r["metric"]): r for r in results or []
            if isinstance(r, dict) and "metric" in r and "value" in r}
    if not rows:
        return
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_sentinel",
            os.path.join(REPO, "tools", "bench_sentinel.py"))
        sentinel = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sentinel)
        prev = {m: r for m, r in _prev_round_values().items()
                if m in rows}
        for f in sentinel.compare(prev, rows):
            if f["kind"] in ("regression", "improvement"):
                print(f"# sentinel [{label}]: {f['kind'].upper()} "
                      f"{f['metric']} {f.get('prev')} -> {f.get('new')} "
                      f"({f['detail']})", file=sys.stderr)
    except Exception as exc:  # noqa: BLE001 — advisory only
        print(f"# sentinel unavailable: {exc!r}", file=sys.stderr)


def main():
    notes = []
    # ISSUE 5 satellite: the r05 artifact tail showed the same metric
    # line duplicated VERBATIM (the child re-emits its flagship for the
    # standalone --child contract, and the parent's final re-emit could
    # repeat an already-last line).  The parent now prints each distinct
    # record once; a deliberate final re-emit that would repeat an
    # earlier line is labeled {"reemit": true} instead of silently
    # doubling the record.
    printed: list[str] = []

    def emit(r) -> None:
        line = json.dumps(r)
        if line not in printed:
            print(line, flush=True)
            printed.append(line)

    results, note = _run_child("tpu", TPU_TIMEOUT)
    for r in results:
        emit(r)
    if note or not results:
        # no chip, a phase that raised, or a time-out: whatever landed
        # is printed above, and the run fails — no retry, no CPU figure
        # under a device metric's name
        print(f"bench: chip child failed: {note or 'no result line'}",
              file=sys.stderr)
        sys.exit(1)
    _sentinel_report(results, "tpu")

    # serving-plane / input-pipeline / metrics-overhead scenarios: their
    # own CPU children (independent of the chip pool), BEFORE the final
    # flagship re-emit so the driver's last-line contract is untouched
    for extra_mode in ("serve", "generate", "fleet",
                       "train_while_serve", "pipeline",
                       "zero_sharding", "quantized_collectives",
                       "metrics_overhead", "compile_latency"):
        # compile_latency's own legs each budget up to CPU_TIMEOUT (two
        # fresh-process probes + the AOT export leg) — its OUTER timeout
        # must exceed their sum or a slow-but-in-budget cold probe gets
        # the whole scenario killed mid-warm-probe.  generate runs the
        # base scenario PLUS the three-arm long-tail comparison (each
        # arm primes then times), so it gets a doubled budget too.
        # fleet boots real worker subprocesses (one cold + one
        # autoscaled) on top of its request sweeps — doubled budget
        # like generate; train_while_serve boots 2 workers + a
        # supervised trainer and waits out a publish + rollout
        budget = 4 * CPU_TIMEOUT if extra_mode == "compile_latency" \
            else 2 * CPU_TIMEOUT if extra_mode in (
                "generate", "fleet", "train_while_serve") \
            else CPU_TIMEOUT
        extra_results, note = _run_child(extra_mode, budget,
                                         platform="cpu")
        if note:
            notes.append(note)
        for r in extra_results:
            emit(r)
        _sentinel_report(extra_results, extra_mode)

    # headline by NAME, not position: the driver reads the final line as
    # the flagship metric
    flagships = [r for r in results if r["metric"].startswith("alexnet")]
    best = flagships[-1] if flagships else results[-1]
    if notes:
        best["notes"] = "; ".join(notes)[:300]
    if printed and printed[-1] == json.dumps(best):
        pass                # already the last line — emitting once is
    else:                   # the whole point (ISSUE 5 satellite)
        if json.dumps(best) in printed:
            best["reemit"] = True   # labeled repeat, never verbatim
        print(json.dumps(best), flush=True)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        child_main(sys.argv[2])
    else:
        main()
