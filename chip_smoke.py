"""chip_smoke.py — the standing proof that znicz-tpu starts on the chip.

One process, no arguments: ``python chip_smoke.py`` from the checkout, on
a machine with a TPU.  It drives the main path once through the entry
points a user would type, at the full width of the models the repo
ships (depth is what it is, weights are random from a seed):

1. trainer       — AlexNet at ImageNet geometry through ``Launcher``;
2. lm_trainer    — the char-LM transformer (flash attention proven in
                   the lowered step) through ``Launcher``, then
                   ``export_lm``;
3. server        — ``generate_main`` over that package: the built-in
                   smoke request, then concurrent streamed requests,
                   with and without ``--pallas-decode`` (greedy tokens
                   must agree: identical, or parting only where the
                   model is undecided within bf16 rounding);
4. kernels       — the compiled Pallas parity sweep, and the AlexNet
                   step with ``engine.pallas`` on;
5. multichip     — data-parallel AlexNet and a dp2 x tp2 char-LM step,
                   when there are four devices.

There is no fallback: the first act is ``jax.devices()``, and anything
but a TPU ends the run non-zero before a phase starts.  A phase that
raises ends the run with its traceback.  The last line of a passing run
is one JSON object naming the device.

What each phase prints (cold and steady seconds, compile-cache hits and
misses, peak HBM) are set-up facts for whoever measures next.  They are
not performance claims and carry no metric's name.

The phase functions take their sizes as arguments so that
``tests/test_chip_smoke.py`` can drive the same code tiny on the CPU
with Pallas interpreted; ``__main__`` has only the full sizes.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import signal
import statistics
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

SEED = 21
#: what bf16 arithmetic may move a logit by, relative to the largest
#: one — the kernel sweep's own bf16 tolerance
BF16_BAND = 2e-2


# -- per-phase facts ----------------------------------------------------------

def _peak_hbm_bytes(jax_device) -> int | None:
    stats = jax_device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def phase(name: str, jax_device):
    """Run one phase: a banner, then — only if it did not raise — the
    facts the body put into the yielded dict plus what the process
    counters say."""
    from znicz_tpu import compilecache

    print(f"== {name}", flush=True)
    facts: dict = {}
    before = compilecache.stats()
    t0 = time.perf_counter()
    yield facts
    after = compilecache.stats()
    facts["wall_s"] = round(time.perf_counter() - t0, 2)
    facts["cache_hits"] = after["hits"] - before["hits"]
    facts["cache_misses"] = after["misses"] - before["misses"]
    peak = _peak_hbm_bytes(jax_device)
    facts["peak_hbm_mib"] = None if peak is None else round(peak / 2**20, 1)
    print(f"{name}: ok {json.dumps(facts)}", flush=True)


def _attach_tap(workflow, read):
    """Link a leaf unit after the workflow's train step that records
    ``(minibatch class, read(), perf_counter)`` for every minibatch —
    the public unit-graph way to watch a run that ``Launcher.main``
    owns."""
    from znicz_tpu.core.units import Unit

    loader = workflow.loader

    class Tap(Unit):
        def __init__(self, wf) -> None:
            super().__init__(wf, name="SmokeTap")
            self.rows: list = []

        def run(self) -> None:
            self.rows.append((int(loader.minibatch_class), read(),
                              time.perf_counter()))

    tap = Tap(workflow)
    tap.link_from(workflow.step)
    return tap


def _timing(rows, t0: float) -> dict:
    """Cold = phase start to the end of the first train minibatch
    (build, initialize, trace, compile, first run); steady = median of
    the train minibatches after it.  Every row ends in a host read of a
    device value, so the stamps are fenced."""
    from znicz_tpu.loader.base import TRAIN

    stamps = [t for cls, _, t in rows if cls == TRAIN]
    prev = [t0] + [t for _, _, t in rows]
    steps = [t - prev[i] for i, (cls, _, t) in enumerate(rows)
             if cls == TRAIN]
    return {"cold_s": round(stamps[0] - t0, 2),
            "steady_s_per_step": round(statistics.median(steps[1:]), 4)
            if len(steps) > 1 else None,
            "train_steps": len(steps)}


def _check_placement(tree, platform: str, what: str) -> int:
    """Every array leaf lives on ``platform`` devices; returns the count."""
    import jax

    leaves = jax.tree.leaves(tree)
    for leaf in leaves:
        wrong = {d.platform for d in leaf.devices()} - {platform}
        if wrong:
            raise AssertionError(f"{what}: a leaf lives on {wrong}, "
                                 f"wanted {platform}")
    return len(leaves)


def _check_losses(losses, what: str) -> None:
    if not losses or not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{what}: losses not finite: {losses}")
    if max(losses) - min(losses) < 1e-4:
        raise AssertionError(f"{what}: loss does not move: {losses}")


def _check_falling(losses, what: str) -> None:
    """At the full sizes, on the shipped data, a few steps must also
    take the loss DOWN (the tiny CPU drives see two steps of noise)."""
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")


def _step_runs(workflow) -> int:
    """The registry's run counter for this workflow's train step."""
    from znicz_tpu.observe import probe

    rows = probe.unit_timing_rows(workflow.name, [workflow.step.name])
    return int(rows[0][1]) if rows else 0


def _loader_minibatches(loader) -> int:
    mb = int(loader.max_minibatch_size)
    return sum(-(-int(n) // mb) for n in loader.class_lengths if n)


# -- phase 1: the AlexNet trainer ---------------------------------------------

def _train_step_call(workflow):
    """``(jitted index-fed train step, its arguments)`` — what
    ``FusedTrainStep.run`` dispatches, with a placeholder batch staged
    the way the step stages one — for lowering and inspection."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    st = workflow.step
    mb = int(workflow.loader.max_minibatch_size)
    idx, mask = st._put((np.zeros(mb, np.int32), np.ones(mb, bool)),
                        (P("data"), P("data")))
    return st._train_fn_idx._fn, (st._params, st._key, st._hyper_device(),
                                  *st._dataset_dev, idx, mask)


def _count_fused_sgd(workflow) -> int:
    """Calls of the fused optimizer kernel in the lowered train step (a
    Mosaic custom call carries its kernel's name; interpreted Pallas
    lowers to plain HLO and counts zero)."""
    fn, args = _train_step_call(workflow)
    return fn.lower(*args).as_text().count('"fused_sgd_update"')


def run_trainer(device, *, minibatch_size: int, n_classes: int,
                input_size: int, n_train: int, mesh=None,
                shard_update: bool = False, pallas: bool = False):
    """``python -m znicz_tpu znicz_tpu/models/alexnet.py -d tpu`` in
    process: ``Launcher.load`` + ``Launcher.main``.  Returns
    ``(workflow, per-minibatch mean losses, facts)``."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.models import alexnet

    t0 = time.perf_counter()
    prng.seed_all(SEED)
    prev_pallas = root.common.engine.get("pallas", False)
    root.common.engine.pallas = pallas
    try:
        launcher = Launcher(device=device)
        w, _ = launcher.load(
            alexnet.build, minibatch_size=minibatch_size,
            n_classes=n_classes, input_size=input_size, n_train=n_train,
            max_epochs=1, mesh=mesh)
        w.step.shard_update = shard_update      # ZeRO-1 state (multichip)

        def read():
            # the step keeps its metric sums on the device until the
            # class pass ends; flush_metrics is its debug read of the
            # running totals, and the read fences the step
            w.step.flush_metrics()
            return float(w.step.loss), int(w.step.minibatch_size)

        tap = _attach_tap(w, read)
        runs_before = _step_runs(w)
        launcher.main()
        # engine.pallas is read while the step traces, so the lowered
        # program must be inspected before the flag goes back
        fused_calls = _count_fused_sgd(w) if pallas else None
    finally:
        root.common.engine.pallas = prev_pallas
    # running totals -> per-minibatch means
    losses, prev = [], (0.0, 0)
    for _, (total, seen), _ in tap.rows:
        losses.append((total - prev[0]) / max(seen - prev[1], 1))
        prev = (total, seen)
    platform = device.jax_device.platform
    n_leaves = _check_placement(w.step._params, platform,
                                "AlexNet params + optimizer state")
    if w.step.compute_dtype != device.compute_dtype:
        raise AssertionError(f"step computes in {w.step.compute_dtype}, "
                             f"the device policy says "
                             f"{device.compute_dtype}")
    _check_losses(losses, "AlexNet")
    runs = _step_runs(w) - runs_before
    if runs != _loader_minibatches(w.loader) or runs != len(tap.rows):
        raise AssertionError(
            f"registry counted {runs} step runs, loader serves "
            f"{_loader_minibatches(w.loader)} minibatches, tap saw "
            f"{len(tap.rows)}")
    facts = {**_timing(tap.rows, t0), "state_leaves": n_leaves,
             "compute_dtype": str(w.step.compute_dtype.__name__),
             "losses": [round(v, 4) for v in losses]}
    if pallas:
        facts["fused_sgd_update_calls"] = fused_calls
    return w, losses, facts


# -- phase 2: the transformer trainer -----------------------------------------

def run_lm_trainer(device, *, n_layers: int, d: int, heads: int,
                   seq_len: int, minibatch_size: int, loss_chunks: int,
                   lr: float, data_dir: str = "", mesh=None,
                   interpret: bool = False):
    """The char-LM workflow through the same ``Launcher``.  Returns
    ``(workflow, {class: per-minibatch losses}, facts)``.  Unless
    ``interpret`` (the CPU dry run, where Pallas lowers to plain HLO)
    the lowered train step must carry both flash kernels as Mosaic
    custom calls — ``_block`` would otherwise have taken dense
    ``ring_attention``."""
    from znicz_tpu.core import prng
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.loader.base import TRAIN
    from znicz_tpu.models import char_lm
    from znicz_tpu.ops.pallas import attention as pattn

    t0 = time.perf_counter()
    prng.seed_all(SEED)
    launcher = Launcher(device=device)
    w, _ = launcher.load(
        char_lm.build, n_layers=n_layers, d=d, heads=heads,
        seq_len=seq_len, minibatch_size=minibatch_size,
        loss_chunks=loss_chunks, lr=lr, max_epochs=1, data_dir=data_dir,
        mesh=mesh)
    tap = _attach_tap(w, lambda: float(w.step.minibatch_mse))
    runs_before = _step_runs(w)
    launcher.main()
    losses: dict = {}
    for cls, loss, _ in tap.rows:
        losses.setdefault(cls, []).append(loss)
    platform = device.jax_device.platform
    n_leaves = _check_placement(w.step._params, platform, "char-LM params")
    _check_losses(losses.get(TRAIN, []), "char-LM")
    for cls, vals in losses.items():
        if not all(math.isfinite(v) for v in vals):
            raise AssertionError(f"char-LM class {cls}: {vals}")
    runs = _step_runs(w) - runs_before
    if runs != _loader_minibatches(w.loader):
        raise AssertionError(
            f"registry counted {runs} step runs, loader serves "
            f"{_loader_minibatches(w.loader)} minibatches")
    loader = w.loader
    staged = w.step._stage_batch(loader.minibatch_data.mem,
                                 loader.minibatch_labels.mem,
                                 int(loader.max_minibatch_size))
    lowered = w.step._step.lower(w.step._params, *staged)
    text = lowered.as_text()
    kernels = [name for name in (pattn.FWD_KERNEL_NAME,
                                 pattn.BWD_KERNEL_NAME)
               if "tpu_custom_call" in text and f'"{name}"' in text]
    if not interpret and len(kernels) != 2:
        raise AssertionError(
            f"the lowered train step carries Mosaic kernels {kernels}; "
            f"wanted both {pattn.FWD_KERNEL_NAME} and "
            f"{pattn.BWD_KERNEL_NAME} (t={seq_len}, head_dim="
            f"{d // heads}: "
            f"{pattn.unsupported_reason(seq_len, d // heads)})")
    facts = {**_timing(tap.rows, t0), "state_leaves": n_leaves,
             "vocab": int(loader.vocab_size), "mosaic_kernels": kernels,
             "first_eval_loss": next(
                 (round(v, 4) for c, v, _ in tap.rows if c != TRAIN), None),
             "train_losses": [round(v, 4) for v in losses[TRAIN]]}
    return w, losses, facts


# -- phase 3: the server ------------------------------------------------------

def _get_json(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _stream_generate(base: str, body: dict, timeout: float) -> dict:
    """One streamed ``POST /generate``: the ndjson token lines, the
    terminal line, and when the first token arrived."""
    req = urllib.request.Request(
        f"{base}/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    tokens, first, terminal = [], None, {}
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for raw in r:
            event = json.loads(raw)
            if "token" in event:
                if first is None:
                    first = time.perf_counter() - t0
                tokens.append(int(event["token"]))
            if event.get("done"):
                terminal = event
    return {"tokens": tokens, "terminal": terminal, "first_s": first,
            "wall_s": time.perf_counter() - t0}


def _drive_traffic(base: str, requests: list, out: dict,
                   boot_timeout_s: float, stop: threading.Event) -> None:
    """Client side of the traffic run, on a thread beside
    ``generate_main``: wait for /readyz, stream every request at once,
    read /metrics, then SIGTERM this process — the signal a supervisor
    sends, which ``generate_main`` turns into a drain and a return."""
    try:
        deadline = time.monotonic() + boot_timeout_s
        while True:
            if stop.is_set():
                return
            try:
                _get_json(f"{base}/readyz", timeout=2.0)
                break
            except (urllib.error.URLError, OSError):
                if time.monotonic() > deadline:
                    raise TimeoutError(f"{base}/readyz never answered")
                time.sleep(0.2)
        out["ready_s"] = time.perf_counter() - out["t0"]
        results: list = [None] * len(requests)

        def one(i: int) -> None:
            try:
                results[i] = _stream_generate(base, requests[i], 300.0)
            except Exception as exc:  # noqa: BLE001 — judged by the caller
                results[i] = {"error": repr(exc)}

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(len(requests))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        out["traffic_s"] = time.perf_counter() - t0
        out["results"] = results
        out["metrics"] = _get_json(f"{base}/metrics")
    except Exception as exc:  # noqa: BLE001 — re-raised by the caller
        out["error"] = exc
    finally:
        # only ever signal a process whose generate_main has installed
        # its handler (the default action would kill the run), and stop
        # waiting for that once generate_main has returned or raised
        while not stop.is_set():
            if signal.getsignal(signal.SIGTERM) not in (
                    signal.SIG_DFL, signal.SIG_IGN, None):
                os.kill(os.getpid(), signal.SIGTERM)
                break
            time.sleep(0.1)


def run_server(pkg: str, *, slots: int, max_len: int, requests: list,
               pallas_decode: bool, boot_timeout_s: float = 900.0):
    """``python -m znicz_tpu generate <pkg> --serve ...`` in process,
    twice: once with ``--smoke-test`` (its own single request), once
    serving ``requests`` concurrently until SIGTERM.  Returns
    ``(token lists, facts)``."""
    from znicz_tpu.fleet.workers import free_port
    from znicz_tpu.serve.server import generate_main

    flags = ["--slots", str(slots), "--max-len", str(max_len)]
    if pallas_decode:
        flags.append("--pallas-decode")
    t0 = time.perf_counter()
    rc = generate_main([pkg, "--serve", "--port", "0", *flags,
                        "--smoke-test"])
    if rc != 0:
        raise AssertionError(f"generate --smoke-test returned {rc}")
    smoke_s = time.perf_counter() - t0

    port = free_port()
    out: dict = {"t0": time.perf_counter()}
    stop = threading.Event()
    client = threading.Thread(
        target=_drive_traffic, daemon=True, name="smoke-client",
        args=(f"http://127.0.0.1:{port}", requests, out, boot_timeout_s,
              stop))
    client.start()
    try:
        rc = generate_main([pkg, "--serve", "--port", str(port), *flags])
    finally:
        stop.set()
    client.join(timeout=30.0)
    if "error" in out:
        raise out["error"]
    if rc != 0 or "results" not in out:
        raise AssertionError(f"generate --serve returned {rc} "
                             f"(client finished: {'results' in out})")
    tokens = []
    for body, res in zip(requests, out["results"]):
        if res is None or "error" in res or \
                "error" in res.get("terminal", {}) or \
                len(res["tokens"]) != body["max_tokens"]:
            raise AssertionError(f"request {body['max_tokens']=} "
                                 f"prompt={len(body['tokens'])}: {res}")
        tokens.append(res["tokens"])
    gen, dec = out["metrics"]["generate"], out["metrics"]["decoder"]
    if gen["completed"] != len(requests) or gen["failed"] or \
            gen["pages_used"] != 0 or dec["pages_peak"] < 1 or \
            dec["use_pallas"] != pallas_decode:
        raise AssertionError(f"server ledger: {gen} decoder: {dec}")
    facts = {"pallas_decode": pallas_decode,
             "smoke_boot_and_request_s": round(smoke_s, 2),
             "cold_s": round(out["ready_s"], 2),
             "steady_s_all_requests": round(out["traffic_s"], 2),
             "requests": len(requests),
             "tokens": sum(len(t) for t in tokens),
             "programs_compiled": dec["compile_count"],
             "decode_steps": dec["decode_steps"],
             "pages_peak": dec["pages_peak"]}
    return tokens, facts


def make_requests(vocab: int, max_len: int, n: int, max_tokens: int):
    """``n`` greedy requests of mixed prompt length (a few tokens up to
    three quarters of ``max_len``), seeded."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    top = max(2, (3 * max_len) // 4 - max_tokens)
    lengths = np.unique(np.geomspace(2, top, n).astype(int))
    lengths = np.resize(lengths, n)
    rng.shuffle(lengths)
    return [{"tokens": rng.integers(0, vocab, int(length)).tolist(),
             "max_tokens": int(max_tokens - (i % 3) * (max_tokens // 4)),
             "temperature": 0.0}
            for i, length in enumerate(lengths)]


def compare_greedy(pkg: str, requests: list, ref: list, got: list,
                   max_len: int) -> dict:
    """Greedy tokens from the jnp path (``ref``) and the Pallas kernel
    (``got``).  They are the same model in different arithmetic — the
    kernel's softmax accumulates page by page — so in bf16 they may
    part ways, but only where the model itself is undecided: at the
    first differing position both choices must sit within the bf16
    band of the best logit, as the plain prefill oracle scores them.
    Anything else is a wrong kernel and raises.  (In f32 on the CPU the
    two are identical outright; tests/test_chip_smoke.py pins that.)"""
    import numpy as np

    from znicz_tpu.serve.kvcache import KVDecoder
    from znicz_tpu.utils.export import load_lm

    ties, oracle = [], None
    for i, (body, a, b) in enumerate(zip(requests, ref, got)):
        if a == b:
            continue
        if oracle is None:
            params, meta = load_lm(pkg)
            oracle = KVDecoder(params, heads=meta["heads"],
                               max_len=max_len, batch=1)
        n = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
        _, logits = oracle.prefill(body["tokens"] + a[:n])
        band = BF16_BAND * float(np.abs(logits).max())
        behind = float(logits.max() - min(logits[a[n]], logits[b[n]]))
        tie = {"request": i, "position": n, "tokens": [a[n], b[n]],
               "behind_best": round(behind, 4), "band": round(band, 4)}
        if behind > band:
            raise AssertionError(
                f"greedy tokens differ with --pallas-decode where the "
                f"model is NOT undecided: {tie}; jnp {a} vs pallas {b}")
        ties.append(tie)
    return {"requests_identical": len(requests) - len(ties),
            "requests": len(requests), "near_ties": ties}


# -- phase 4: kernels ---------------------------------------------------------

def run_kernels(interpret: bool) -> dict:
    """The Pallas parity sweep, compiled (``interpret=False``) on the
    chip.  Anything but ``ok`` for any family fails the phase."""
    from znicz_tpu.utils.pallas_hw import run_parity

    results = run_parity(interpret=interpret)
    for name, verdict in results.items():
        print(f"  kernel {name}: {verdict}", flush=True)
    bad = {k: v for k, v in results.items() if v != "ok"}
    if bad:
        raise AssertionError(f"kernel sweep: {bad}")
    return results


# -- phase 5: four chips ------------------------------------------------------

def _distinct_devices(array) -> int:
    return len({s.device for s in array.addressable_shards})


def _compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def run_multichip(devices, *, trainer: dict, lm: dict, ref_trainer_loss,
                  ref_lm_loss, interpret: bool = False):
    """Data-parallel AlexNet over all ``devices`` and one char-LM
    workflow on ``data=n/2 x model=2``: state and batch on every
    device, the gradient all-reduce in the compiled program, first
    losses agreeing with the one-device runs — the char-LM's to bf16
    rounding, AlexNet's more loosely because each data shard draws its
    own dropout mask."""
    import numpy as np

    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.parallel.mesh import data_parallel_mesh, make_mesh

    n = len(devices)
    device = XLADevice(devices[0])
    facts = {}
    w, losses, f = run_trainer(device, mesh=data_parallel_mesh(n, devices),
                               shard_update=True, **trainer)
    fn, args = _train_step_call(w)
    idx = args[-2]
    spread = {"momenta": _distinct_devices(w.step._params[0]["vw"]),
              "weights": _distinct_devices(w.step._params[0]["w"]),
              "batch": _distinct_devices(idx)}
    if set(spread.values()) != {n} or idx.addressable_shards[0] \
            .data.shape != (idx.shape[0] // n,):
        raise AssertionError(f"dp{n} AlexNet placement: {spread}")
    hlo = _compiled_text(fn, *args)
    if "all-reduce" not in hlo:
        raise AssertionError(f"dp{n} AlexNet: no all-reduce in the HLO")
    if not np.isclose(losses[0], ref_trainer_loss, rtol=5e-2):
        raise AssertionError(f"dp{n} AlexNet first loss {losses[0]} vs "
                             f"one device {ref_trainer_loss}")
    facts["alexnet"] = {**f, "devices": spread, "first_loss_one_device":
                        round(ref_trainer_loss, 4),
                        "all_reduces": hlo.count("all-reduce(")}

    mesh = make_mesh({"data": n // 2, "seq": 1, "model": 2}, devices)
    w, lm_losses, f = run_lm_trainer(device, mesh=mesh,
                                     interpret=interpret, **lm)
    st = w.step
    loader = w.loader
    staged = st._stage_batch(loader.minibatch_data.mem,
                             loader.minibatch_labels.mem,
                             int(loader.max_minibatch_size))
    spread = {"wq": _distinct_devices(st._params["blocks"][0]["wq"]),
              "emb": _distinct_devices(st._params["emb"]),
              "tokens": _distinct_devices(staged[0])}
    if set(spread.values()) != {n}:
        raise AssertionError(f"dp{n // 2} x tp2 char-LM placement: "
                             f"{spread}")
    hlo = _compiled_text(st._step, st._params, *staged)
    if "all-reduce" not in hlo:
        raise AssertionError("dp x tp char-LM: no all-reduce in the HLO")
    first = {cls: vals[0] for cls, vals in lm_losses.items()}
    for cls, want in ref_lm_loss.items():
        if not np.isclose(first[cls], want, rtol=2e-2):
            raise AssertionError(f"dp x tp char-LM first loss of class "
                                 f"{cls}: {first[cls]} vs one device "
                                 f"{want}")
    facts["char_lm"] = {**f, "devices": spread, "first_losses_one_device":
                        {c: round(v, 4) for c, v in ref_lm_loss.items()},
                        "all_reduces": hlo.count("all-reduce(")}
    return facts


# -- the run ------------------------------------------------------------------

TRAINER = dict(minibatch_size=128, n_classes=1000, input_size=227,
               n_train=512)
# lr: the workflow's default 0.05 is tuned for its d=32 default and
# diverges at this width within three plain-SGD steps (6.9 -> 4e25 on
# the chip); 2e-4 trains
LM = dict(n_layers=6, d=512, heads=4, seq_len=2048, minibatch_size=8,
          loss_chunks=16, lr=2e-4)
SERVER = dict(slots=8, max_len=2048)


def main() -> int:
    import jax

    devices = jax.devices()
    d0 = devices[0]
    import importlib.metadata

    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}", flush=True)
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU — jax found {devices}; nothing ran",
              file=sys.stderr)
        return 1

    from znicz_tpu import compilecache, native
    from znicz_tpu.core.backends import TPUDevice

    device = TPUDevice()
    print(f"compile cache: {compilecache.ensure()} | native loader: "
          f"{'built' if native.available() else 'NOT built (numpy path)'}",
          flush=True)
    # is block_until_ready a fence here?  Dispatch of a long chain must
    # return well before the chain is done.
    import jax.numpy as jnp
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    chain = jax.jit(lambda a: jax.lax.fori_loop(
        0, 64, lambda _, b: (b @ a) * jnp.bfloat16(2.0 ** -12), a))
    chain(x).block_until_ready()
    t0 = time.perf_counter()
    y = chain(x)
    dispatched = time.perf_counter() - t0
    y.block_until_ready()
    fenced = time.perf_counter() - t0
    print(f"fence: dispatch returned in {dispatched * 1e3:.2f} ms, "
          f"block_until_ready in {fenced * 1e3:.2f} ms", flush=True)
    if not fenced > 2 * dispatched:
        raise AssertionError("block_until_ready did not wait for the "
                             "device: timings here would be enqueue rates")

    with phase("trainer", d0) as facts:
        _, trainer_losses, f = run_trainer(device, **TRAINER)
        facts.update(f)
        _check_falling(trainer_losses, "AlexNet")
        if f["compute_dtype"] != "bfloat16":
            raise AssertionError(f"compute_dtype on the chip: {f}")

    with tempfile.TemporaryDirectory(prefix="znicz_smoke_") as tmp:
        pkg = os.path.join(tmp, "char_lm.npz")
        with phase("lm_trainer", d0) as facts:
            w, lm_losses, f = run_lm_trainer(device, **LM)
            facts.update(f)
            _check_falling(f["train_losses"], "char-LM")
            w.step.export_lm(pkg)
            vocab = int(w.loader.vocab_size)
            del w
            facts["package_mib"] = round(os.path.getsize(pkg) / 2**20, 1)

        requests = make_requests(vocab, SERVER["max_len"], n=12,
                                 max_tokens=32)
        tokens = {}
        for pallas_decode in (False, True):
            with phase("server_pallas" if pallas_decode else "server",
                       d0) as facts:
                tokens[pallas_decode], f = run_server(
                    pkg, requests=requests, pallas_decode=pallas_decode,
                    **SERVER)
                facts.update(f)
                if pallas_decode:
                    facts["vs_jnp"] = compare_greedy(
                        pkg, requests, tokens[False], tokens[True],
                        SERVER["max_len"])

    with phase("kernels", d0) as facts:
        facts["sweep"] = run_kernels(interpret=False)
        _, _, f = run_trainer(device, pallas=True, **TRAINER)
        facts["alexnet_engine_pallas"] = f
        if f["fused_sgd_update_calls"] < 1:
            raise AssertionError("engine.pallas=True: no fused_sgd_update "
                                 "kernel in the lowered AlexNet step")

    if len(devices) >= 4:
        with phase("multichip", d0) as facts:
            facts.update(run_multichip(
                devices[:4], trainer=TRAINER, lm=LM,
                ref_trainer_loss=trainer_losses[0],
                ref_lm_loss={c: v[0] for c, v in lm_losses.items()}))
    else:
        print(f"multichip: not run ({len(devices)} device)", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
