"""Fleet telemetry smoke for tools/t1.sh (ISSUE 11).

Boots TWO real ``python -m znicz_tpu generate --serve`` workers in
fresh processes (rank env set, the elastic fleet contract), streams one
short generation through each so request phase spans exist on both,
then stands up a :class:`FleetAggregator` over their HTTP endpoints and
asserts end to end over the wire:

- ``/fleet/metrics.prom`` carries ``znicz_generate_*`` families with
  BOTH ``rank="0"`` and ``rank="1"`` labels, and the merged text
  re-parses cleanly (no torn exposition);
- the merged fleet trace (aggregator ``trace_doc`` AND the
  ``python -m znicz_tpu trace --fleet`` CLI) carries request phase
  spans (``generate.prefill``) from both ranks on one timeline;
- the fleet watchtower sees the merged view (a trivial rule over
  ``znicz_generate_tokens_total`` summed across ranks evaluates).

jax-on-CPU; the compile cache is pinned off (a CPU smoke has no use
for it; the segfault once blamed on it was never reproduced — PR 21).
Every failure prints a ``fleet_smoke:``-prefixed line and exits 1.
"""

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def fail(msg: str) -> "None":
    print(f"fleet_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def build_package(tmp: str) -> str:
    import numpy as np

    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.utils.export import export_lm

    charmap = list("abcdefghijklmnopqrstuvwxyz .,!?")
    params = init_params(np.random.default_rng(29), 2, 32, 4, 64,
                         len(charmap))
    pkg = os.path.join(tmp, "lm_fleet.npz")
    export_lm(params, pkg, heads=4, charmap=charmap, name="fleet_lm")
    return pkg


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_healthy(proc, base: str, deadline_s: float = 120.0) -> None:
    deadline = time.monotonic() + deadline_s
    while True:
        if proc.poll() is not None:
            out = (proc.stdout.read() or "")[-2000:]
            fail(f"worker exited rc={proc.returncode} before healthy: "
                 f"{out}")
        try:
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=5) as r:
                if json.load(r)["status"] == "ok":
                    return
        except (urllib.error.URLError, OSError, json.JSONDecodeError):
            pass
        if time.monotonic() > deadline:
            fail(f"worker at {base} never became healthy within "
                 f"{deadline_s:.0f}s")
        time.sleep(0.25)


def stream_one(base: str, prompt: str) -> None:
    req = urllib.request.Request(
        base + "/generate",
        data=json.dumps({"prompt": prompt, "max_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"})
    lines = []
    with urllib.request.urlopen(req, timeout=60) as r:
        if not r.headers.get("X-Request-Id"):
            fail("stream response missing the X-Request-Id header")
        for raw in r:
            lines.append(json.loads(raw))
    if not lines or not lines[-1].get("done"):
        fail(f"stream from {base} did not end with a done line: {lines}")


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="znicz_fleet_smoke_")
    procs = []
    try:
        pkg = build_package(tmp)
        bases = []
        for rank in range(2):
            port = free_port()
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       ZNICZ_TPU_COMPILE_CACHE="off",
                       ZNICZ_TPU_ELASTIC_RANK=str(rank),
                       ZNICZ_TPU_ELASTIC_WORLD="2")
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "znicz_tpu", "generate", pkg,
                 "--serve", "--port", str(port), "--slots", "2",
                 "--max-len", "64"],
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
            bases.append(f"http://127.0.0.1:{port}")
        for proc, base in zip(procs, bases):
            wait_healthy(proc, base)
        for i, base in enumerate(bases):
            stream_one(base, "hello" if i == 0 else "world")

        from znicz_tpu.observe import federation as fed

        agg = fed.FleetAggregator()
        for rank, base in enumerate(bases):
            agg.add_http_source(rank, base)
        # a fleet rule over the merged view must actually evaluate
        rule = agg.add_rule(fed.Rule(
            "smoke_fleet_tokens", "znicz_generate_tokens_total",
            lambda v: v >= 8))
        agg.tower.observe_now()
        if not rule.matching or rule.trips != 1:
            fail(f"fleet rule over merged tokens did not evaluate/trip: "
                 f"{rule.snapshot()}")

        prom = agg.render_prometheus()
        _, samples = fed.parse_prometheus(prom)   # must re-parse whole
        for family in ("znicz_generate_tokens_total",
                       "znicz_generate_requests_total",
                       "znicz_generate_ttft_seconds_count"):
            for rank in (0, 1):
                if not any(name == family and f'rank="{rank}"' in inner
                           for _, name, inner, _ in samples):
                    fail(f"{family} rank={rank} missing from "
                         f"/fleet/metrics.prom")

        merged = agg.trace_doc()
        pids = {e["pid"] for e in merged["traceEvents"]
                if e.get("name") == "generate.prefill"}
        if pids != {0, 1}:
            fail(f"merged trace is missing prefill spans from both "
                 f"ranks (pids {sorted(pids)})")
        rids = {e["args"]["rid"] for e in merged["traceEvents"]
                if e.get("name") == "generate.prefill"}
        if len(rids) < 2:
            fail(f"prefill spans are not rid-linked: {rids}")

        # the offline CLI merge must agree
        out_path = os.path.join(tmp, "fleet_trace.json")
        proc = subprocess.run(
            [sys.executable, "-m", "znicz_tpu", "trace", "--fleet",
             "-o", out_path] + bases,
            cwd=REPO, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            fail(f"trace --fleet exited {proc.returncode}: "
                 f"{proc.stderr.strip()[:300]}")
        with open(out_path) as f:
            cli_doc = json.load(f)
        cli_pids = {e["pid"] for e in cli_doc["traceEvents"]
                    if e.get("name") == "generate.prefill"}
        if cli_pids != {0, 1}:
            fail(f"CLI-merged trace missing ranks: {sorted(cli_pids)}")
        agg.close()

        for proc in procs:
            proc.send_signal(signal.SIGTERM)
        for proc in procs:
            try:
                rc = proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                fail("worker did not drain within 60s of SIGTERM")
            if rc != 0:
                fail(f"worker exited rc={rc} on SIGTERM drain")
        procs.clear()
        print(f"fleet_smoke: ok — 2 workers, per-rank labels merged, "
              f"fleet rule evaluated, merged trace carries both ranks "
              f"({sum(1 for e in cli_doc['traceEvents'] if e['ph'] != 'M')}"
              f" events)")
        return 0
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
