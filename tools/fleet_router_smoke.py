"""Serving-fleet smoke for tools/t1.sh (ISSUE 13).

Boots the REAL ``python -m znicz_tpu fleet`` CLI in a fresh process —
which itself spawns 2 real ``generate --serve`` worker processes from
one exported LM package — then, over the wire only:

- streams generations THROUGH the router under light threaded traffic
  (readiness-gated least-loaded routing, X-Request-Id minted at the
  router);
- performs one rolling weight update via ``POST /rollout`` onto a
  second package and polls ``GET /rollout`` to completion;
- asserts ZERO lost requests: every admitted stream carries exactly
  one terminal event (completed or error-sentinel), the router ledger
  closes (admitted == completed + failed + client_gone), and rejected
  requests were refused at admission (503), never silently dropped;
- asserts the fleet CONVERGED: every worker reports the new package's
  sha256 on ``/readyz``, and steady-state decode compiles nothing
  (compile_count delta 0 across post-rollout traffic);
- asserts the merged ``/fleet/metrics.prom`` carries the
  ``znicz_router_*`` families beside the workers' rank-labeled series.

jax-on-CPU; the compile cache is pinned off (a CPU smoke has no use
for it; the segfault once blamed on it was never reproduced — PR 21).
Every failure prints a ``fleet_router_smoke:``-prefixed line, exits 1.
"""

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def fail(msg: str) -> "None":
    print(f"fleet_router_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def build_packages(tmp: str):
    import numpy as np

    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.utils.export import export_lm
    from znicz_tpu.utils.naming import package_fingerprint

    charmap = list("abcdefghijklmnopqrstuvwxyz .,!?")
    paths = []
    for seed, name in ((31, "lm_v1"), (32, "lm_v2")):
        params = init_params(np.random.default_rng(seed), 2, 32, 4, 64,
                             len(charmap))
        path = os.path.join(tmp, f"{name}.npz")
        export_lm(params, path, heads=4, charmap=charmap, name=name)
        paths.append(path)
    return paths[0], paths[1], package_fingerprint(paths[1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_json(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="znicz_fleet_router_smoke_")
    proc = None
    stop = threading.Event()
    results = []
    res_lock = threading.Lock()
    try:
        pkg_a, pkg_b, fp_b = build_packages(tmp)
        port = free_port()
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   ZNICZ_TPU_COMPILE_CACHE="off")
        proc = subprocess.Popen(
            [sys.executable, "-m", "znicz_tpu", "fleet", pkg_a,
             "--workers", "2", "--port", str(port),
             "--run-dir", os.path.join(tmp, "fleet"),
             "--", "--slots", "2", "--max-len", "48"],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        base = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 240
        while True:
            if proc.poll() is not None:
                out = (proc.stdout.read() or "")[-2000:]
                fail(f"fleet CLI exited rc={proc.returncode} before "
                     f"ready: {out}")
            try:
                if get_json(base + "/readyz", 5)["status"] == "ready":
                    break
            except (urllib.error.URLError, urllib.error.HTTPError,
                    OSError, ValueError):
                pass
            if time.monotonic() > deadline:
                fail("router never reported a ready worker within 240s")
            time.sleep(0.5)

        def client(cid: int) -> None:
            n = 0
            while not stop.is_set():
                n += 1
                req = urllib.request.Request(
                    base + "/generate",
                    data=json.dumps(
                        {"prompt": "ab" if cid % 2 else "cd",
                         "max_tokens": 5, "timeout_s": 30}).encode(),
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=90) as r:
                        lines = [json.loads(raw) for raw in r]
                except urllib.error.HTTPError as exc:
                    exc.read()
                    with res_lock:          # refused at admission:
                        results.append(("rejected", exc.code))
                    time.sleep(0.05)        # not admitted, not lost
                    continue
                except Exception as exc:  # noqa: BLE001
                    with res_lock:
                        results.append(("broken", repr(exc)))
                    continue
                terminals = [ln for ln in lines if ln.get("done")]
                with res_lock:
                    if len(terminals) != 1:
                        results.append(("bad_terminal", lines))
                    elif "error" in terminals[0]:
                        results.append(("errored", terminals[0]))
                    else:
                        results.append(("completed", n))

        threads = [threading.Thread(target=client, args=(c,),
                                    daemon=True) for c in range(3)]
        for t in threads:
            t.start()
        time.sleep(1.0)                     # traffic flowing pre-roll

        # -- the rolling weight update, over the wire ----------------
        req = urllib.request.Request(
            base + "/rollout",
            data=json.dumps({"package": pkg_b}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            if r.status != 202:
                fail(f"POST /rollout answered {r.status}")
        deadline = time.monotonic() + 300
        while True:
            status = get_json(base + "/rollout", 15)
            if status["state"] == "done":
                break
            if status["state"] == "failed":
                fail(f"rollout failed: {status}")
            if time.monotonic() > deadline:
                fail(f"rollout did not finish within 300s: {status}")
            time.sleep(0.5)
        time.sleep(1.0)                     # a post-roll traffic tail
        stop.set()
        for t in threads:
            t.join(timeout=120)

        if status.get("fingerprint", {}).get("sha256") != \
                fp_b["sha256"]:
            fail(f"rollout fingerprint mismatch: {status}")
        with res_lock:
            kinds: dict = {}
            for kind, _ in results:
                kinds[kind] = kinds.get(kind, 0) + 1
        if kinds.get("broken", 0) or kinds.get("bad_terminal", 0):
            fail(f"lost/garbled streams during the rollout: {kinds}; "
                 f"tail: {results[-6:]}")
        if kinds.get("completed", 0) < 8:
            fail(f"too little completed traffic to trust the drill: "
                 f"{kinds}")

        # ledger closes + fleet converged on the new fingerprint
        meta = get_json(base + "/metrics", 15)
        ledger = meta["router"]
        if ledger["admitted"] != ledger["completed"] + \
                ledger["failed"] + ledger["client_gone"]:
            fail(f"router ledger does not close: {ledger}")
        workers = meta["pool"]["workers"]
        shas = {(w.get("fingerprint") or {}).get("sha256")
                for w in workers}
        if shas != {fp_b["sha256"]}:
            fail(f"fleet serves a torn mix after the rollout: "
                 f"{workers}")

        # steady state: decode compiles nothing across fresh traffic
        bases = [w["base"] for w in workers]
        before = [get_json(b + "/metrics", 15)["decoder"]
                  ["compile_count"] for b in bases]
        for _ in range(4):
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"prompt": "ef",
                                 "max_tokens": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=90) as r:
                lines = [json.loads(raw) for raw in r]
            if not lines or not lines[-1].get("done") or \
                    "error" in lines[-1]:
                fail(f"post-rollout stream did not complete: {lines}")
        after = [get_json(b + "/metrics", 15)["decoder"]
                 ["compile_count"] for b in bases]
        if before != after:
            fail(f"steady-state decode recompiled after the rollout: "
                 f"{before} -> {after}")

        # merged telemetry: router families beside rank-labeled workers
        prom = urllib.request.urlopen(base + "/fleet/metrics.prom",
                                      timeout=15).read().decode()
        for needle in ("znicz_router_requests_total",
                       "znicz_fleet_scale_workers",
                       'znicz_generate_tokens_total{rank="'):
            if needle not in prom:
                fail(f"{needle!r} missing from /fleet/metrics.prom")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            proc.kill()
            fail("fleet CLI did not drain within 90s of SIGTERM")
        if rc != 0:
            fail(f"fleet CLI exited rc={rc} on SIGTERM drain")
        proc = None
        print(f"fleet_router_smoke: ok — rolled {len(workers)} workers "
              f"onto {os.path.basename(pkg_b)} under traffic, "
              f"{kinds.get('completed', 0)} completed / "
              f"{kinds.get('errored', 0)} errored / "
              f"{kinds.get('rejected', 0)} rejected, zero lost, "
              f"ledger closed, compile delta 0")
        return 0
    finally:
        stop.set()
        if proc is not None and proc.poll() is None:
            proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
