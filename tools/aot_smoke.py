"""Zero-JIT serve boot smoke for tools/t1.sh (ISSUE 7).

Exports a tiny forward package, embeds ahead-of-time executables
(`attach_aot`), then boots the real `python -m znicz_tpu serve` CLI in
a FRESH process (no in-process jit/trace cache warmth to hide behind),
scrapes `GET /metrics`, and asserts the engine compiled **nothing**:
`compile_count == 0` with every bucket served from its deserialized
AOT executable.  One `POST /predict` round-trip proves the zero-JIT
boot actually serves.

jax-on-CPU by design (the caller pins JAX_PLATFORMS=cpu); the AOT
fingerprint is captured and checked on the same box, so the match is
exact.  Every failure prints an `aot_smoke:`-prefixed line and exits
nonzero.
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
    print(f"aot_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def build_package(tmp: str) -> str:
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.utils.export import attach_aot, export_forward

    prng.seed_all(23)
    w = StandardWorkflow(
        name="AotSmoke", loss_function="softmax",
        layers=[{"type": "all2all_tanh", "->": {"output_sample_shape": 8}},
                {"type": "softmax", "->": {"output_sample_shape": 3}}],
        loader_name="synthetic_classifier",
        loader_config={"n_classes": 3, "sample_shape": (6,), "n_train": 60,
                       "n_valid": 0, "minibatch_size": 20},
        decision_config={"max_epochs": 1})
    w.initialize(device=XLADevice())
    w.run()
    pkg = os.path.join(tmp, "aot_smoke.npz")
    export_forward(w, pkg)
    meta = attach_aot(pkg, max_batch=8)
    if meta["buckets"] != [1, 2, 4, 8]:
        fail(f"unexpected AOT buckets {meta['buckets']}")
    return pkg


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def scrape(url: str, timeout: float = 5.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="znicz_aot_smoke_")
    proc = None
    try:
        # hermetic persistent cache: the smoke must not depend on (or
        # pollute) whatever cache directory it was started under, so its
        # own temporary one REPLACES $JAX_COMPILATION_CACHE_DIR — the
        # variable that outranks everything else (docs/COMPILE.md)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "xla")
        os.environ.pop("ZNICZ_TPU_COMPILE_CACHE", None)
        pkg = build_package(tmp)
        port = free_port()
        proc = subprocess.Popen(
            [sys.executable, "-m", "znicz_tpu", "serve", pkg,
             "--port", str(port), "--max-batch", "8"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        base = f"http://127.0.0.1:{port}"
        deadline = time.time() + 90
        while True:
            if proc.poll() is not None:
                _, err = proc.communicate()
                fail(f"serve exited rc={proc.returncode}: "
                     f"{err.strip().splitlines()[-3:]}")
            try:
                if scrape(f"{base}/healthz")["status"] == "ok":
                    break
            except (urllib.error.URLError, OSError, ConnectionError):
                pass
            if time.time() > deadline:
                fail("serve did not come up within 90s")
            time.sleep(0.25)
        metrics = scrape(f"{base}/metrics")
        engine = metrics.get("engine", {})
        if engine.get("compile_count") != 0:
            fail(f"AOT boot compiled {engine.get('compile_count')} "
                 f"buckets (want 0) — engine stats: {engine}")
        if engine.get("aot_count") != 4:
            fail(f"expected 4 AOT-served buckets, got "
                 f"{engine.get('aot_count')} — engine stats: {engine}")
        req = urllib.request.Request(
            f"{base}/predict",
            data=json.dumps({"input": [[0.0] * 6, [1.0] * 6]}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        if len(out["output"]) != 2 or len(out["output"][0]) != 3:
            fail(f"bad predict shape: {out}")
        after = scrape(f"{base}/metrics")["engine"]
        if after.get("compile_count") != 0:
            fail("the predict round-trip itself compiled a bucket")
        print(f"aot_smoke: ok — zero-JIT boot served on :{port} "
              f"(compile_count=0, aot_count=4, "
              f"run_count={after.get('run_count')})")
        return 0
    finally:
        if proc is not None and proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
