"""Generative serving smoke for tools/t1.sh (ISSUE 10).

Exports a tiny LM package (random transformer params + a charmap),
then boots the real ``python -m znicz_tpu generate --serve`` CLI in a
FRESH process (no in-process warmth to hide behind), streams one short
generation over HTTP, and asserts:

- the ndjson stream carries non-empty token lines and EXACTLY ONE
  terminal ``done`` line (the stream contract the chaos drill pins);
- ``GET /metrics`` shows the request completed and tokens counted;
- ``GET /metrics.prom`` exposes the ``znicz_generate_*`` metric
  families (the observability satellite, end to end over the wire),
  including the paged-arena occupancy gauges (the CLI serves from the
  block-paged KV arena by default, ISSUE 12).

Invoked with ``--speculative`` it runs the ISSUE 12 exactness leg
instead: two fresh-process boots from one draft-carrying package —
speculation off, then on — must stream BYTE-IDENTICAL greedy text, and
the ``znicz_generate_spec_tokens_total`` family must be live.

jax-on-CPU by design (the caller pins JAX_PLATFORMS=cpu); the compile
cache is pinned off — XLA's persistent cache intermittently segfaults
single-process workers on this box (PR 9 note).  Every failure prints
a ``generate_smoke:``-prefixed line and exits nonzero.
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
    print(f"generate_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def build_package(tmp: str, with_draft: bool = False) -> str:
    import numpy as np

    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.serve.paged import truncate_draft
    from znicz_tpu.utils.export import export_lm

    charmap = list("abcdefghijklmnopqrstuvwxyz .,!?")
    params = init_params(np.random.default_rng(23), 2, 32, 4, 64,
                         len(charmap))
    pkg = os.path.join(tmp, "lm_smoke.npz")
    export_lm(params, pkg, heads=4, charmap=charmap, name="smoke_lm",
              draft_params=truncate_draft(params, 1) if with_draft
              else None)
    return pkg


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def scrape(url: str, timeout: float = 5.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def boot(pkg: str, extra_args=()) -> "tuple":
    """Start a fresh-process `generate --serve` worker; returns
    ``(proc, base_url)`` once /healthz answers ok."""
    port = free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ZNICZ_TPU_COMPILE_CACHE="off")
    proc = subprocess.Popen(
        [sys.executable, "-m", "znicz_tpu", "generate", pkg,
         "--serve", "--port", str(port), "--slots", "2",
         "--max-len", "64", *extra_args],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    base = f"http://127.0.0.1:{port}"
    deadline = time.monotonic() + 120
    while True:
        if proc.poll() is not None:
            out = (proc.stdout.read() or "")[-2000:]
            fail(f"server exited rc={proc.returncode} before "
                 f"healthy: {out}")
        try:
            if json.loads(scrape(f"{base}/healthz"))["status"] == "ok":
                return proc, base
        except (urllib.error.URLError, OSError, json.JSONDecodeError):
            pass
        if time.monotonic() > deadline:
            proc.kill()
            fail("server never became healthy within 120s")
        time.sleep(0.25)


def drain(proc) -> None:
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        fail("server did not drain within 60s of SIGTERM")
    if rc != 0:
        fail(f"server exited rc={rc} on SIGTERM drain")


def generate_text(base: str, prompt: str, n: int = 12) -> str:
    """One GREEDY streamed generation; returns the concatenated text."""
    req = urllib.request.Request(
        f"{base}/generate",
        data=json.dumps({"prompt": prompt, "max_tokens": n,
                         "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json"})
    lines = []
    with urllib.request.urlopen(req, timeout=60) as r:
        for raw in r:
            lines.append(json.loads(raw))
    if not lines or not lines[-1].get("done") or \
            "error" in lines[-1]:
        fail(f"greedy stream did not end cleanly: {lines}")
    return "".join(ln["text"] for ln in lines if "token" in ln)


def speculative_leg() -> int:
    """ISSUE 12 satellite: the decoded text must be BYTE-IDENTICAL with
    speculation on vs off — two fresh-process boots from one package
    carrying a truncated draft, same greedy request, compared exactly;
    plus the spec/pages metric families live over the wire."""
    tmp = tempfile.mkdtemp(prefix="znicz_generate_smoke_spec_")
    proc = None
    try:
        pkg = build_package(tmp, with_draft=True)
        proc, base = boot(pkg)
        plain = generate_text(base, "hello world")
        drain(proc)
        proc, base = boot(pkg, ("--speculative", "--spec-k", "3"))
        meta = json.loads(scrape(base))
        if not meta.get("speculative") or not meta.get("paged"):
            fail(f"speculative boot meta wrong: {meta}")
        spec = generate_text(base, "hello world")
        if spec != plain:
            fail(f"speculative text diverged: {spec!r} != {plain!r}")
        prom = scrape(f"{base}/metrics.prom").decode()
        for family in ("znicz_generate_spec_tokens_total",
                       "znicz_generate_cache_pages_used",
                       "znicz_generate_cache_pages_total"):
            if family not in prom:
                fail(f"{family} missing from /metrics.prom")
        snap = json.loads(scrape(f"{base}/metrics"))["generate"]
        if snap["spec_accepted"] + snap["spec_rejected"] < 1:
            fail(f"verify pass judged no draft tokens: {snap}")
        drain(proc)
        proc = None
        print(f"generate_smoke: ok — speculative text byte-identical "
              f"({plain!r}), spec families live")
        return 0
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="znicz_generate_smoke_")
    proc = None
    try:
        pkg = build_package(tmp)
        proc, base = boot(pkg)

        req = urllib.request.Request(
            f"{base}/generate",
            data=json.dumps({"prompt": "hello world", "max_tokens": 8,
                             "temperature": 0.8, "top_k": 5,
                             "seed": 7}).encode(),
            headers={"Content-Type": "application/json"})
        lines = []
        with urllib.request.urlopen(req, timeout=60) as r:
            if r.headers["Content-Type"] != "application/x-ndjson":
                fail(f"unexpected content type "
                     f"{r.headers['Content-Type']!r}")
            for raw in r:
                lines.append(json.loads(raw))
        tokens = [ln for ln in lines if "token" in ln]
        terminals = [ln for ln in lines if ln.get("done")]
        if len(tokens) != 8:
            fail(f"wanted 8 streamed tokens, got {len(tokens)}: {lines}")
        if not all("text" in ln for ln in tokens):
            fail(f"token lines missing charmap text: {tokens[:3]}")
        if len(terminals) != 1 or terminals[0].get("reason") != \
                "length" or lines[-1] is not terminals[0]:
            fail(f"stream must end with exactly one done line: {lines}")

        snap = json.loads(scrape(f"{base}/metrics"))
        gen = snap.get("generate", {})
        if gen.get("completed") != 1 or gen.get("tokens") != 8:
            fail(f"metrics did not count the generation: {gen}")
        if snap.get("decoder", {}).get("prefill_count", 0) < 1:
            fail(f"decoder stats missing prefill: {snap.get('decoder')}")

        prom = scrape(f"{base}/metrics.prom").decode()
        for family in ("znicz_generate_tokens_total",
                       "znicz_generate_requests_total",
                       "znicz_generate_ttft_seconds",
                       "znicz_generate_active_slots",
                       # ISSUE 12: the CLI defaults to the paged arena,
                       # so its occupancy gauges must be live
                       "znicz_generate_cache_pages_used",
                       "znicz_generate_cache_pages_total"):
            if family not in prom:
                fail(f"{family} missing from /metrics.prom")

        drain(proc)
        proc = None
        print(f"generate_smoke: ok — streamed {len(tokens)} tokens, "
              f"terminal line + metrics families verified")
        return 0
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(speculative_leg() if "--speculative" in sys.argv[1:]
             else main())
