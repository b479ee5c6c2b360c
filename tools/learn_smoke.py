"""Train-while-serve smoke for tools/t1.sh (ISSUE 14).

Boots the REAL ``python -m znicz_tpu learn`` CLI in a fresh process —
which itself spawns 2 real ``generate --serve`` worker processes (each
appending accepted traffic to the shared feedback spool) and ONE
trainer process under the elastic supervisor — in ``--smoke-test``
mode: the CLI drives throttled self-traffic through its router, the
trainer consumes the spool and publishes after ``--publish-every``
epochs, and the adoption bridge rolls the fleet onto the published
package.

The CLI's JSON verdict is re-asserted here:

- at least one publish was ADOPTED (polled rollout ran to done);
- the fleet CONVERGED: every worker reports the published package's
  sha256 (and it differs from the base package's — the loop actually
  moved the weights);
- the router ledger CLOSED (admitted == completed + failed +
  client_gone) with zero broken streams — zero lost requests.

jax-on-CPU; the compile cache is pinned off (a CPU smoke has no use
for it; the segfault once blamed on it was never reproduced — PR 21).
Every failure prints a ``learn_smoke:``-prefixed line, exits 1.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def fail(msg: str) -> "None":
    print(f"learn_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def build_package(tmp: str) -> str:
    import numpy as np

    from znicz_tpu.parallel.params import init_params
    from znicz_tpu.utils.export import export_lm

    charmap = list("abcdefgh .,!?")
    params = init_params(np.random.default_rng(31), 2, 32, 4, 64,
                         len(charmap))
    path = os.path.join(tmp, "lm.npz")
    export_lm(params, path, heads=4, charmap=charmap, name="lm_base")
    return path


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="znicz_learn_smoke_")
    try:
        pkg = build_package(tmp)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   ZNICZ_TPU_COMPILE_CACHE="off")
        proc = subprocess.run(
            [sys.executable, "-m", "znicz_tpu", "learn", pkg,
             "--workers", "2", "--port", "0", "--smoke-test",
             "--max-epochs", "2", "--publish-every", "2",
             "--records-per-epoch", "6", "--seq-len", "8",
             "--run-dir", os.path.join(tmp, "learn"),
             "--", "--slots", "2", "--max-len", "48"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=660)
        verdict = None
        for line in (proc.stdout or "").strip().splitlines():
            if line.startswith("{"):
                try:
                    verdict = json.loads(line)
                except json.JSONDecodeError:
                    pass
        if proc.returncode != 0 or verdict is None:
            fail(f"learn CLI rc={proc.returncode}; stdout tail: "
                 f"{(proc.stdout or '')[-1500:]!r}; stderr tail: "
                 f"{(proc.stderr or '')[-1500:]!r}")
        if verdict.get("smoke") != "ok":
            fail(f"CLI verdict bad: {verdict}")
        if verdict.get("adoptions", 0) < 1 or \
                not verdict.get("converged"):
            fail(f"no adopted publish / fleet not converged: {verdict}")
        if verdict.get("fingerprint") == verdict.get(
                "base_fingerprint"):
            fail(f"fleet still serves the BASE weights — the loop "
                 f"never moved them: {verdict}")
        ledger = verdict.get("ledger") or {}
        if ledger.get("admitted") != ledger.get("completed", 0) + \
                ledger.get("failed", 0) + ledger.get("client_gone", 0):
            fail(f"router ledger does not close: {ledger}")
        traffic = verdict.get("traffic") or {}
        if traffic.get("broken"):
            fail(f"broken client streams during the loop: {traffic}")
        print(f"learn_smoke: ok — {verdict['adoptions']} publish(es) "
              f"adopted (latency "
              f"{verdict.get('adoption_latency_s'):.1f}s), fleet on "
              f"sha {verdict['fingerprint']}, ledger closed over "
              f"{ledger.get('admitted')} routed requests "
              f"({traffic})")
        return 0
    except subprocess.TimeoutExpired as exc:
        fail(f"learn CLI did not finish within 660s; stdout tail: "
             f"{(exc.stdout or b'')[-1200:]!r}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
