"""How the limits in ``reference/<name>.py`` are read, on the chip, at the
cell's own size, in one process (one set-up's compiles serve every seed):

    python benchmark/limits.py --workload <name> --seeds 1,2,...  [--control 3] [--seconds 2]

For each seed it drives the cell as ``run.py`` does with a short window and
prints every number compared (the program against the reference); for the
first ``--control`` seeds it also prints the same numbers for the control:
the reference computed in the precision below the one the configuration
states (``reference/precision.py``), put in the program's place.  A limit
goes above the sound runs' largest and below the control's smallest.
Never part of a check; needs a TPU like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None, roots_extra=None, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sound, control = {}, {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rc, result, outcome = run.execute(
            ["--workload", args.workload, "--seed", str(seed), "--seconds",
             str(args.seconds), "--trace", "0"], roots_extra, allow_cpu,
            control=i < args.control)
        if rc:
            return rc
        samples = outcome["samples"]
        for name, value in samples["readings"].items():
            sound.setdefault(name, []).append(value)
        for name, value in samples.get("control_readings", {}).items():
            control.setdefault(name, []).append(value)
        print(f"[limits] seed {seed}: correct {result['correct']} sound "
              f"{samples['readings']} control "
              f"{samples.get('control_readings')}", flush=True)
    summary = {name: {"sound_max": max(vals), "sound": vals,
                      "control_min": min(control[name])
                      if name in control else None,
                      "control": control.get(name)}
               for name, vals in sound.items()}
    print("[limits] " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
