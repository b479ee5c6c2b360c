"""Shared arithmetic of the benchmark: file lookup by name, percentiles,
the window's rates, the comparison of training readings, peaks.

Nothing here imports the program or touches a device, so the tests can
pin every function on the CPU.
"""

from __future__ import annotations

import importlib.util
import json
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))

_MODULES: dict = {}      # path -> module: a plug-in is executed once


class BenchmarkError(RuntimeError):
    """A refusal: the run prints no result and exits non-zero."""


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Roots:
    """Where the harness looks for data files and plug-in modules, by
    name.  The first root that has the file wins, so a test (or a later
    PR's rehearsal) can lay a directory of its own over the shipped one
    without editing a file that is there."""

    def __init__(self, extra: list[str] | None = None) -> None:
        self.dirs = [os.path.abspath(d) for d in (extra or [])] + [HERE]

    def path(self, kind: str, name: str, suffixes=(".json",)) -> str:
        for root in self.dirs:
            for suffix in suffixes:
                p = os.path.join(root, kind, name + suffix)
                if os.path.isfile(p):
                    return p
        raise BenchmarkError(
            f"no {kind}/{name}{'|'.join(suffixes)} under {self.dirs}")

    def data(self, kind: str, name: str) -> dict:
        return load_json(self.path(kind, name))

    def module(self, kind: str, name: str):
        """Import ``<root>/<kind>/<name>.py`` under a private module
        name (two roots may both carry a ``readers/foo.py``)."""
        p = self.path(kind, name, suffixes=(".py",))
        if p in _MODULES:
            return _MODULES[p]
        mod_name = f"_bench_{kind}_{name}_{abs(hash(p)) & 0xffffff:x}"
        spec = importlib.util.spec_from_file_location(mod_name, p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[p] = mod
        return mod


def benchmark_json(roots: Roots) -> dict:
    """``BENCHMARK.json``: beside an overlay root if it carries one,
    else at the root of the checkout (the parent of this directory)."""
    for d in roots.dirs:
        for cand in (os.path.join(d, "BENCHMARK.json"),
                     os.path.join(os.path.dirname(d), "BENCHMARK.json")):
            if os.path.isfile(cand):
                return load_json(cand)
    raise BenchmarkError("BENCHMARK.json not found")


# -- statistics ---------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (``q`` in 0..100) of a non-empty
    sequence; the same rule as numpy's default."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_rates(walls, samples_per_window: float, chips: int = 1) -> dict:
    """The two training rates.  ``walls`` are the wall seconds of the
    fenced sub-windows, back to back from the window's opening to its
    close, each of the same ``samples_per_window``.  ``rate_window`` is
    all the samples over all the time of the window, the end-to-end
    rate: a stall inside the window lowers it.  ``rate_median`` is the
    rate at the median sub-window, what the step sustains when nothing
    stalls (the per-layer ``train_step_rate_median``).  With them the
    three slowest sub-windows."""
    walls = [float(w) for w in walls]
    if not walls:
        raise ValueError("no sub-window closed inside the window")
    med = statistics.median(walls)
    slow = sorted(range(len(walls)), key=lambda i: -walls[i])[:3]
    return {
        "rate_window": samples_per_window * len(walls) / sum(walls) / chips,
        "rate_median": samples_per_window / med / chips,
        "n_windows": len(walls),
        "median_wall_s": med,
        "slowest": [(i, walls[i]) for i in slow],
    }


def traced_steps(samples: dict) -> int | None:
    """Steps the profiler saw in a traced training run, or None."""
    if not samples.get("traced_windows"):
        return None
    lo, hi = samples["traced_windows"]
    return (hi - lo) * samples["k"]


# -- the comparison of training readings --------------------------------------

def worst_leaf_gap(prog: dict, ref: dict) -> tuple[float, str]:
    """Gap between the program's norm and the reference's, leaf by leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero); the worst
    leaf and its name."""
    if set(prog) != set(ref):
        raise BenchmarkError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    med = statistics.median(ref.values())
    worst, name = 0.0, ""
    for leaf, r in ref.items():
        gap = abs(prog[leaf] - r) / max(r, med, 1e-30)
        if not gap <= worst:          # NaN is the worst there is
            worst, name = gap, leaf
    return worst, name


def worst_leaf_difference(prog: dict, ref: dict) -> tuple[float, str]:
    """Norm of (the program's leaf - the reference's), leaf by leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger; the worst leaf and its name.  First order in the
    error where a gap between two norms is second order, so it is the
    number to read where rounding errors cancel in the norms."""
    import numpy as np

    if set(prog) != set(ref):
        raise BenchmarkError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
             for k, v in ref.items()}
    med = statistics.median(norms.values())
    worst, name = 0.0, ""
    for leaf, r in ref.items():
        diff = np.asarray(prog[leaf], np.float64) - np.asarray(r, np.float64)
        gap = float(np.linalg.norm(diff.ravel())) / max(norms[leaf], med,
                                                        1e-30)
        if not gap <= worst:
            worst, name = gap, leaf
    return worst, name


def compare_train_readings(prog: dict, ref: dict) -> dict:
    """``{reading: (value, detail)}`` for the numbers a training cell
    compares: the worst of the first steps' losses (relative), the worst
    leaf's first-gradient norm and parameter-change norm, and, where both
    sides kept the first gradient itself (``grad_first``), the worst
    leaf's difference."""
    loss = max(abs(p - r) / max(abs(r), 1e-30)
               for p, r in zip(prog["loss"], ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]):
        raise BenchmarkError("the program and the reference took a "
                             "different number of steps")
    g, g_leaf = worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])
    d, d_leaf = worst_leaf_gap(prog["delta_norm"], ref["delta_norm"])
    out = {"loss_gap": (loss, f"steps {prog['loss']} vs {ref['loss']}"),
           "grad_norm_gap": (g, f"worst leaf {g_leaf}"),
           "delta_norm_gap": (d, f"worst leaf {d_leaf}")}
    if "grad_first" in prog and "grad_first" in ref:
        f, f_leaf = worst_leaf_difference(prog["grad_first"],
                                          ref["grad_first"])
        out["grad_diff_gap"] = (f, f"worst leaf {f_leaf}")
    return out


def judge(readings: dict, limits: dict) -> tuple[bool, list[str]]:
    """Every number compared, beside its limit; all must hold."""
    lines, ok = [], True
    for name, (value, detail) in readings.items():
        if name not in limits:
            raise BenchmarkError(f"no limit for {name} in the reference "
                                 f"file's LIMITS")
        good = value <= limits[name]      # NaN fails
        ok = ok and good
        lines.append(f"check {name}: {value:.6g} (limit {limits[name]:g}) "
                     f"{'ok' if good else 'FAILED'} [{detail}]")
    return ok, lines


# -- peaks --------------------------------------------------------------------

def peaks_for(device_kind: str, roots: Roots) -> dict:
    """The chip's published peaks; a kind the table lacks is an error."""
    table = load_json(os.path.join(roots.dirs[-1], "peaks.json"))
    for entry in table["devices"]:
        if entry["device_kind"] == device_kind:
            return entry
    raise BenchmarkError(
        f"no peaks for device_kind {device_kind!r} in benchmark/peaks.json "
        f"(has {[e['device_kind'] for e in table['devices']]})")
