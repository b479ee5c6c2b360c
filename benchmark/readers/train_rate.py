"""``train_samples_per_s``: every sample of the window over all of the
window's time, per chip.  An earlier line gives the count of sub-windows,
the rate at the median one, and the three slowest with what the host did
in them, so that a stall is named and not guessed."""

from benchlib import window_rates


def _host(h: dict) -> str:
    return (f"cpu {h['cpu_s'] * 1e3:.0f} stolen {h['steal_s'] * 1e3:.0f} "
            f"iowait {h['iowait_s'] * 1e3:.0f} gc {h['gc_s'] * 1e3:.0f} ms, "
            f"{h['nivcsw']} involuntary switches, {h['majflt']} major "
            f"faults")


def read(rc):
    s = rc.samples
    if s.get("kind") != "train" or not s["walls"]:
        return None
    est = window_rates(s["walls"], s["k"] * s["batch"], s["chips"])
    host = s.get("host") or []
    slow = "; ".join(
        f"#{i} {w * 1e3:.1f} ms" + (f" ({_host(host[i])})" if host else "")
        for i, w in est["slowest"])
    rc.log(f"train rate: {est['n_windows']} sub-windows of {s['k']} steps, "
           f"{est['rate_window']:.3f} samples/s/chip over the whole window, "
           f"{est['rate_median']:.3f} at the median sub-window "
           f"({est['median_wall_s'] * 1e3:.2f} ms); slowest: {slow}"
           + (f"; traced sub-windows {s['traced_windows']}"
              if s.get("traced_windows") else ""))
    if host:
        total = {k: sum(h[k] for h in host) for k in host[0]}
        rc.log(f"host over the window: {_host(total)}")
    return est["rate_window"]
