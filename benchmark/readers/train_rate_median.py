"""``train_step_rate_median``: K * batch / median(sub-window wall) /
chips.  The rate the step sustains in a sub-window that nothing stalled;
``train_samples_per_s`` falls below it by what stalls cost."""

from benchlib import window_rates


def read(rc):
    s = rc.samples
    if s.get("kind") != "train" or not s["walls"]:
        return None
    return window_rates(s["walls"], s["k"] * s["batch"],
                        s["chips"])["rate_median"]
