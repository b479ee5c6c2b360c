"""``train_mfu``: operations the forward and backward passes of one sample
require (the reference file's count; recomputation excluded) times the
whole-window rate per chip, over the chip's peak."""

from benchlib import window_rates


def read(rc):
    s = rc.samples
    if s.get("kind") != "train" or rc.peaks is None or not s["walls"]:
        return None
    rate = window_rates(s["walls"], s["k"] * s["batch"],
                        s["chips"])["rate_window"]
    return 100.0 * rate * s["flops_per_sample"] / rc.peaks["bf16_flops"]
