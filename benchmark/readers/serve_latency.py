"""Serving tails, timed by the benchmark from the instant a request was
DUE.  ``params.quantity``: ``ttft`` (first token minus due time, one sample
a request; a failed or refused request counts as the largest value there
is) or ``gap`` (between consecutive tokens, pooled over every request).
``params.q``: the percentile.  The sample count goes on an earlier line."""

from benchlib import percentile


def read(rc):
    s = rc.samples
    if s.get("kind") != "serve":
        return None
    p, reqs = rc.metric["params"], s["requests"]
    if p["quantity"] == "ttft":
        worst = 1e3 * s["wall_s"]
        vals = [worst if r["failed"] or not r["stamps"]
                else 1e3 * (r["stamps"][0] - r["due"]) for r in reqs]
    else:
        vals = [1e3 * (b - a) for r in reqs
                for a, b in zip(r["stamps"], r["stamps"][1:])]
    if not vals:
        return None
    beyond = int(len(vals) * (100 - p["q"]) / 100)
    rc.log(f"{rc.metric['name']}: {len(vals)} samples ({beyond} beyond the "
           f"percentile), p50 {percentile(vals, 50):.2f} p{p['q']} "
           f"{percentile(vals, p['q']):.2f} max {max(vals):.2f} ms")
    return percentile(vals, p["q"])
