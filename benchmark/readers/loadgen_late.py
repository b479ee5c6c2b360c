"""``loadgen_late_ms_p95``: how late requests left the generator (sent
minus due), so that a starved generator is not read as a fast server."""

from benchlib import percentile


def read(rc):
    s = rc.samples
    if s.get("kind") != "serve" or not s["requests"]:
        return None
    return percentile([1e3 * (r["sent"] - r["due"]) for r in s["requests"]],
                      rc.metric["params"]["q"])
