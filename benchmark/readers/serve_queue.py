"""``serve_queue_ms_p95``: from the instant a request was due to the
instant the batcher took it out of its queue: how late it left the
generator plus the server's own ``generate.queue`` span."""

from benchlib import percentile


def read(rc):
    s = rc.samples
    if s.get("kind") != "serve":
        return None
    queue = {(e.get("args") or {}).get("rid"): e["dur"] / 1e3
             for e in s["program_spans"] if e["name"] == "generate.queue"}
    vals = [1e3 * (r["sent"] - r["due"]) + queue[r["rid"]]
            for r in s["requests"] if r["rid"] in queue]
    return percentile(vals, rc.metric["params"]["q"]) if vals else None
