"""A percentile of one of the server's own spans, in ms:
``params.span`` (``generate.prefill``, ``generate.decode_step``),
``params.q``."""

from benchlib import percentile


def read(rc):
    s = rc.samples
    if s.get("kind") != "serve":
        return None
    p = rc.metric["params"]
    vals = [e["dur"] / 1e3 for e in s["program_spans"]
            if e["name"] == p["span"]]
    return percentile(vals, p["q"]) if vals else None
