"""``serve_batch_occupancy``: mean occupied slots per decode step over the
slots, from the ``active`` count on the server's ``generate.decode_step``
spans."""


def read(rc):
    s = rc.samples
    if s.get("kind") != "serve":
        return None
    active = [(e.get("args") or {}).get("active") for e in s["program_spans"]
              if e["name"] == "generate.decode_step"]
    active = [a for a in active if a is not None]
    if not active:
        return None
    return 100.0 * sum(active) / len(active) / s["slots"]
