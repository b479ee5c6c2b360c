"""Training traffic: a fixed set of seeded rows resident for the whole run
and fed in storage order.  The rows themselves come from the
configuration's reference module (``make_rows`` / ``make_tokens``), which
the reference shares; this file only checks that a traffic file's numbers
fit together."""


def plan(traffic: dict, batch: int) -> dict:
    rows = int(traffic.get("n_train", traffic.get("n_rows", 0)))
    if rows < 3 * batch or rows % batch:
        raise ValueError(f"{traffic['name']}: {rows} rows do not hold three "
                         f"whole steps of {batch}")
    return {"rows": rows, "steps_per_epoch": rows // batch,
            "k_steps": int(traffic["k_steps"])}
