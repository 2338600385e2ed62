"""Milliseconds per point from the end of the probe's last device
operation to the end of its ``collect()``: readback to the host and
decode into result rows. Read where the probe is traced whole."""


def read(ctx):
    p = ctx["probe"]
    ends = [d["last_op_end"] for d in p["devices"].values()
            if d["last_op_end"] is not None]
    if not ctx["full"] or not ends or p["collect_end"] is None:
        return None
    return (p["collect_end"] - max(ends)) * 1e-6 / ctx["points"]
