"""Share of the probe's window in which a chip runs no operation, in
percent: the highest over the chips. The window runs from the probe's
dispatch to the end of its collect (or of its sample)."""


def read(ctx):
    p = ctx["probe"]
    if not p["devices"] or p["window_ns"] <= 0:
        return None
    return 100.0 * max(1.0 - d["busy_ns"] / p["window_ns"]
                       for d in p["devices"].values())
