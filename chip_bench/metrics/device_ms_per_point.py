"""Device milliseconds per point: the union of the probe's operation
intervals, summed over the chips, over its points. Read where the probe
is traced whole."""


def read(ctx):
    busy = sum(d["busy_ns"] for d in ctx["probe"]["devices"].values())
    if not ctx["full"] or not busy:
        return None
    return busy * 1e-6 / ctx["points"]
