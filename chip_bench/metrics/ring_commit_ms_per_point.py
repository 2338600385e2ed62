"""Device milliseconds per point in the ring-commit kernel: the summed
durations of its operations in the probe, over the probe's points. Read
where the probe is traced whole and holds the kernel."""


def read(ctx):
    k = sum(d["kernel_ns"] for d in ctx["probe"]["devices"].values())
    if not ctx["full"] or not k:
        return None
    return k * 1e-6 / ctx["points"]
