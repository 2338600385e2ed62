"""The ring-commit kernel's share of device time in the probe, in
percent: its operations' summed durations over the union of all
operations, over the chips. Read in every cell, sampled or whole."""


def read(ctx):
    devs = ctx["probe"]["devices"].values()
    k = sum(d["kernel_ns"] for d in devs)
    busy = sum(d["busy_ns"] for d in devs)
    if not k or not busy:
        return None
    return 100.0 * k / busy
