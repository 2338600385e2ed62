"""Share of the HBM roofline the ring-commit kernel reaches, in percent:
the least time its work could take at the chip's HBM bandwidth over its
measured time. The work is ``ringbytes.tick_bytes`` a tick, which counts
the bytes the commit must move, unpadded, whatever implements it; bound
by bytes, since the commit does no arithmetic worth counting. Read where
the probe is traced whole and holds the kernel."""


def read(ctx):
    k = sum(d["kernel_ns"] for d in ctx["probe"]["devices"].values())
    if not ctx["full"] or not k:
        return None
    work = ctx["ring_bytes_per_tick"] * ctx["ticks_per_point"] * ctx["points"]
    return 100.0 * work / ctx["peaks"]["hbm_bytes_per_s"] / (k * 1e-9)
