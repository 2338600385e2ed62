"""Host milliseconds per point in ``experiment.dispatch_sweep`` (grid
lowering, program lookup and enqueue), over the window's grids."""


def read(ctx):
    host = ctx["host"]
    points = sum(p for _, _, p in host)
    if not points:
        return None
    return sum(d for d, _, _ in host) * 1e3 / points
