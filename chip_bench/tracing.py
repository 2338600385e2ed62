"""From a profiler trace to the per-layer numbers.

A traced run ends with one traced probe of the cell's program (see
``run.py``): every device operation of it (the ``XLA Ops`` line of each
TPU plane) and the benchmark's own host spans (``bench.dispatch``,
``bench.collect``, ``bench.sample``) on the profiler's clock. A point's
scan is about a million operations, so a probe is one point, or the
first stretch of a sharded grid.

``extract`` turns the ``.xplane.pb`` into plain data (names and
nanoseconds); ``summarize`` reduces it over the probe's window, from the
first dispatch to the end of the last collect or sample span: each
device's busy time (the union of its operations), its kernel time, the
end of its last operation, the idle gaps named by the host span they fall
in, and the top operations by summed time.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def extract(xplane_path: str, line: str) -> dict:
    """{"devices": {id: [event, ...]}, "spans": [event, ...]}: the events
    of ``line`` on every TPU plane, and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[m.group(1)] = [
                (e.name, float(e.start_ns), float(e.end_ns))
                for ln in plane.lines if ln.name == line
                for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.end_ns))
                          for e in ln.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def union(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged busy intervals of ``events``, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in events
                if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def spans(data: dict, name: str) -> List[Event]:
    return [s for s in data["spans"] if s[0] == SPAN_PREFIX + name]


def _span_at(data: dict, t: float) -> str:
    for name, s, e in data["spans"]:
        if s <= t < e:
            return name[len(SPAN_PREFIX):]
    return "host"


def summarize(data: dict, is_kernel) -> dict:
    disp = spans(data, "dispatch")
    ends = spans(data, "collect") + spans(data, "sample")
    if not disp or not ends:
        raise RuntimeError("the trace holds no dispatch span or no "
                           "collect or sample span")
    lo, hi = disp[0][1], max(e for _, _, e in ends)
    per_dev: Dict[str, dict] = {}
    gaps: List[Tuple[str, float]] = []
    total: Dict[str, float] = {}
    for dev, events in sorted(data["devices"].items()):
        busy = union(events, lo, hi)
        inside = [(n, s, e) for n, s, e in events if e > lo and s < hi]
        per_dev[dev] = {
            "busy_ns": sum(e - s for s, e in busy),
            "kernel_ns": sum(e - s for n, s, e in inside if is_kernel(n)),
            "last_op_end": max((e for _, _, e in inside), default=None),
            "n_ops": len(events)}
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(data, (a + b) / 2), (b - a) * 1e-9))
        for n, s, e in inside:
            key = n.split(" = ")[0].lstrip("%")
            total[key] = total.get(key, 0.0) + (e - s) * 1e-9
    coll = spans(data, "collect")
    top = sorted(total.items(), key=lambda kv: -kv[1])[:10]
    return {"window_ns": hi - lo, "devices": per_dev,
            "dispatch_ns": sum(e - s for _, s, e in disp),
            "collect_end": coll[-1][2] if coll else None,
            "gaps": sorted(gaps, key=lambda g: -g[1])[:10],
            "top_ops": [[k, v] for k, v in top]}
