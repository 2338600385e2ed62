"""Declarative client traffic — the other half of the scenario engine.

A ``Workload`` is a named list of composable traffic-shape primitives
(open-loop Poisson, on/off bursts, diurnal ramps, flash crowds,
WPaxos-style migrating region skew, Atlas-style closed-loop geo-placed
client pools). ``compile.lower`` turns one into fixed-shape windowed
per-origin rate tables that stack leaf-wise and ride through the batched
experiment engine (``experiment.SweepSpec.workloads``) as a third sweep
axis of ONE compiled program per protocol.

The bare ``PoissonOpen()`` workload compiles to the all-ones table and a
static fast path that broadcasts the scalar rate, whose draws are the
seed-era draws bit for bit, keeping the fig 6-9 artifacts byte-identical
(pinned by tests/test_workloads.py).
"""
from repro.workloads.compile import (
    TRIVIAL_MODE,
    WorkloadMode,
    as_workload,
    is_trivial,
    lower,
    mode_of,
)
from repro.workloads.primitives import (
    ClosedLoop,
    DiurnalRamp,
    FlashCrowd,
    OnOffBurst,
    PoissonOpen,
    RegionSkew,
    Workload,
)

__all__ = [
    "ClosedLoop", "DiurnalRamp", "FlashCrowd", "OnOffBurst", "PoissonOpen",
    "RegionSkew", "Workload", "WorkloadMode", "TRIVIAL_MODE",
    "as_workload", "compile", "is_trivial", "lower", "mode_of",
]
