"""The paper's own deployment configuration (§5.1–5.2).

Regions, RTT matrix, bandwidth, batch sizes and request sizes used by the
WAN simulator (core/netsim.py) and the figure benchmarks.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Tuple, Union

import numpy as np

# 9 AWS regions of §5.1 (first 5 used for figs 6-8; up to 9 for fig 9).
REGIONS: Tuple[str, ...] = (
    "virginia", "ireland", "mumbai", "saopaulo", "tokyo",
    "oregon", "ohio", "singapore", "sydney",
)

# Public inter-region RTT estimates (ms). Symmetric; diagonal ~0.5ms.
# Source: cloudping-style public measurements, rounded.
_RTT_MS = np.array([
    #  vir   ire   mum   sao   tok   ore   ohi   sin   syd
    [   1,   75,  185,  115,  160,   60,   12,  215,  200],  # virginia
    [  75,    1,  120,  175,  210,  130,   85,  175,  260],  # ireland
    [ 185,  120,    1,  300,  125,  215,  195,   60,  220],  # mumbai
    [ 115,  175,  300,    1,  255,  175,  125,  325,  310],  # saopaulo
    [ 160,  210,  125,  255,    1,   95,  145,   70,  105],  # tokyo
    [  60,  130,  215,  175,   95,    1,   50,  165,  140],  # oregon
    [  12,   85,  195,  125,  145,   50,    1,  200,  190],  # ohio
    [ 215,  175,   60,  325,   70,  165,  200,    1,   90],  # singapore
    [ 200,  260,  220,  310,  105,  140,  190,   90,    1],  # sydney
    # lint: allow(dtype-hygiene): host-side RTT reference table kept in
    # f64 for exact ms arithmetic; netsim.build_env downcasts to f32 at
    # the device boundary
], dtype=np.float64)


def one_way_delay_ms(n: int) -> np.ndarray:
    """One-way delay matrix for the first n regions."""
    assert 3 <= n <= 9
    return _RTT_MS[:n, :n] / 2.0


@dataclass(frozen=True)
class SMRConfig:
    """§5.2 workload + per-protocol batching constants."""
    n_replicas: int = 5
    request_bytes: int = 16            # 8B key + 8B value
    client_batch: int = 100            # client-side batch size
    max_batch_ms: float = 5.0          # replica max batch time
    nic_gbps: float = 10.0             # c4.4xlarge "up to 10 Gbps"
    # per-request replica CPU cost (µs) — calibrated so Multi-Paxos lands at
    # its measured ~40k tx/s plateau (DESIGN.md §8); shared by all protocols.
    cpu_us_per_request: float = 3.0
    # replica-side batch sizes (requests) per §5.2
    batch_epaxos: int = 1000
    batch_paxos: int = 5000
    batch_rabia: int = 300
    batch_sporades: int = 2000
    batch_mandator: int = 2000
    # §4 child processes: parallel stateless dissemination lanes per replica.
    # Each lane pipelines one outstanding Mandator-batch (chain completion
    # stays strictly in round order).
    mandator_lanes: int = 4
    # consensus metadata message size (bytes) — vector clock for mandator-*
    meta_bytes: int = 128
    epaxos_conflict_rate: float = 0.03
    view_timeout_ms: float = 300.0     # sporades/paxos view-change timeout
    sim_seconds: float = 10.0
    tick_ms: float = 1.0
    # Delayed-delivery horizon (ring-buffer slots) of the simulated channels:
    # a message's total delay (link + DDoS + NIC backlog) is capped at
    # horizon-1 ticks. Per-tick channel cost is linear in the horizon, so
    # the default "auto" sizes it exactly per sweep: static link delay +
    # the scenario's max extra delay + a NIC-backlog bound, next power of
    # two (netsim.resolve_horizon). Pass an int to pin it (2048 was the
    # seed-era fixed size: worst §5.5 attack + ~1s queueing headroom).
    delay_horizon_ticks: Union[int, str] = "auto"
    # Packed-channel-ring commit backend (repro.kernels.channel_ring):
    # "auto" = Pallas kernel on TPU, pure-jnp oracle elsewhere; also
    # "jnp"/"ref", "pallas" (the compiled kernel, TPU only) and
    # "pallas-interpret" (the kernel interpreted, for parity tests).
    channel_backend: str = "auto"
    # Flight recorder (repro.obs): "off" (default — the compiled program
    # is instruction-identical to an untraced build), "counters"
    # (per-kind event counts only), or "full" (event rings + per-batch
    # phase marks). Static: each level is its own compiled program.
    trace_level: str = "off"
    # Event-ring capacity per replica per layer at trace_level="full";
    # overflow keeps the newest events and counts the dropped oldest.
    trace_events: int = 512
    # Consensus health monitor (repro.obs.monitor): "off" (default — the
    # compiled program is instruction-identical to an unmonitored build,
    # exactly like trace_level), "gauges" (resource gauges only: ring
    # occupancy, dropped sends, inflight high-water, starvation), or
    # "full" (gauges + on-device safety/liveness invariant checks).
    # Static: each level is its own compiled program.
    monitor_level: str = "off"
    # Commit-stall watchdog grace window (ms). 0 = derive per sweep from
    # the view timeout and the scenario's delay tables (scenario-aware:
    # a DDoS that slows every link widens the window it is judged by).
    monitor_stall_grace_ms: float = 0.0

    def delays_ms(self) -> np.ndarray:
        return one_way_delay_ms(self.n_replicas)

    @property
    def quorum(self) -> int:
        """n - f replicas, with f = (n - 1) // 2 faults tolerated: the
        votes that complete a Mandator round or a Sporades decision."""
        return self.n_replicas - (self.n_replicas - 1) // 2


PAPER_CLAIMS = {
    # headline numbers from the paper, used by EXPERIMENTS.md comparisons
    "mandator_sporades_tput": 300_000,   # tx/s, <900ms median, 5 replicas
    "mandator_paxos_tput": 300_000,
    "multipaxos_tput": 40_000,           # ~295ms median
    "epaxos_tput": 6_500,                # ~720ms median
    "rabia_tput": 500,                   # ~500ms median
    "ddos_mandator_sporades_tput": 400_000,  # under 5s median bound
    "ddos_mandator_paxos_tput": 250_000,
    "ddos_multipaxos_tput": 45_000,
    "ddos_epaxos_tput": 7_200,
    "scal_9_replicas_tput": 150_000,
}
