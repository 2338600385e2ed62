"""HLO cost and roofline blocks of the simulator's programs: the packed
channel loop the ``channel`` suite times, and the canonical one-lane sweep
program of the ``scaling`` suite. Hardware constants: TPU v5e — 197
TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI
(distributed/hlo_analysis.py).
"""
from __future__ import annotations


def channel_hlo_block(dmax: int = 256, ticks: int = 200) -> dict:
    """HLO cost + roofline terms of the packed-channel tick loop — the
    exact program the ``channel`` microbench times. Lowered and compiled
    in-process, the optimized HLO goes through the loop-aware
    ``distributed/hlo_analysis.module_cost`` walker; XLA's own flat
    ``cost_analysis()`` rides along as a cross-check (it counts the scan
    body once, so its flops read ~``ticks``x low by design).
    benchmarks/run.py drops this block into the channel suite's
    BENCH_core.json entry."""
    import jax

    from benchmarks.bench_kernels import packed_loop_fn
    from repro.distributed import hlo_analysis as ha

    compiled = jax.jit(packed_loop_fn(dmax=dmax, ticks=ticks)
                       ).lower().compile()
    cost = ha.module_cost(compiled.as_text())
    terms = ha.roofline_terms(cost["flops"], cost["bytes"],
                              cost["collective_bytes"])
    block = {
        "dmax": dmax, "ticks": ticks,
        "flops": float(cost["flops"]),
        "hbm_bytes": float(cost["bytes"]),
        "collective_bytes": float(cost["collective_bytes"]),
        "compute_s": terms["compute_s"],
        "memory_s": terms["memory_s"],
        "collective_s": terms["collective_s"],
        "dominant": terms["dominant"],
        "bound_s": terms["bound_s"],
    }
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        block["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception:  # noqa: BLE001 — backend-dependent API, optional
        pass
    return block


def sweep_hlo_block(sim_seconds: float = 0.25,
                    protocol: str = "mandator-sporades") -> dict:
    """Where does the packed ring kernel sit now that run time matters?
    Lower the canonical single-lane sweep program (the unit of work every
    grid point executes, sharded or not) and attribute its HBM traffic by
    opcode with the loop-aware ``distributed/hlo_analysis.opcode_cost``
    walker. The packed channel ring shows up as the
    ``dynamic-update-slice`` scatter; its byte share is the headline
    number. benchmarks/run.py drops this block into the scaling suite's
    BENCH_core.json entry."""
    from functools import partial

    import jax

    from repro.configs.smr import SMRConfig
    from repro.core import experiment
    from repro.distributed import hlo_analysis as ha

    cfg = SMRConfig(sim_seconds=sim_seconds)
    spec = experiment.SweepSpec(rates=(200_000.0,))
    _, cfg, mode, env_b, wl_b, rate_b, seed_b, sig = experiment._lower(
        cfg, spec)
    fn = partial(experiment._sweep_body, protocol, cfg, mode)
    compiled = jax.jit(fn).lower(env_b, wl_b, rate_b, seed_b).compile()
    hlo = compiled.as_text()
    cost = ha.module_cost(hlo)
    ops = ha.opcode_cost(hlo)
    total = sum(d["bytes"] for d in ops.values()) or 1.0
    ring = ops.get("dynamic-update-slice", {"count": 0.0, "bytes": 0.0})
    top = sorted(ops.items(), key=lambda kv: -kv[1]["bytes"])[:8]
    return {
        "protocol": protocol, "signature": repr(sig),
        "sim_seconds": sim_seconds,
        "hbm_bytes": float(cost["bytes"]),
        "flops": float(cost["flops"]),
        "ring_scatter": {"count": float(ring["count"]),
                         "bytes": float(ring["bytes"]),
                         "byte_share": round(ring["bytes"] / total, 4)},
        "top_opcodes": [{"opcode": k, "count": float(d["count"]),
                         "bytes": float(d["bytes"]),
                         "byte_share": round(d["bytes"] / total, 4)}
                        for k, d in top],
    }
