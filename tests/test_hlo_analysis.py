"""Unit tests for ``repro.distributed.hlo_analysis`` on a fixture HLO
module: the loop-aware cost model, the async
``-start``/``-done`` opcode handling, parser hardening (tuple results,
nested tuples, fusion calls, while bodies with ``known_trip_count``),
and the program-audit queries behind ``repro.analysis.hlo_lint``.

The fixture is a hand-written module with one scan-shaped while loop
(trip count 10) containing an async all-gather and a python-callback
custom-call, plus entry-level dots (one direct, one fused), an f64
convert, and a host-buffer custom-call OUTSIDE the loop — so every
audit query has both a positive and a negative case. The last two tests
check the cost model on a compiled ``lax.scan`` and the collective parser
on a hand-written module.
"""
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.distributed import hlo_analysis as hlo

FIXTURE = (Path(__file__).parent / "fixtures" / "hlo" /
           "audit_fixture.hlo").read_text()

# fixture constants
TRIPS = 10
AG_BYTES = 128 * 4            # f32[128] all-gather result
DOT_FLOPS = 2 * (8 * 32) * 16  # f32[8,32] dot with K=16


def test_parse_module_structure():
    comps, entry = hlo._parse_module(FIXTURE)
    assert entry == "main"
    assert set(comps) == {"fused_dot", "body", "cond", "main"}
    names = {op.name: op for op in comps["main"]}
    # tuple-shaped results parse (while carry + a nested tuple)
    assert names["w"].opcode == "while"
    assert names["w"].shape == "(s32[], f32[64])"
    assert names["nt"].opcode == "tuple"
    assert names["nt"].shape == "((f32[2], s32[]), f32[4])"
    # while op exposes both computations as callees
    assert set(names["w"].callees()) == {"cond", "body"}
    # fusion call target
    assert names["fu"].callees() == ["fused_dot"]


def test_base_opcode_strips_async_suffix_only():
    # str.rstrip("-start") strips a CHARACTER SET and would eat
    # "all-gather-start" down to "all-gathe" — the old bug this pins
    assert hlo._base_opcode("all-gather-start") == "all-gather"
    assert hlo._base_opcode("all-gather-done") == "all-gather"
    assert hlo._base_opcode("reduce-scatter-start") == "reduce-scatter"
    assert hlo._base_opcode("all-reduce") == "all-reduce"
    assert hlo._base_opcode("all-to-all") == "all-to-all"


def test_dot_flops():
    comps, _ = hlo._parse_module(FIXTURE)
    shapes = {op.name: op.shape
              for ops in comps.values() for op in ops}
    (dot,) = [op for op in comps["main"] if op.opcode == "dot"]
    assert hlo._dot_flops(dot, shapes) == DOT_FLOPS


def test_module_cost_counts_fused_and_direct_dots():
    cost = hlo.module_cost(FIXTURE)
    # entry dot + the dot inside the fusion body; loop has no dots
    assert cost["flops"] == 2 * DOT_FLOPS
    assert cost["bytes"] > 0


def test_collective_stats_are_loop_scaled():
    stats = hlo.collective_stats(FIXTURE)
    # the async all-gather runs once per trip; -done must not double it
    # (and must not vanish, as under the rstrip bug)
    assert stats["all-gather"]["count"] == TRIPS
    assert stats["all-gather"]["bytes"] == AG_BYTES * TRIPS
    for kind in ("all-reduce", "reduce-scatter", "all-to-all",
                 "collective-permute"):
        assert stats[kind]["count"] == 0


def test_dtype_op_counts():
    counts = hlo.dtype_op_counts(FIXTURE)
    assert counts["f64"] == 1          # the convert — H1's positive case
    assert counts["f32"] > 10
    assert "bf16" not in counts


def test_while_stats():
    (w,) = hlo.while_stats(FIXTURE)
    assert w["comp"] == "main" and w["outer"] is True
    assert w["body"] == "body" and w["trip_count"] == TRIPS


def test_loop_computations():
    assert hlo.loop_computations(FIXTURE) == {"cond", "body"}


def test_host_transfer_ops_tag_loop_membership():
    ops = {t["name"]: t for t in hlo.host_transfer_ops(FIXTURE)}
    assert set(ops) == {"cb", "hcb"}
    assert ops["cb"]["in_loop"] is True      # H2's positive case
    assert ops["hcb"]["in_loop"] is False    # post-scan host pull: legal


def test_hlo_cost_model_counts_scan_trips():
    from repro.distributed.hlo_analysis import module_cost

    def f(a, b):
        def body(x, _):
            return x @ b, None
        out, _ = jax.lax.scan(body, a, None, length=10)
        return out

    s = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    txt = jax.jit(f).lower(s, s).compile().as_text()
    mc = module_cost(txt)
    expect = 10 * 2 * 256 ** 3
    assert abs(mc["flops"] - expect) / expect < 0.05


def test_hlo_collective_parsing_fixture():
    from repro.distributed.hlo_analysis import collective_stats
    fake = """
HloModule m

ENTRY %main (p0: f32[16,128]) -> f32[16,128] {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ar = f32[16,128]{1,0} all-reduce(%p0), to_apply=%add
  ROOT %ag = f32[32,128]{1,0} all-gather(%ar), dimensions={0}
}
"""
    st_ = collective_stats(fake)
    assert st_["all-reduce"]["count"] == 1
    assert st_["all-reduce"]["bytes"] == 16 * 128 * 4
    assert st_["all-gather"]["bytes"] == 32 * 128 * 4
