"""The per-layer reduction (``chip_bench/scopes.py``) on a probe recorded
on one TPU v5e with the program's scopes (the ``ms5.fig6-seeds`` cell,
trimmed to the first and last 1,500 of its 1.1 million device operations,
with the ``op_scopes`` of those), on made-up events and HLO text, and the
whole probe on the host CPU at a small size."""
import gzip
import json
from pathlib import Path

import pytest

from chip_bench import run, scopes, tracing

DATA = Path(__file__).parent / "data" / "probe_fig6_scopes.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as fh:
        return json.load(fh)


def test_recorded_scoped_probe_reduces_to_the_chip_run_numbers(recorded):
    red = scopes.reduce_probe(recorded, 1, run.is_kernel)
    # the numbers the chip run printed for what it kept
    assert red["window_ns"] == 340025790.0
    assert red["busy_ns"] == 322941442.0          # the scan's outer loop
    assert red["kernel_ns"] == 38840.0
    assert red["parts_ns"] == {
        "arrivals": 184780.0, "ring_deliver": 21528.0,
        "ring_commit": 179121.0, "mandator": 57341.0, "sporades": 124471.0,
        "paxos": 0.0, "extract": 2644434.0, "unscoped": 319729767.0}
    assert sum(red["parts_ns"].values()) == red["busy_ns"]
    assert red["transfer_ms_per_point"] == pytest.approx(8.052947)
    assert red["readback_ms_per_point"] == pytest.approx(8.178278)
    # the head gap is the grid's lowering, the tail gap its readback
    assert [g[0] for g in red["gaps"][:2]] == ["experiment.lower",
                                               "experiment.readback"]
    assert red["gaps"][0][1] == pytest.approx(0.00890265)


def test_recorded_ring_commit_kernel_lies_under_its_scope(recorded):
    kernels = [scopes._instr(n) for n, _, _ in recorded["devices"]["0"]
               if run.is_kernel(n)]
    assert kernels
    assert {scopes.layer_of(recorded["op_scopes"][k]) for k in kernels} \
        == {("ring_commit", 2)}
    layers = {scopes.layer_of(v)[0] for v in recorded["op_scopes"].values()}
    assert layers == set(scopes.PARTS) - {"paxos"}

HLO = '''\
ENTRY %main.7 (x.1: f32[1000]) -> f32[] {
  %broadcast_multiply_fusion = f32[1000]{0} fusion(%x.1), kind=kLoop, \
calls=%fused_computation.2, metadata={op_name="jit(f)/arrivals/mul" \
stack_frame_id=3}
  %while.6 = (s32[], f32[1000]{0}) while(%tuple.3), condition=%cond, \
body=%body, metadata={op_name="jit(f)/while"}
  %copy.4 = f32[1000]{0} copy(%x.1)
  ROOT %reduce_add_fusion = f32[] fusion(%while.6), kind=kLoop, \
calls=%fused_computation.1, metadata={op_name="jit(f)/vmap(extract)/add"}
}
'''


def test_hlo_op_scopes_reads_each_instructions_op_name():
    assert scopes.hlo_op_scopes(HLO) == {
        "broadcast_multiply_fusion": "jit(f)/arrivals/mul",
        "while.6": "jit(f)/while",
        "reduce_add_fusion": "jit(f)/vmap(extract)/add"}


@pytest.mark.parametrize("op_name, layer", [
    ("jit(call)/call_exported/jit(<unknown>)/vmap()/while/body/closed_call"
     "/mandator/arrivals/jit(_poisson)/while", ("arrivals", 2)),
    ("jit(f)/while/body/closed_call/sporades/ring_commit/pallas_call",
     ("ring_commit", 2)),
    ("jit(f)/while/body/closed_call/paxos/ge", ("paxos", 1)),
    ("jit(f)/vmap(extract)/vmap(jit(searchsorted))/while", ("extract", 1)),
    ("jit(f)/while/body/closed_call/random_fold_in", ("unscoped", 0)),
    ("jit(f)/jit(floor_divide)/rem", ("unscoped", 0)),
    ("", ("unscoped", 0)),
])
def test_layer_of_takes_the_deepest_layer_scope(op_name, layer):
    assert scopes.layer_of(op_name) == layer


OPS = {"scan": "jit(f)/while",
       "m_call": "jit(f)/while/body/closed_call/mandator",
       "m_fus": "jit(f)/while/body/closed_call/mandator/add",
       "arr_while": "jit(f)/while/body/closed_call/mandator/arrivals/while",
       "arr_fus": "jit(f)/while/body/closed_call/mandator/arrivals/mul",
       "s_fus": "jit(f)/while/body/closed_call/sporades/and",
       "commit": "jit(f)/while/body/closed_call/sporades/ring_commit/cc",
       "ext": "jit(f)/vmap(extract)/sort"}


def _ev(name, s, e):
    return (f"%{name} = f32[1] fusion()", float(s), float(e))


def _data(events, **extra):
    return {"devices": {"0": events}, "spans": [], "op_scopes": OPS,
            **extra}


def test_partition_gives_each_instant_to_the_deepest_layer():
    events = [
        _ev("scan", 0, 100),           # the scan's loop: unscoped
        _ev("m_call", 10, 40),         # a container in mandator
        _ev("arr_while", 12, 30),      # arrivals inside mandator
        _ev("arr_fus", 14, 20),
        _ev("copy.9", 20, 24),         # no metadata, inside arrivals
        _ev("m_fus", 32, 38),
        _ev("s_fus", 50, 70),          # sporades, overlapped by
        _ev("commit", 60, 80),         # its ring commit (deeper)
        _ev("xla.1", 85, 90),          # no metadata, inside the scan
        _ev("ext", 110, 130),          # extraction after the scan
        _ev("ext", 140, 250),          # clipped at hi
    ]
    part = scopes.scope_partition(_data(events), 0.0, 200.0)["0"]
    assert part == {"arrivals": 18.0, "ring_deliver": 0.0,
                    "ring_commit": 20.0, "mandator": 12.0, "sporades": 10.0,
                    "paxos": 0.0, "extract": 80.0, "unscoped": 40.0}
    busy = tracing.union(events, 0.0, 200.0)
    assert sum(part.values()) == sum(e - s for s, e in busy) == 200.0 - 20.0


def test_partition_ties_and_unknown_names():
    # two overlapping ops at the same depth: the earlier layer in PARTS
    events = [_ev("m_fus", 0, 10), _ev("s_fus", 5, 15), _ev("other", 20, 30)]
    part = scopes.scope_partition(_data(events), 0.0, 40.0)["0"]
    assert (part["mandator"], part["sporades"], part["unscoped"]) == (
        10.0, 5.0, 10.0)
    # without op_scopes every operation is unscoped, and still counted
    bare = scopes.scope_partition({"devices": {"0": events}}, 0.0, 40.0)
    assert bare["0"]["unscoped"] == 25.0
    assert sum(bare["0"].values()) == 25.0


def test_partition_sums_to_busy_time_on_many_nested_events():
    import random
    rng = random.Random(5)
    names = list(OPS) + ["plain.1"]
    events = []
    for _ in range(3000):
        s = rng.randrange(0, 10_000)
        events.append(_ev(rng.choice(names), s, s + rng.randrange(1, 300)))
    part = scopes.scope_partition(_data(events), 500.0, 9_000.0)["0"]
    busy = tracing.union(events, 500.0, 9_000.0)
    assert sum(part.values()) == sum(e - s for s, e in busy)


def test_gaps_are_named_by_the_innermost_span():
    spans = [("bench.dispatch", 0.0, 40.0),
             ("experiment.lower", 1.0, 20.0),
             ("experiment.enqueue", 20.0, 39.0),
             ("bench.collect", 40.0, 100.0),
             ("experiment.readback", 41.0, 90.0),
             ("experiment.decode", 90.0, 99.0)]
    assert scopes.span_at(spans, 10.0) == "experiment.lower"
    assert scopes.span_at(spans, 39.5) == "dispatch"
    assert scopes.span_at(spans, 95.0) == "experiment.decode"
    assert scopes.span_at(spans, 120.0) == "host"
    data = {"devices": {"0": [_ev("scan", 30.0, 85.0)]}, "spans": spans}
    gaps = scopes.idle_gaps(data, 0.0, 100.0)
    assert gaps == [("experiment.lower", pytest.approx(30e-9)),
                    ("experiment.decode", pytest.approx(15e-9))]


def test_gaps_without_program_spans_are_named_as_tracing_names_them():
    kernel = '%k = f32[8] custom-call(x), custom_call_target="tpu_custom_call"'
    data = {"devices": {"0": [(kernel, 20.0, 60.0),
                              ("%f = fusion(x)", 60.0, 80.0)]},
            "spans": [("bench.dispatch", 0.0, 10.0),
                      ("bench.collect", 10.0, 100.0)]}
    old = tracing.summarize(data, lambda n: False)["gaps"]
    assert sorted(scopes.idle_gaps(data, 0.0, 100.0)) == sorted(old)


def test_probe_runs_on_the_host_cpu(tmp_path):
    """The command's path end to end on a small Multi-Paxos cell: every
    host span once a grid, nothing compiled in the grids or the probe,
    and the program's HLO names each layer Multi-Paxos runs."""
    from chip_bench.tests.helpers import tiny_root
    root = tiny_root(tmp_path, sim_seconds=0.2)
    out = scopes.probe("tiny.mp", 2**31 + 77, grids=2, root=root,
                       require_chip=False)
    assert out["compiles"] == 0 and out["whole"]
    assert out["host"]["spans_per_grid"] == dict.fromkeys(
        scopes.HOST_SPANS, 1.0)
    assert all(out["host"][f"{k}_ms_per_point"] > 0
               for k in scopes.HOST_SPANS)
    assert out["layers_in_program"] == sorted(
        ["arrivals", "ring_deliver", "ring_commit", "paxos", "extract",
         "unscoped"])


def test_top_unscoped_leaves_out_the_containers():
    events = [_ev("while.1", 0, 100), _ev("fold.3", 10, 30),
              _ev("fold.3", 40, 45), _ev("copy.2", 50, 60),
              _ev("m_fus", 60, 90)]
    data = {"devices": {"0": events}, "op_scopes": {"m_fus": OPS["m_fus"]}}
    assert scopes.top_unscoped(data, 0.0, 100.0) == [
        ("fold.3", pytest.approx(25e-9)), ("copy.2", pytest.approx(10e-9))]
