"""The trace reduction, on a probe recorded on one TPU v5e (the
``ms5.ddos-single`` cell, trimmed to the first and last 1,500 of its 1.1
million device operations) and on made-up events."""
import gzip
import json
from pathlib import Path

import pytest

from chip_bench import run, tracing

DATA = Path(__file__).parent / "data" / "probe_ddos.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as fh:
        return json.load(fh)


def test_recorded_probe_reduces_to_the_chip_run_numbers(recorded):
    s = tracing.summarize(recorded, run.is_kernel)
    # the numbers the chip run printed for this probe
    assert s["window_ns"] == 378217373.0
    dev = s["devices"]["0"]
    assert dev["busy_ns"] == 357622893.0       # the scan's outer loop
    assert dev["n_ops"] == 3000
    assert dev["kernel_ns"] == 154108.0        # 18 ring commits kept
    assert s["collect_end"] - dev["last_op_end"] == 9208102.0  # readback
    assert s["dispatch_ns"] == 12710270.0
    # the longest idle gap is the dispatch before the scan starts, the
    # next the readback after it ends
    assert [g[0] for g in s["gaps"][:2]] == ["dispatch", "collect"]
    assert s["gaps"][0][1] == pytest.approx(0.011383663)
    assert s["top_ops"][0][0] == "while.236"


def test_recorded_kernel_operations_are_the_ring_commits(recorded):
    names = [n for n, _, _ in recorded["devices"]["0"] if run.is_kernel(n)]
    assert names
    assert all('custom_call_target="tpu_custom_call"' in n for n in names)
    assert all(n.split(" = ")[1].startswith(("f32[1024,5,5,50]",
                                             "f32[1024,5,5,5]"))
               for n in names)


def test_allocations_are_not_the_kernel():
    assert not run.is_kernel('%custom-call.81 = s32[1,5]{1,0} custom-call(), '
                             'custom_call_target="AllocateBuffer"')


def test_union_merges_overlaps_and_clips():
    ev = [("a", 0.0, 10.0), ("b", 5.0, 20.0), ("c", 30.0, 40.0),
          ("d", 35.0, 36.0), ("e", 90.0, 200.0)]
    assert tracing.union(ev, 2.0, 100.0) == [(2.0, 20.0), (30.0, 40.0),
                                             (90.0, 100.0)]


def test_summarize_names_gaps_by_host_span():
    kernel = '%k = f32[8] custom-call(x), custom_call_target="tpu_custom_call"'
    data = {"devices": {"0": [(kernel, 20.0, 60.0),
                              ("%f = fusion(x)", 60.0, 80.0)]},
            "spans": [("bench.dispatch", 0.0, 10.0),
                      ("bench.collect", 10.0, 100.0)]}
    s = tracing.summarize(data, run.is_kernel)
    d = s["devices"]["0"]
    assert (s["window_ns"], d["busy_ns"], d["kernel_ns"]) == (100.0, 60.0,
                                                              40.0)
    assert sorted(g[1] for g in s["gaps"]) == pytest.approx([2e-8, 2e-8])
    assert {g[0] for g in s["gaps"]} == {"collect"}
    assert s["top_ops"][0] == ["k", pytest.approx(4e-8)]


def test_a_trace_without_spans_is_refused():
    with pytest.raises(RuntimeError):
        tracing.summarize({"devices": {}, "spans": []}, run.is_kernel)
