"""Five replicas under faults and bursty traffic against the plain
reference, on the single-chip path (one lane, no mesh) through the
benchmark's own path on the host CPU: a healing minority partition, a
throttled leader NIC, a leader crash, and on/off bursts, for both
protocols. The benchmark's single-chip cells run only the fault-free
grids and the DDoS point; the matrix with bursts runs sharded."""
import pytest

from bench_cells import (ONOFF, POISSON, add_cell, assert_matches_reference,
                         bench_root, run_cell, scenario)

CASES = [("symmetric-partition", POISSON), ("throttled-nic", POISSON),
         ("leader-crash", POISSON), ("baseline", ONOFF)]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_root(tmp_path_factory.mktemp("bench_faults_n5"))


@pytest.mark.parametrize("scen,workload", CASES,
                         ids=[f"{s}-{w['name']}" for s, w in CASES])
@pytest.mark.parametrize("protocol", ["mandator-sporades", "multipaxos"])
def test_point_matches_the_reference(root, protocol, scen, workload):
    name = add_cell(root, protocol, 5, scenario(scen, 5), workload)
    assert_matches_reference(run_cell(root, name), protocol)
