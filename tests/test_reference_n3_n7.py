"""Three and seven replicas against the plain reference, through the
benchmark's own path on the host CPU: both protocols, fault-free and under
the paper's DDoS, a healing minority partition, a throttled leader NIC and
a leader crash. No cell of the benchmark runs these cluster sizes; each
case is a one-point cell written into a throwaway copy of the benchmark
(``bench_cells``)."""
import pytest

from bench_cells import (add_cell, assert_matches_reference, bench_root,
                         run_cell, scenario)

SCENARIOS = ("baseline", "paper-ddos", "symmetric-partition",
             "throttled-nic", "leader-crash")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_root(tmp_path_factory.mktemp("bench_n3_n7"))


@pytest.mark.parametrize("scen", SCENARIOS)
@pytest.mark.parametrize("protocol", ["mandator-sporades", "multipaxos"])
@pytest.mark.parametrize("n", [3, 7])
def test_point_matches_the_reference(root, n, protocol, scen):
    name = add_cell(root, protocol, n, scenario(scen, n))
    assert_matches_reference(run_cell(root, name), protocol)
