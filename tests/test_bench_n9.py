"""The nine-region deployment (fig 9, n = 9) through the benchmark's
normal path on the host CPU: the shipped configuration is the program's,
one point of the ``ms9.fig9-single`` cell proves correct against the plain
reference, and a fault that only shows at n != 5 turns ``correct`` false.

The benchmark runs on a copy of ``BENCHMARK.json`` and ``chip_bench/``
whose n = 9 configuration simulates 1 s a point instead of 4."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chip_bench import cell as cellmod  # noqa: E402
from chip_bench import run  # noqa: E402
from chip_bench.tests.helpers import tiny_root  # noqa: E402
from repro.configs.smr import SMRConfig  # noqa: E402
from repro.core import experiment  # noqa: E402
from repro.core.experiment import SweepSpec  # noqa: E402

CELL = "ms9.fig9-single"
CONFIG = "mandator-sporades.n9"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny_root(tmp_path_factory.mktemp("bench_n9"))
    path = tmp / "chip_bench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["smr"]["sim_seconds"] = 1.0
    path.write_text(json.dumps(cfg))
    return tmp


@pytest.fixture
def fresh_programs(monkeypatch):
    """Each test builds its own program, so a broken one is never reused
    nor a sound one reused by a broken test."""
    monkeypatch.setattr(experiment, "_PROGRAMS", {})


def _run(root):
    return run.run(CELL, 2**31 + 4321, 0.01, False, root=root,
                   require_chip=False)


def test_shipped_n9_configuration_is_the_programs():
    """The shipped file's RTT table, regions and settings are the ones
    the program simulates at n = 9."""
    from repro.configs.smr import REGIONS
    cell = cellmod.load(CELL)
    assert cell.config["regions"] == list(REGIONS)
    cfg = cellmod.smr_config(cell)
    assert cfg.n_replicas == 9 and cfg.quorum == 5
    assert cfg.sim_seconds == 4.0


def test_an_n9_table_the_program_does_not_use_is_refused():
    cell = cellmod.load(CELL)
    cell.config["rtt_ms"][8][7] += 10
    with pytest.raises(SystemExit, match="one-way delays differ"):
        cellmod.smr_config(cell)


def test_n9_point_proves_correct(root, fresh_programs):
    out = _run(root)
    assert out["correct"], out["check"]
    assert out["attempted"] == 1 and out["failed"] == 0
    assert out["check"]["state_gap"]["value"] == 0.0
    assert out["check"]["count_gap"]["value"] < 1e-6
    assert out["check"]["latency_gap"]["value"] < 1e-6
    assert "horizon_gap" not in out["check"]


@pytest.mark.no_persistent_cache
def test_a_quorum_with_f_fixed_at_two_is_not_correct(root, fresh_programs,
                                                     monkeypatch):
    """f = 2 is n = 5's: at n = 9 the quorum becomes 7 of 9 instead of 5,
    in the Mandator round and the Sporades votes alike."""
    monkeypatch.setattr(SMRConfig, "quorum",
                        property(lambda self: self.n_replicas - 2))
    out = _run(root)
    assert not out["correct"], out["check"]
    assert out["check"]["state_gap"]["value"] > 0.0


def test_dispatches_are_counted_by_program_shape():
    """Two programs in one process: ``by_shape`` keeps them apart, while
    ``dispatches`` and ``horizon`` keep their meaning."""
    experiment.reset_timing_stats()
    spec = SweepSpec(rates=(150_000,))
    for n, times in ((5, 2), (9, 1)):
        for _ in range(times):
            experiment.run_sweep("mandator-sporades",
                                 SMRConfig(n_replicas=n, sim_seconds=1.0),
                                 spec)
    st = experiment.timing_stats()["mandator-sporades"]
    assert st["by_shape"] == {"n5.d256": 2, "n9.d256": 1}
    assert st["dispatches"] == 3 and st["horizon"] == 256
    st["by_shape"]["n5.d256"] = 0
    assert experiment.timing_stats()["mandator-sporades"]["by_shape"][
        "n5.d256"] == 2
