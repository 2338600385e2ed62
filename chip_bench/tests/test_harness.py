"""The harness end to end on the host CPU, at small sizes: a cell added as
new files is found by name and proves correct; with the timed path broken
underneath, ``correct`` comes out false; without a chip there is no
result."""
import json
import os
import subprocess
import sys

import pytest

from chip_bench import run
from chip_bench.tests.helpers import ROOT, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def fresh_programs(monkeypatch):
    """Each test traces its own program (a broken one must not be reused,
    nor reuse a sound one)."""
    from repro.core import experiment
    monkeypatch.setattr(experiment, "_PROGRAMS", {})
    monkeypatch.setattr(experiment, "_SHARDED", {})


def _run(root, cell, seed=2**31 + 12345):
    return run.run(cell, seed, 0.01, False, root=root, require_chip=False)


@pytest.mark.parametrize("cell", ["tiny.ms", "tiny.mp"])
def test_a_cell_added_as_files_runs_and_proves_correct(root, cell,
                                                       fresh_programs):
    out = _run(root, cell)
    assert out["correct"], out["check"]
    assert out["attempted"] == 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"points_per_s", "setup_s"}
    assert out["check"]["count_gap"]["value"] < 1e-6
    assert out["check"]["latency_gap"]["value"] < 1e-6
    assert list(out["check"]) == sorted(out["check"])


def _state_unchanged(monkeypatch):
    from repro.core import paxos, sporades
    monkeypatch.setattr(sporades, "tick", lambda st, *a, **k: st)
    monkeypatch.setattr(paxos, "tick", lambda st, *a, **k: st)


def _half_the_grid(monkeypatch):
    # the second half of the grid's results are copies of the first half's
    from repro.core import experiment
    orig = experiment.PendingSweep.collect

    def collect(self):
        rows = orig(self)
        half = len(rows) // 2
        return rows[:len(rows) - half] + [dict(r) for r in rows[:half]]

    monkeypatch.setattr(experiment.PendingSweep, "collect", collect)


def _answer_altered(monkeypatch):
    # one batch's requests counted twice where the point's metrics are
    # produced
    from repro.core import harness
    orig = harness._batch_metrics

    def metrics(cfg, create_t, arr_mean, count, commit_t, *a, **k):
        return orig(cfg, create_t, arr_mean, count.at[0, 1].multiply(2.0),
                    commit_t, *a, **k)

    monkeypatch.setattr(harness, "_batch_metrics", metrics)


@pytest.mark.parametrize("cell", ["tiny.ms", "tiny.mp"])
@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_grid,
                                   _answer_altered])
def test_a_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch,
                                            fresh_programs):
    fault(monkeypatch)
    out = _run(root, cell)
    assert not out["correct"], out["check"]


_CHIPS = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from pathlib import Path
from chip_bench import run
from repro.core import experiment
if {broken}:
    # the exchange between chips left out: every row holds what the
    # first device computed for its own points
    orig = experiment.PendingSweep.collect
    def collect(self):
        where = self.point_devices()
        rows = orig(self)
        first = [r for r, d in zip(rows, where) if d == where[0]]
        return [rows[i] if where[i] == where[0]
                else dict(first[i % len(first)]) for i in range(len(rows))]
    experiment.PendingSweep.collect = collect
out = run.run("tiny.matrix", 2**31 + 99, 0.01, False, root=Path({tmp!r}),
              require_chip=False)
print(json.dumps(out))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_sharded_cell_over_four_devices(root, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_COMPILE_CACHE="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _CHIPS.format(root=str(ROOT), src=str(ROOT / "src"),
                         tmp=str(root), broken=broken)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4 and out["attempted"] == 8
    assert out["correct"] is (not broken), out["check"]


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(ROOT / "chip_bench" / "run.py"), "--workload",
         "ms5.fig6-seeds", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 3
    assert res.stdout == ""
    assert "no TPU" in res.stderr
