"""A quorum fault that only shows at n != 5 turns ``correct`` false at
three and seven replicas, as it does at nine (``test_bench_n9.py``): with
f fixed at n = 5's two, the quorum is 1 of 3 instead of 2, and 5 of 7
instead of 4, in the Mandator round and the Sporades votes alike."""
import pytest

from bench_cells import add_cell, bench_root, run_cell, scenario
from repro.configs.smr import SMRConfig
from repro.core import experiment


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_root(tmp_path_factory.mktemp("bench_planted"))


@pytest.mark.no_persistent_cache
@pytest.mark.parametrize("n,wrong", [(3, 1), (7, 5)])
def test_a_quorum_with_f_fixed_at_two_is_not_correct(root, n, wrong,
                                                     monkeypatch):
    # each case builds its own program: a sound one is never reused
    monkeypatch.setattr(experiment, "_PROGRAMS", {})
    monkeypatch.setattr(SMRConfig, "quorum",
                        property(lambda self: self.n_replicas - 2))
    assert SMRConfig(n_replicas=n).quorum == wrong
    name = add_cell(root, "mandator-sporades", n, scenario("baseline", n))
    out = run_cell(root, name)
    assert not out["correct"], out["check"]
    assert out["check"]["state_gap"]["value"] > 0.0
