"""The control (the reference in bfloat16 put in the program's place)
comes out not correct, at test size."""
import pytest

from chip_bench import check, control
from chip_bench.tests.helpers import tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["tiny.ms", "tiny.mp"])
def test_bfloat16_control_fails_a_limit(root, cell):
    r = control.readings(cell, 2**31 + 7, root)
    lim = check.limits()
    assert any(v > lim[k] for k, v in r.items()), r
