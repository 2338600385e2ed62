"""The ring-commit byte count: pinned at n = 5, held to the program's ring
layouts, and the same whatever the ring's horizon."""
import pytest

from chip_bench import ringbytes


def test_tick_bytes_pinned():
    # Mandator ring: vote 700 + batch 1000 + clear 500; Sporades ring:
    # 2 x vote 2500 + prop 4000 + to 2500 + 2 x pa 2200 + va 1900 +
    # ac 2500 + clear 5000; Multi-Paxos ring: fw 1000 + acc 2800 +
    # ack 700 + clear 1400 (bytes a tick)
    assert ringbytes.tick_bytes("mandator-sporades", 5) == 2200 + 25300
    assert ringbytes.tick_bytes("multipaxos", 5) == 5900


@pytest.mark.parametrize("protocol,d,want", [
    ("mandator-sporades", 256, 20971520),
    ("mandator-sporades", 1024, 83886080),
    ("multipaxos", 256, 10485760),
    ("multipaxos", 1024, 41943040),
])
def test_dense_pass_bytes_pinned(protocol, d, want):
    # read + write of every slot, [5, 5, K] padded to [5, 8, 128] f32
    assert ringbytes.dense_pass_bytes(protocol, 5, d) == want


def test_layouts_match_the_program():
    from repro.core import mandator, paxos, sporades
    program = {"mandator": mandator.ring_spec(),
               "sporades": sporades.ring_spec(5),
               "paxos": paxos.ring_spec(5, False)}
    mine = {**ringbytes.rings("mandator-sporades", 5),
            **ringbytes.rings("multipaxos", 5)}
    for ring, spec in program.items():
        assert [(c.name, c.width, c.additive) for c in spec.channels] \
            == mine[ring]
        assert spec.k == ringbytes.ring_k(mine[ring])
        assert set(ringbytes.SENDS[ring]) <= {c.name for c in spec.channels}


def test_unknown_protocol_is_refused():
    with pytest.raises(ValueError):
        ringbytes.tick_bytes("epaxos", 5)
