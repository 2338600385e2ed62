"""Bytes that one tick's ring commit must move, whatever implements it.

Each protocol keeps its channels in one packed ring ``[D, n, n, K]``,
every channel's payload fields followed by its flag field. Per tick and
per ring the commit

  - reads each send's target slot (int32), values and flag for every
    (sender, receiver) pair: ``4 * (w + 2)`` bytes a pair;
  - reads and writes the ``w + 1`` ring fields the send merges into:
    ``2 * 4 * (w + 1)`` bytes a pair;
  - writes the delivered slot back to its clear value: ``n * n * K * 4``.

Counted unpadded, so the count is the same for the dense Pallas pass of
today and for any scatter that replaces it. The channel widths and the
sends of a tick are written out here, apart from the program; a test
holds them to the program's ring specs.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# (channel, payload width, additive) in ring order, per ring
def rings(protocol: str, n: int) -> Dict[str, List[Tuple[str, int, bool]]]:
    mandator = [("batch", 2, False), ("vote", 1, False)]
    sporades = [("prop", 2 + 2 * n, False), ("vote", 2 + n, False),
                ("to", 2 + n, False), ("pa", 1 + n, False),
                ("va", n, False), ("ac", 2 + n, False)]
    paxos = [("fw", 2, True), ("acc", 3 + n, False), ("ack", 1, False)]
    if protocol == "mandator-sporades":
        return {"mandator": mandator, "sporades": sporades}
    if protocol == "multipaxos":
        return {"paxos": paxos}
    raise ValueError(f"no ring layout for {protocol!r}")


# the channels each ring is sent on in one tick, in order (a channel sent
# twice merges twice)
SENDS = {"mandator": ("vote", "batch"),
         "sporades": ("vote", "prop", "to", "pa", "va", "pa", "ac", "vote"),
         "paxos": ("fw", "acc", "ack")}


def ring_k(layout) -> int:
    return sum(w + 1 for _, w, _ in layout)


def tick_bytes(protocol: str, n: int) -> int:
    """Bytes one tick's commits of every ring of the protocol must move."""
    total = 0
    for ring, layout in rings(protocol, n).items():
        width = {c: w for c, w, _ in layout}
        for c in SENDS[ring]:
            w = width[c]
            total += n * n * (4 * (w + 2) + 8 * (w + 1))
        total += n * n * ring_k(layout) * 4
    return total


def dense_pass_bytes(protocol: str, n: int, d: int) -> int:
    """What a dense pass over every slot moves per tick (read and write of
    the whole ring, its two minor dims padded to the (8, 128) tile) — for
    comparison only."""
    pad8 = -(-n // 8) * 8
    return sum(2 * d * n * pad8 * (-(-ring_k(layout) // 128) * 128) * 4
               for layout in rings(protocol, n).values())
