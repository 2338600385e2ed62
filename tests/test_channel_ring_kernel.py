"""The ring-commit kernel (``kernels/channel_ring``), the one custom
kernel: the Pallas kernel in interpret mode is bitwise-equal to the
pure-jnp scatter oracle, its slot blocking fits the VMEM budget, and the
backend selector refuses what it does not know."""
import jax.numpy as jnp
import pytest


@pytest.mark.parametrize("seed,dmax,ticks,bs", [
    pytest.param(0, 32, range(64), None, id="0"),
    pytest.param(7, 32, range(64), None, id="7"),
    # the paper-ddos horizon with 16 slots per grid step (64 steps), over
    # the wrap of t % D
    pytest.param(3, 1024, range(1016, 1032), 16, id="1024-bs16"),
])
def test_channel_ring_commit_interpret_matches_ref(seed, dmax, ticks, bs,
                                                   monkeypatch):
    """Pallas dense ring-commit kernel (interpret mode) is bitwise-equal to
    the pure-jnp scatter oracle over random tick traffic — max-merged and
    additive channels, drops, in-slot collisions, and the slot-clear."""
    from functools import partial

    import numpy as np

    from repro.core import channel as ch
    from repro.kernels.channel_ring import kernel as ring_kernel
    from repro.kernels.channel_ring import ops as ring_ops

    if bs is not None:
        monkeypatch.setattr(ring_ops, "ring_commit_tpu",
                            partial(ring_kernel.ring_commit_tpu, bs=bs))
    rng = np.random.RandomState(seed)
    n = 5
    spec = ch.RingSpec(ch.ChannelSpec("a", 2),
                       ch.ChannelSpec("fw", 2, additive=True),
                       ch.ChannelSpec("b", 3))
    ring_ref = ch.make_ring(spec, dmax, n)
    ring_pal = ch.make_ring(spec, dmax, n)
    for t in ticks:
        drop = jnp.asarray(rng.rand(n, n) < 0.2)
        sends = []
        for name, w in (("a", 2), ("fw", 2), ("b", 3), ("a", 2)):
            pay = jnp.asarray(rng.uniform(-1.0, 50.0, (n, n, w)
                                          ).astype(np.float32))
            delay = jnp.asarray(rng.randint(0, 2 * dmax, (n, n)), jnp.int32)
            mask = jnp.asarray(rng.rand(n, n) < 0.5)
            sends.append(ch.Send(name, pay, delay, mask))
        ring_ref = ch.ring_commit(spec, ring_ref, jnp.int32(t), sends,
                                  drop=drop, backend="jnp")
        ring_pal = ch.ring_commit(spec, ring_pal, jnp.int32(t), sends,
                                  drop=drop, backend="pallas-interpret")
        np.testing.assert_array_equal(np.asarray(ring_ref["buf"]),
                                      np.asarray(ring_pal["buf"]),
                                      err_msg=f"t={t}")


def test_ring_commit_block_slots():
    """Slots per grid step: a power of two dividing D whose padded
    [bs, n, n, K] f32 block stays within BLOCK_BYTES."""
    from repro.kernels.channel_ring.kernel import BLOCK_BYTES, block_slots
    assert block_slots(256, 5, 50) == 64      # 20 KiB per padded slot
    assert block_slots(1024, 5, 50) == 64
    assert block_slots(48, 5, 50) == 16       # largest power-of-2 divisor
    assert block_slots(256, 9, 200) * 9 * 16 * 256 * 4 <= BLOCK_BYTES


def test_channel_backend_rejects_unknown():
    from repro.kernels.channel_ring.ops import resolve_backend
    with pytest.raises(ValueError, match="channel backend"):
        resolve_backend("cuda")
    assert resolve_backend("ref") == "jnp"
