"""Per-kernel validation: shape/dtype sweeps, interpret-mode kernels vs the
pure-jnp oracles, plus custom-VJP correctness of the jnp fast paths."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("b,s,h,kh,d", [
    (2, 128, 4, 2, 32),
    (1, 256, 8, 8, 64),
    (2, 96, 6, 3, 16),      # non-multiple seq -> padding path
    (1, 64, 4, 1, 32),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kh, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d), dtype)
    out = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=True)
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    assert out.shape == ref.shape and out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("shape", [(4, 16, 64), (3, 7, 32), (2, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_sweep(shape, dtype, residual):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], shape, dtype)
    r = jax.random.normal(ks[1], shape, dtype) if residual else None
    w = jax.random.normal(ks[2], shape[-1:], dtype)
    out = rmsnorm(x, w, residual=r)
    ref = rmsnorm_ref(x, w, residual=r)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("b,s,di,n", [(2, 64, 32, 8), (1, 48, 16, 4),
                                      (2, 128, 8, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssm_scan_sweep(b, s, di, n, dtype):
    ks = jax.random.split(KEY, 6)
    x = (jax.random.normal(ks[0], (b, s, di)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) - 1).astype(dtype)
    B = jax.random.normal(ks[2], (b, s, n), dtype)
    C = jax.random.normal(ks[3], (b, s, n), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.3)
    D = jax.random.normal(ks[5], (di,))
    out = ssm_scan(x, dt, B, C, A, D, bd=16, chunk=16)
    ref = ssm_scan_ref(x, dt, B, C, A, D)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


def test_flash_chunked_vjp_matches_dense():
    from repro.models.layers import dense_attention, flash_chunked
    b, s, h, kh, d = 2, 128, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    g1 = jax.grad(lambda *a: (flash_chunked(*a, True, 32) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (dense_attention(*a, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b_))) < 1e-4


def test_selective_scan_vjp_matches_autodiff():
    from repro.models.ssm import _selective_scan
    b, s, di, n = 2, 32, 8, 4
    ks = jax.random.split(KEY, 6)
    args = (jax.random.normal(ks[0], (b, s, di)) * 0.5,
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) - 1),
            jax.random.normal(ks[2], (b, s, n)),
            jax.random.normal(ks[3], (b, s, n)),
            -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.3),
            jax.random.normal(ks[5], (di,)))
    g1 = jax.grad(lambda *a: (_selective_scan(*a, 8) ** 2).sum(),
                  argnums=tuple(range(6)))(*args)
    g2 = jax.grad(lambda *a: (ssm_scan_ref(*a) ** 2).sum(),
                  argnums=tuple(range(6)))(*args)
    for x, y in zip(g1, g2):
        denom = max(1.0, float(jnp.max(jnp.abs(y))))
        assert float(jnp.max(jnp.abs(x - y))) / denom < 1e-4


@pytest.mark.parametrize("b,h,kh,s,d", [
    (2, 4, 2, 256, 32),
    (1, 8, 8, 128, 64),     # MHA
    (2, 4, 1, 512, 16),     # MQA
])
def test_decode_attention_sweep(b, h, kh, s, d):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, kh, s, d))
    v = jax.random.normal(ks[2], (b, kh, s, d))
    kv_len = jnp.arange(1, b + 1, dtype=jnp.int32) * (s // (b + 1) + 1)
    out = decode_attention(q, k, v, kv_len, bs=64)
    ref = decode_attention_ref(q, k, v, kv_len)
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-6


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


def test_decode_attention_matches_model_decode_path():
    """Kernel agrees with the model's cache attention (dense path)."""
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.models.layers import dense_attention
    b, h, kh, s, d = 2, 4, 2, 64, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    kv_len = jnp.array([40, 64], jnp.int32)
    a = dense_attention(q, k, v, causal=False, kv_len=kv_len)[:, 0]
    r = decode_attention_ref(q[:, 0], k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), kv_len)
    assert float(jnp.max(jnp.abs(a - r))) < 5e-6
