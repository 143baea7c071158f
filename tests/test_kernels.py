"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models import mamba, rwkv6


def _rand(key, shape, dtype):
    return (0.5 * jax.random.normal(key, shape)).astype(dtype)


@pytest.mark.parametrize("s,d,h,kv,bq,bk", [
    (128, 64, 4, 4, 64, 64),       # MHA
    (256, 64, 4, 2, 128, 64),      # GQA 2:1
    (256, 128, 8, 1, 64, 128),     # MQA
    (128, 32, 2, 2, 128, 128),     # single block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, d, h, kv, bq, bk, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(keys[0], (2, s, h, d), dtype)
    k = _rand(keys[1], (2, s, kv, d), dtype)
    v = _rand(keys[2], (2, s, kv, d), dtype)
    out = ops.flash_attention_bshd(q, k, v, block_q=bq, block_k=bk)
    expect = ref.reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window,softcap", [(32, 0.0), (0, 30.0), (64, 20.0)])
def test_flash_attention_window_softcap(window, softcap):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(keys[0], (1, 256, 4, 64), jnp.float32)
    k = _rand(keys[1], (1, 256, 2, 64), jnp.float32)
    v = _rand(keys[2], (1, 256, 2, 64), jnp.float32)
    out = ops.flash_attention_bshd(q, k, v, window=window, softcap=softcap,
                                   block_q=64, block_k=64)
    expect = ref.reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), window=window,
        softcap=softcap).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def _wkv_inputs(b, s, h, d, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    r = _rand(keys[0], (b, s, h, d), dtype)
    k = _rand(keys[1], (b, s, h, d), dtype)
    v = _rand(keys[2], (b, s, h, d), dtype)
    w_log = jnp.clip(jax.random.normal(keys[3], (b, s, h, d)) - 1.0, -8.0, 1.6)
    w = jnp.exp(-jnp.exp(w_log)).astype(dtype)
    u = _rand(keys[4], (h, d), jnp.float32)
    return r, k, v, w, u


@pytest.mark.parametrize("s,d,chunk", [(64, 16, 16), (128, 32, 16), (48, 16, 8)])
def test_wkv6_kernel_vs_ref(s, d, chunk):
    r, k, v, w, u = _wkv_inputs(2, s, 2, d)
    out = ops.wkv6(r, k, v, w, u, chunk=chunk)
    expect, _ = ref.reference_wkv6(*(t.transpose(0, 2, 1, 3)
                                     for t in (r, k, v, w)), u)
    scale = float(jnp.abs(expect).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(expect.transpose(0, 2, 1, 3)),
                               rtol=1e-4, atol=1e-4 * scale)


def test_model_wkv_chunked_matches_scan():
    """The jnp chunked training path == the sequential oracle, with state
    carry across calls (decode continuation)."""
    r, k, v, w, u = _wkv_inputs(2, 80, 2, 16, seed=3)
    rt, kt, vt, wt = (t for t in (r, k, v, w))
    o1, s1 = rwkv6.wkv_chunked(rt, kt, vt, wt, u)
    o2, s2 = rwkv6.wkv_scan(rt, kt, vt, wt, u)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)
    # continuation: chunked(first half) state feeds scan(second half)
    oa, sa = rwkv6.wkv_chunked(rt[:, :40], kt[:, :40], vt[:, :40],
                               wt[:, :40], u)
    ob, sb = rwkv6.wkv_scan(rt[:, 40:], kt[:, 40:], vt[:, 40:], wt[:, 40:],
                            u, state=sa)
    np.testing.assert_allclose(np.asarray(ob), np.asarray(o2[:, 40:]),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunked_matches_scan():
    b, s, h, p, n = 2, 96, 3, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    xv = 0.5 * jax.random.normal(keys[0], (b, s, h, p))
    bb = 0.5 * jax.random.normal(keys[1], (b, s, h, n))
    cc = 0.5 * jax.random.normal(keys[2], (b, s, h, n))
    dt = jax.nn.softplus(jax.random.normal(keys[3], (b, s, h)))
    decay = jnp.exp(-dt * jnp.exp(jax.random.normal(keys[4], (h,)) * 0.3))
    dskip = jnp.ones((h, p))
    o1, s1 = mamba.ssd_chunked(xv, bb, cc, dt, decay, dskip, chunk=32)
    o2, s2 = mamba.ssd_scan(xv, bb, cc, dt, decay, dskip)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("w,n,block", [(8, 4096, 1024), (16, 8192, 4096),
                                       (3, 512, 512),
                                       # non-multiple sizes: the last grid
                                       # block is ragged, clipped at N
                                       (8, 5000, 1024), (4, 700, 256),
                                       (5, 3, 4096)])
def test_backup_reduce_kernel(w, n, block):
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(w, n), jnp.float32)
    mask = jnp.asarray(rng.rand(w) < 0.75)
    n_agg = max(1, int(mask.sum()))
    out = ops.backup_reduce(g, mask, n_agg, block=block)
    expect = ref.reference_backup_reduce(g, mask, n_agg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("w", [2, 3, 8])
@pytest.mark.parametrize("size", ["ragged", "small"])
def test_backup_reduce_default_block(w, dtype, size):
    """The block chosen from the shape gives the 4,096-lane kernel's
    output bit for bit: over several grid steps with a ragged last one,
    and as one full-width block when N is below the chosen block."""
    from repro.kernels.backup_reduce import choose_block
    from repro.kernels.bucketed_reduce import ref_masked_mean
    chosen = choose_block(w, 1 << 30, jnp.dtype(dtype).itemsize)
    n = chosen + 1_000 + 37 if size == "ragged" else 3_000
    g = _rand(jax.random.PRNGKey(w), (w, n), dtype)
    mask = jnp.arange(w) != 1                  # worker 1 a straggler
    n_agg = w - 1
    out = ops.backup_reduce(g, mask, n_agg)
    assert out.shape == (n,) and out.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ops.backup_reduce(g, mask, n_agg,
                                                      block=4096)))
    with jax.default_matmul_precision("highest"):
        expect = ref_masked_mean(g, mask, n_agg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("w,n,itemsize,expect", [
    (2, 596_049_920, 4, 262_144), (4, 596_049_920, 4, 131_072),
    (8, 596_049_920, 2, 131_072), (3, 596_049_920, 4, 174_080),
    (2, 1 << 20, 2, 524_288), (2, 262_143, 4, 262_143),
    (6, 5_000, 4, 5_000), (1, 37, 4, 37)])
def test_backup_reduce_block_rule(w, n, itemsize, expect):
    from repro.kernels.backup_reduce import LANES, TILE_BYTES, choose_block
    block = choose_block(w, n, itemsize)
    assert block == expect
    assert block % LANES == 0 or block == n
    assert w * block * itemsize <= TILE_BYTES
    assert block <= n
    # the largest such block: one granule more overflows the budget
    assert block == n or w * (block + LANES) * itemsize > TILE_BYTES


def test_backup_reduce_matches_sync_backup_semantics():
    """Kernel == repro.core.sync_backup.aggregate_masked on flattened grads."""
    from repro.core import sync_backup
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(6, 2048), jnp.float32)
    mask = jnp.asarray([1, 0, 1, 1, 0, 1], bool)
    out = ops.backup_reduce(g, mask, 4, block=512)
    expect = sync_backup.aggregate_masked(g, mask, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)
