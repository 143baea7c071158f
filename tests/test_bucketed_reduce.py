"""Fused bucketed reduce-then-psum: property tests against the jnp oracle.

The kernel under test (``repro.kernels.bucketed_reduce``) is the
collective half of the SPMD engine's aggregation: cut the flattened
[W, P] gradient stack into buckets, masked-reduce each in-shard, psum
per bucket, with monitoring scalars riding the last bucket. The oracle
is ``ref_masked_mean`` — the dense jnp reduction the property tests
hold every configuration to (random shapes, masks, bucket sizes, Pallas
blocks that do NOT divide the bucket, i.e. a ragged last grid block).

Property tests use the ``hypothesis_stub`` shim: with hypothesis
installed (requirements-dev.txt, the CI path) they fuzz; without it they
report skipped while the deterministic edge-case tests still run.
"""
import numpy as np
import pytest

from hypothesis_stub import given, settings, st

import jax
import jax.numpy as jnp

from repro.kernels.bucketed_reduce import (bucket_bounds, ref_masked_mean,
                                           reduce_then_psum)


def _rand(seed, w, p):
    rng = np.random.default_rng(seed)
    grads = rng.standard_normal((w, p)).astype(np.float32)
    mask = (rng.random(w) < 0.7).astype(np.float32)
    return jnp.asarray(grads), jnp.asarray(mask)


def _assert_matches_ref(grads, mask, n_agg, **kw):
    agg, _ = reduce_then_psum(grads, mask, n_agg, **kw)
    ref = ref_masked_mean(grads, mask, n_agg)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# bucket_bounds
# ---------------------------------------------------------------------------


def test_bucket_bounds_edges():
    assert bucket_bounds(10, 0) == ((0, 10),)          # unbucketed
    assert bucket_bounds(10, 10) == ((0, 10),)         # bucket == total
    assert bucket_bounds(10, 11) == ((0, 10),)         # bucket > total
    assert bucket_bounds(10, 4) == ((0, 4), (4, 8), (8, 10))  # ragged last
    assert bucket_bounds(8, 4) == ((0, 4), (4, 8))     # exact
    assert bucket_bounds(0, 4) == ((0, 0),)            # empty flatten
    with pytest.raises(ValueError, match=">= 0"):
        bucket_bounds(-1, 4)


def test_bucket_bounds_cover_exactly():
    for total in (1, 7, 64, 100):
        for bucket in (1, 3, 8, 64, 200):
            bounds = bucket_bounds(total, bucket)
            assert bounds[0][0] == 0 and bounds[-1][1] == total
            for (a, b), (c, d) in zip(bounds, bounds[1:]):
                assert b == c and a < b


# ---------------------------------------------------------------------------
# Deterministic edges (always run, hypothesis or not)
# ---------------------------------------------------------------------------


def test_single_worker_shortcut_matches_ref():
    # W == 1 takes the scalar-rescale shortcut (no dot, no kernel) —
    # the common case when the mesh 'data' axis equals the worker count
    grads, _ = _rand(0, 1, 37)
    for mask_val in (0.0, 1.0):
        mask = jnp.asarray([mask_val])
        for bucket in (0, 16):
            _assert_matches_ref(grads, mask, 3, bucket=bucket,
                                use_kernel=True, interpret=True)


def test_kernel_padding_edges():
    # P=50 lanes, bucket=16 -> ragged last bucket of 2 lanes, block=8
    # does not divide it: backup_reduce's ragged last grid block
    grads, mask = _rand(1, 4, 50)
    _assert_matches_ref(grads, mask, 2, bucket=16, use_kernel=True,
                        interpret=True, block=8)
    # block larger than the whole bucket
    _assert_matches_ref(grads, mask, 2, bucket=6, use_kernel=True,
                        interpret=True, block=64)
    # the default block, chosen from each bucket's own width
    _assert_matches_ref(grads, mask, 2, bucket=16, use_kernel=True,
                        interpret=True)


def test_empty_flatten():
    grads, mask = _rand(2, 3, 0)
    agg, tail = reduce_then_psum(grads, mask, 2, tail=jnp.asarray([5.0, 7.0]),
                                 use_kernel=True, interpret=True)
    assert agg.shape == (0,)
    np.testing.assert_allclose(np.asarray(tail), [5.0, 7.0])


def test_tail_rides_last_bucket_without_perturbing_gradient():
    grads, mask = _rand(3, 5, 23)
    tail_in = jnp.asarray([2.5, -1.25, 9.0])
    plain, none_tail = reduce_then_psum(grads, mask, 4, bucket=8,
                                        use_kernel=False)
    agg, tail = reduce_then_psum(grads, mask, 4, bucket=8, tail=tail_in,
                                 use_kernel=False)
    assert none_tail is None
    np.testing.assert_allclose(np.asarray(agg), np.asarray(plain))
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_in))


def test_mask_shape_mismatch_raises():
    grads, _ = _rand(4, 4, 10)
    with pytest.raises(ValueError, match="does not match the worker axis"):
        reduce_then_psum(grads, jnp.ones((3,)), 2)


def test_psum_path_on_single_device_mesh():
    """axis_name wired through shard_map on a (1, 1) mesh: the collective
    branch (psum per bucket, tail split after the psum) compiles and
    matches the oracle in-process — tier-1 coverage of the exact code
    the multi-device engine runs."""
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    grads, mask = _rand(5, 4, 33)
    tail_in = jnp.asarray([3.0, 4.0])

    from repro.distributed.spmd_engine import _shard_map
    from jax.sharding import PartitionSpec as P

    def body(g, m, t):
        return reduce_then_psum(g, m, 3, axis_name="data", bucket=10,
                                tail=t, use_kernel=False)

    fn = _shard_map(body, mesh, in_specs=(P(), P(), P()),
                    out_specs=(P(), P()))
    agg, tail = jax.jit(fn)(grads, mask, tail_in)
    np.testing.assert_allclose(np.asarray(agg),
                               np.asarray(ref_masked_mean(grads, mask, 3)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_in))


# ---------------------------------------------------------------------------
# Hypothesis properties: every configuration equals the oracle
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 2**31 - 1), w=st.integers(1, 6),
       p=st.integers(1, 160), bucket=st.integers(0, 200))
@settings(max_examples=40, deadline=None)
def test_property_jnp_bucketing_matches_ref(seed, w, p, bucket):
    grads, mask = _rand(seed, w, p)
    n_agg = max(1, int(np.asarray(mask).sum()))
    _assert_matches_ref(grads, mask, n_agg, bucket=bucket, use_kernel=False)


@given(seed=st.integers(0, 2**31 - 1), w=st.integers(2, 5),
       p=st.integers(1, 120), bucket=st.integers(0, 130),
       block=st.integers(2, 48))
@settings(max_examples=25, deadline=None)
def test_property_kernel_bucketing_matches_ref(seed, w, p, bucket, block):
    # interpret-mode Pallas kernel per bucket, including blocks that do
    # not divide the (possibly ragged) bucket width — ragged grid blocks
    grads, mask = _rand(seed, w, p)
    _assert_matches_ref(grads, mask, 2, bucket=bucket, use_kernel=True,
                        interpret=True, block=block)


@given(seed=st.integers(0, 2**31 - 1), p=st.integers(1, 100),
       bucket=st.integers(0, 110), e=st.integers(1, 5))
@settings(max_examples=30, deadline=None)
def test_property_tail_passthrough(seed, p, bucket, e):
    grads, mask = _rand(seed, 3, p)
    rng = np.random.default_rng(seed + 1)
    tail_in = jnp.asarray(rng.standard_normal(e).astype(np.float32))
    agg, tail = reduce_then_psum(grads, mask, 2, bucket=bucket, tail=tail_in,
                                 use_kernel=False)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_in),
                               rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.asarray(agg),
                               np.asarray(ref_masked_mean(grads, mask, 2)),
                               rtol=1e-5, atol=1e-6)
