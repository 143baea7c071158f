"""The main path's Pallas kernels compiled for a described TPU v5e, at
qwen3-0.6b's widths, with nothing attached: what Mosaic and the TPU
compiler refuse shows up here at no chip time. Nothing runs, so this
says nothing about results or speed.

Only one process at a time may load the TPU compiler's library, so the
topology is described inside a module fixture (never at import) and
every compile happens in this test process.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.backup_reduce import backup_reduce
from repro.kernels.bucketed_reduce import bucket_bounds, reduce_then_psum
from repro.kernels.page_gather import gather_pages_pallas

# qwen3-0.6b (configs/qwen3_0_6b.py): flattened parameter count, and the
# KV layout a serving page holds (8 KV heads of 128, 8-token pages)
NUM_PARAMS = 596_049_920
KV_HEADS, HEAD_DIM, PAGE = 8, 128, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:           # noqa: BLE001 — any refusal skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# [W, P] f32 is how the spmd engine hands its shard's gradients over; at
# W = 8 that is 19 GB, more than a v5e holds, so W = 8 compiles bf16 rows
@pytest.mark.parametrize("workers,dtype", [(2, jnp.float32),
                                           (4, jnp.float32),
                                           (8, jnp.bfloat16)])
def test_backup_reduce_compiles_native(one_chip, workers, dtype):
    grads = jax.ShapeDtypeStruct((workers, NUM_PARAMS), dtype,
                                 sharding=one_chip)
    mask = jax.ShapeDtypeStruct((workers,), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda g, m: backup_reduce(g, m, workers - 1, interpret=False),
        grads, mask)
    assert "tpu_custom_call" in text


def test_reduce_then_psum_bucketed_compiles_native(topo):
    """Two workers per chip on a 4-chip 'data' mesh, the flattened
    gradient cut into buckets: one kernel and one all-reduce each."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    w_local, bucket = 2, 1 << 27

    def body(g, m):
        agg, _ = reduce_then_psum(g, m, 6, axis_name="data", bucket=bucket,
                                  use_kernel=True, interpret=False)
        return agg

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=P(), check_vma=False)
    sharded = NamedSharding(mesh, P("data"))
    text = _compiled_text(
        fn,
        jax.ShapeDtypeStruct((4 * w_local, NUM_PARAMS), jnp.float32,
                             sharding=sharded),
        jax.ShapeDtypeStruct((4 * w_local,), jnp.float32, sharding=sharded))
    n_buckets = len(bucket_bounds(NUM_PARAMS, bucket))
    assert n_buckets > 1
    assert text.count("tpu_custom_call") >= n_buckets
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") \
        == n_buckets


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_page_gather_compiles_native(one_chip, int8):
    num_pages, slots, max_pages = 129, 4, 32
    pool = jax.ShapeDtypeStruct(
        (num_pages, PAGE, KV_HEADS, HEAD_DIM),
        jnp.int8 if int8 else jnp.bfloat16, sharding=one_chip)
    table = jax.ShapeDtypeStruct((slots, max_pages), jnp.int32,
                                 sharding=one_chip)
    args = [pool, table]
    if int8:                       # the pool keeps fp16 scales per token
        args.append(jax.ShapeDtypeStruct((num_pages, PAGE, KV_HEADS),
                                         jnp.float16, sharding=one_chip))
    text = _compiled_text(
        lambda *a: gather_pages_pallas(*a, out_dtype=jnp.bfloat16,
                                       interpret=False), *args)
    assert "tpu_custom_call" in text
