"""Fused bucketed reduce-then-psum: the collective half of Alg. 4 line 7.

``backup_reduce`` (the in-shard Pallas masked reduce) and the ``psum``
over the mesh ``'data'`` axis used to be two sequential steps on the
whole flattened gradient: nothing crossed the wire until the entire
[W_local, P] stack had been reduced, and the optimizer waited until the
entire [P] psum finished. This module fuses them into a *bucketed*
pipeline: the flat gradient is cut into fixed-size buckets and each
bucket's psum is issued the moment that bucket's in-shard reduce
completes — so with async collectives (the latency-hiding XLA recipe in
``launch.mesh.set_platform``) bucket i's wire time overlaps bucket
i+1's reduce compute. The unrolled per-bucket chain is exactly the
dependency structure XLA's latency-hiding scheduler needs; a single
monolithic reduce+psum gives it nothing to overlap.

Two in-shard reduce implementations, selected by ``use_kernel``:

* the ``kernels.backup_reduce`` Pallas kernel per bucket (one fused
  mask+scale+reduce pass over VMEM-streamed tiles; interpret mode
  off-TPU, chosen from the backend), or
* a jnp reference (``[W] @ [W, bucket]`` dot) — the oracle the property
  tests in ``tests/test_bucketed_reduce.py`` hold the kernel to.

The scalar *tail*: per-step monitoring scalars (the masked loss sum and
the aux-loss sum) ride in the last bucket's padding lanes, so the whole
step needs exactly ``ceil(P / bucket)`` collectives — with the default
single bucket, ONE psum per step where the unfused engine issued three
(gradient + two scalar reductions). On a CPU host with forced devices
every psum is a full cross-device thread rendezvous, so collective
count is the first-order cost this module removes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.backup_reduce import backup_reduce


def ref_masked_mean(grads: jnp.ndarray, mask: jnp.ndarray,
                    n_aggregate: int) -> jnp.ndarray:
    """The dense jnp oracle: [W, P] stacked grads, [W] mask ->
    (1/n_aggregate) * sum_w mask_w * g_w, in f32."""
    m = mask.astype(jnp.float32)
    return (m @ grads.astype(jnp.float32)) / n_aggregate


def bucket_bounds(total: int, bucket: int) -> Tuple[Tuple[int, int], ...]:
    """(lo, hi) slices cutting ``total`` lanes into ``bucket``-size pieces.

    ``bucket <= 0`` means one bucket spanning everything (the unbucketed
    fused path). The last bucket is ragged when ``bucket`` does not
    divide ``total``.
    """
    if total < 0:
        raise ValueError(f"total lanes must be >= 0 (got {total})")
    if bucket <= 0 or bucket >= total:
        return ((0, total),)
    return tuple((lo, min(lo + bucket, total))
                 for lo in range(0, total, bucket))


def reduce_then_psum(grads: jnp.ndarray, mask: jnp.ndarray,
                     n_aggregate: int, *,
                     axis_name: Optional[str] = None,
                     bucket: int = 0,
                     tail: Optional[jnp.ndarray] = None,
                     use_kernel: bool = True,
                     interpret: Optional[bool] = None,
                     block: Optional[int] = None
                     ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Bucketed masked reduce of [W, P] stacked grads, psum'd per bucket.

    Returns ``([P] f32 aggregated gradient, tail_out)`` where the
    gradient is ``(1/n_aggregate) * sum_{selected} g_w`` summed over
    ``axis_name`` (no collective when ``axis_name`` is None — the pure
    single-shard function the property tests exercise), and ``tail_out``
    is the [E] ``tail`` vector summed over ``axis_name`` (it rides the
    last bucket's psum; None in == None out).

    ``bucket`` is the lane count per collective (0 = single bucket);
    ``use_kernel`` picks the Pallas in-shard reduce vs the jnp dot;
    ``block`` is the Pallas grid tile within each bucket (None: chosen
    from the bucket's shape and dtype, ``backup_reduce.choose_block``).
    """
    w, p = grads.shape
    if mask.shape != (w,):
        raise ValueError(f"mask shape {mask.shape} does not match the "
                         f"worker axis of grads {grads.shape}")
    mf = mask.astype(jnp.float32)

    def reduce_bucket(chunk: jnp.ndarray) -> jnp.ndarray:
        if w == 1:
            # one local worker: the masked mean is a scalar rescale of
            # the single row — no kernel / dot needed (the common case
            # when the mesh 'data' axis equals the worker count)
            return chunk[0].astype(jnp.float32) * (mf[0] / n_aggregate)
        if use_kernel and chunk.shape[1] > 0:
            return backup_reduce(chunk, mf, n_aggregate,
                                 block=block, interpret=interpret)
        return (mf @ chunk.astype(jnp.float32)) / n_aggregate

    bounds = bucket_bounds(p, bucket)
    out = []
    tail_out = None
    for i, (lo, hi) in enumerate(bounds):
        red = reduce_bucket(grads[:, lo:hi])
        last = i == len(bounds) - 1
        if last and tail is not None:
            # the monitoring scalars ride the final bucket's collective
            red = jnp.concatenate([red, tail.astype(jnp.float32)])
        if axis_name is not None:
            red = jax.lax.psum(red, axis_name)
        if last and tail is not None:
            red, tail_out = red[:hi - lo], red[hi - lo:]
        out.append(red)
    agg = out[0] if len(out) == 1 else jnp.concatenate(out)
    return agg, tail_out
