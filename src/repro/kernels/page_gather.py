"""Page-gather kernel for paged decode attention.

Decode attention over a paged KV cache needs, per slot, the slot's pages
assembled into a contiguous ``[tokens, kv_heads, head_dim]`` view. The
reference path is a jnp advanced-index gather (XLA lowers it to a dynamic
gather from HBM); the Pallas kernel instead drives one DMA per (slot,
logical page) grid cell, using the page table as a **scalar-prefetch**
operand so the block index map can look up the physical page id before the
body runs (``pltpu.PrefetchScalarGridSpec`` — see the quantization-kernel
pattern in the Pallas guide). Dequantization of int8 pages fuses into the
same pass: payload and scale blocks are gathered together and multiplied
in VMEM, so the scales never round-trip through a separate gather. The
pool stores scales as fp16, which Mosaic cannot load into a vector
register; the kernel is handed them widened to f32 (exact), a pass over
1/64 of the int8 payload's bytes.

Like the other kernels in this package the Pallas path runs natively on
TPU and in interpret mode elsewhere (``kernels.mode``), and is
parity-tested against the jnp twin (tests/test_serve_engine.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode


def gather_pages_reference(pool: jnp.ndarray, page_table: jnp.ndarray,
                           scales: Optional[jnp.ndarray] = None,
                           out_dtype=jnp.float32) -> jnp.ndarray:
    """jnp twin: pool [P, ps, kv, hd], page_table [B, maxp] ->
    [B, maxp*ps, kv, hd] (dead table entries gather the trash page)."""
    b, maxp = page_table.shape
    _, ps, kv, hd = pool.shape
    g = pool[page_table]                            # [B, maxp, ps, kv, hd]
    if scales is not None:
        s = scales[page_table]                      # [B, maxp, ps, kv]
        g = g.astype(jnp.float32) * s.astype(jnp.float32)[..., None]
    return g.reshape(b, maxp * ps, kv, hd).astype(out_dtype)


def _gather_kernel(tbl_ref, pool_ref, out_ref):
    out_ref[0, 0] = pool_ref[0].astype(out_ref.dtype)


def _gather_dequant_kernel(tbl_ref, pool_ref, scale_ref, out_ref):
    deq = pool_ref[0].astype(jnp.float32) * scale_ref[0][..., None]
    out_ref[0, 0] = deq.astype(out_ref.dtype)


def gather_pages_pallas(pool: jnp.ndarray, page_table: jnp.ndarray,
                        scales: Optional[jnp.ndarray] = None,
                        out_dtype=jnp.float32,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Pallas page gather (+ fused int8 dequant when ``scales`` is given)."""
    b, maxp = page_table.shape
    _, ps, kv, hd = pool.shape

    in_specs = [pl.BlockSpec((1, ps, kv, hd),
                             lambda i, p, tbl: (tbl[i, p], 0, 0, 0))]
    operands = [pool]
    kernel = _gather_kernel
    if scales is not None:
        in_specs.append(pl.BlockSpec((1, ps, kv),
                                     lambda i, p, tbl: (tbl[i, p], 0, 0)))
        operands.append(scales.astype(jnp.float32))
        kernel = _gather_dequant_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, ps, kv, hd),
                               lambda i, p, tbl: (i, p, 0, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, maxp, ps, kv, hd), out_dtype),
        interpret=interpret_mode(interpret),
    )(page_table, *operands)
    return out.reshape(b, maxp * ps, kv, hd)


def gather_pages(pool: jnp.ndarray, page_table: jnp.ndarray,
                 scales: Optional[jnp.ndarray] = None, *,
                 out_dtype=jnp.float32, use_kernel: bool = False,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    if use_kernel:
        return gather_pages_pallas(pool, page_table, scales, out_dtype,
                                   interpret=interpret)
    return gather_pages_reference(pool, page_table, scales, out_dtype)
