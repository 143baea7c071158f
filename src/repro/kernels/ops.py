"""Jitted public wrappers for the Pallas kernels.

The kernels run natively on TPU and in interpret mode on every other
backend (``kernels.mode.interpret_mode``). These wrappers also adapt
layouts: models carry activations as [B, S, H, D]; the kernels want
[B, H, S, D].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backup_reduce as _br
from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv6_scan as _wk
from repro.kernels.mode import interpret_mode


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention_bshd(q, k, v, *, causal=True, window=0, softcap=0.0,
                         block_q=128, block_k=128):
    """q: [B, S, H, D]; k/v: [B, S, KV, D] -> [B, S, H, D]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              softcap=softcap, block_q=block_q, block_k=block_k,
                              interpret=interpret_mode())
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, w, u, *, chunk=16):
    """r/k/v/w: [B, S, H, D]; u: [H, D] -> [B, S, H, D]."""
    args = [t.transpose(0, 2, 1, 3) for t in (r, k, v, w)]
    out = _wk.wkv6_chunked(*args, u, chunk=chunk,
                           interpret=interpret_mode())
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("n_aggregate", "block"))
def backup_reduce(grads, mask, n_aggregate, *, block=4096):
    """grads: [W, N]; mask: [W] -> [N] = (1/N_agg) * sum_selected."""
    return _br.backup_reduce(grads, mask, n_aggregate, block=block,
                             interpret=interpret_mode())
