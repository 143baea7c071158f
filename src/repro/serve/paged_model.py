"""Paged prefill + decode: the jitted halves of the serve engine.

Two traced functions per engine, each compiled once (decode) or once per
prompt bucket (prefill):

* ``prefill(params, tokens[1, S_bucket], meta, pool)`` runs the full
  stack over one bucket-padded prompt (``meta`` packs ``[true_len,
  *page_ids]`` as one int32 vector), returns the greedy first token and
  the pool with the prompt's K/V scattered into the request's pages.
  Padding positions are written too (the scatter shape must be static per
  bucket) — they are masked by the decode validity rule (``kpos <= len``)
  until real decode tokens overwrite them.
* ``decode(params, state[S_slots, 2 + max_pages], pool)`` advances every
  slot one token. ``state`` packs per slot ``[last_token, len,
  *page_table_row]`` — one int32 host->device transfer per step, which is
  what the scheduler loop's wall clock is made of at smoke scale. Scatter
  the new K/V at ``len``, gather each slot's pages
  (``repro.kernels.page_gather``), attend under the per-slot validity +
  sliding-window mask, and return each slot's greedy next token (argmax
  stays on device; only ``[S]`` int32 comes back). Idle slots carry a
  zeroed page-table row, so their dead writes land on the reserved trash
  page and their tokens are ignored by the host.

Both run layers through ``lax.scan`` (HLO size O(1) in depth) and carry
the tensor-parallel f/g hooks exactly where ``transformer.block_apply``
puts them, so :func:`build_tp_paged_fns` can wrap the same bodies in
``shard_map`` over the mesh 'model' axis with a locally-reshaped config —
a ``mesh_model > 1`` checkpoint from training serves without resharding.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import tp
from repro.kernels.page_gather import gather_pages
from repro.models import attention, common, mlp, moe
from repro.models.common import Params
from repro.models.transformer import layer_windows_np, segments


def supports_paged(cfg) -> Tuple[bool, str]:
    """Families the paged serve path covers (mirrors decode_step support)."""
    if cfg.family not in ("dense", "moe"):
        return False, f"family {cfg.family!r} has no paged decode path"
    if cfg.attention_kind != "gqa":
        return False, (f"attention_kind {cfg.attention_kind!r} is not paged "
                       f"(MLA latents need their own page layout)")
    return True, ""


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _paged_attn(p_attn: Params, cfg, h, pool_l, lens, page_table, window, *,
                quantized: bool, use_kernel: bool):
    """One layer's paged decode attention. h: [B, 1, d] (post-ln, post f)."""
    b = h.shape[0]
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    ps = pool_l["k"].shape[1]
    pos = lens[:, None]                                   # per-slot positions
    q, k_new, v_new = attention._project_qkv(p_attn, cfg, h, pos)
    # scatter the new token's K/V into its slot's current page
    bidx = jnp.arange(b)
    pid = page_table[bidx, lens // ps]                    # idle rows -> trash
    off = lens % ps
    new_pool = dict(pool_l)
    if quantized:
        kq, ksc = attention._quantize_kv(k_new)
        vq, vsc = attention._quantize_kv(v_new)
        new_pool["k"] = pool_l["k"].at[pid, off].set(kq[:, 0])
        new_pool["v"] = pool_l["v"].at[pid, off].set(vq[:, 0])
        new_pool["k_scale"] = pool_l["k_scale"].at[pid, off].set(ksc[:, 0])
        new_pool["v_scale"] = pool_l["v_scale"].at[pid, off].set(vsc[:, 0])
        k = gather_pages(new_pool["k"], page_table, new_pool["k_scale"],
                         out_dtype=h.dtype, use_kernel=use_kernel)
        v = gather_pages(new_pool["v"], page_table, new_pool["v_scale"],
                         out_dtype=h.dtype, use_kernel=use_kernel)
    else:
        new_pool["k"] = pool_l["k"].at[pid, off].set(
            k_new[:, 0].astype(pool_l["k"].dtype))
        new_pool["v"] = pool_l["v"].at[pid, off].set(
            v_new[:, 0].astype(pool_l["v"].dtype))
        k = gather_pages(new_pool["k"], page_table, out_dtype=h.dtype,
                         use_kernel=use_kernel)
        v = gather_pages(new_pool["v"], page_table, out_dtype=h.dtype,
                         use_kernel=use_kernel)
    s = k.shape[1]                                        # max_pages * ps
    qg = q.reshape(b, kv, cfg.q_per_kv, hd)
    scores = jnp.einsum("bgqd,bsgd->bgqs", qg, k).astype(jnp.float32) \
        / math.sqrt(hd)
    scores = common.softcap(scores, cfg.attn_logit_softcap)
    kpos = jnp.arange(s)
    valid = (kpos[None, :] <= lens[:, None]) \
        & attention._window_ok(lens[:, None] - kpos[None, :], window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgqs,bsgd->bgqd", probs.astype(v.dtype), v)
    out = out.reshape(b, 1, cfg.num_heads * hd)
    return common.dense(p_attn["wo"], out), new_pool


def _ffn(p_l: Params, cfg, kind: str, h2):
    if kind == "moe":
        b = h2.shape[0]
        out, _ = moe.moe_apply(p_l["moe"], cfg, h2.reshape(b * h2.shape[1], -1),
                               cfg.moe.capacity_factor)
        return out.reshape(h2.shape)
    h2 = tp.col_in(h2, "ffn")
    return tp.row_out(mlp.mlp_apply(p_l["mlp"], h2, cfg.hidden_act), "ffn")


def build_paged_decode(model, *, quantized: bool, use_kernel: bool = False,
                       gather_logits: Callable = None) -> Callable:
    """decode(params, state[B, 2+maxp], pool) -> (next_token [B], new pool).

    ``state[:, 0]`` last tokens, ``state[:, 1]`` lens, ``state[:, 2:]`` the
    page table. Greedy argmax happens in-graph; callers get int32 ids.
    ``gather_logits`` (TP) reassembles vocab-sharded logits first."""
    cfg = model.cfg
    windows = layer_windows_np(cfg)

    def decode(params, state, pool):
        state = state.astype(jnp.int32)
        tokens = state[:, 0:1]
        lens = state[:, 1]
        page_table = state[:, 2:]
        x = model._embed_inputs(params, tokens)
        new_segs = []
        for kind, count, first in segments(cfg):
            stacked = params[f"seg_{kind}"]
            seg_windows = jnp.asarray(windows[first:first + count])
            seg_pool = {n: b[first:first + count] for n, b in pool.items()}

            def body(h, xs, _kind=kind):
                p_l, win, pool_l = xs
                h1 = common.rmsnorm(p_l["ln1"], h, cfg.norm_eps)
                h1 = tp.col_in(h1, "attn")
                attn_out, pool_l = _paged_attn(
                    p_l["attn"], cfg, h1, pool_l, lens, page_table, win,
                    quantized=quantized, use_kernel=use_kernel)
                h = h + tp.row_out(attn_out, "attn")
                h2 = common.rmsnorm(p_l["ln2"], h, cfg.norm_eps)
                return h + _ffn(p_l, cfg, _kind, h2), pool_l

            x, new_seg = jax.lax.scan(body, x, (stacked, seg_windows,
                                                seg_pool))
            new_segs.append(new_seg)
        new_pool = {n: jnp.concatenate([s[n] for s in new_segs], axis=0)
                    for n in pool}
        x = common.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = (tp.col_in(x, "vocab") @ model._output_weights(params))[:, 0]
        if gather_logits is not None:
            logits = gather_logits(logits)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_pool

    return decode


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def build_paged_prefill(model, *, quantized: bool,
                        gather_logits: Callable = None) -> Callable:
    """prefill(params, tokens[1,S_bucket], meta, pool)
    -> (first_token scalar int32, new pool). One compile per bucket.

    ``meta`` packs ``[true_len, *page_ids]`` as one int32 vector so an
    admission costs two host->device transfers, not four."""
    cfg = model.cfg
    windows = layer_windows_np(cfg)
    hd = cfg.resolved_head_dim

    def prefill(params, tokens, meta, pool):
        meta = meta.astype(jnp.int32)
        true_len, page_ids = meta[0], meta[1:]
        x = model._embed_inputs(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        s = x.shape[1]
        ks_all, vs_all = [], []
        for kind, count, first in segments(cfg):
            stacked = params[f"seg_{kind}"]
            seg_windows = jnp.asarray(windows[first:first + count])

            def body(h, xs, _kind=kind):
                p_l, win = xs
                h1 = common.rmsnorm(p_l["ln1"], h, cfg.norm_eps)
                h1 = tp.col_in(h1, "attn")
                # inline gqa_attend so the projected K/V can be captured
                # for the page scatter below
                q, k, v = attention._project_qkv(p_l["attn"], cfg, h1,
                                                 positions)
                ke = attention._expand_kv(k, cfg.q_per_kv)
                ve = attention._expand_kv(v, cfg.q_per_kv)
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, ke).astype(
                    jnp.float32) / math.sqrt(hd)
                scores = common.softcap(scores, cfg.attn_logit_softcap)
                mask = attention.make_attention_mask(s, s, window=win)
                scores = jnp.where(mask[None, None], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(h1.dtype)
                out = jnp.einsum("bhqk,bkhd->bqhd", probs, ve)
                attn_out = common.dense(p_l["attn"]["wo"],
                                        out.reshape(1, s, -1))
                h = h + tp.row_out(attn_out, "attn")
                h2 = common.rmsnorm(p_l["ln2"], h, cfg.norm_eps)
                return h + _ffn(p_l, cfg, _kind, h2), (k[0], v[0])

            x, (ks, vs) = jax.lax.scan(body, x, (stacked, seg_windows))
            ks_all.append(ks)
            vs_all.append(vs)
        k_all = jnp.concatenate(ks_all, axis=0)        # [L, S, kv, hd]
        v_all = jnp.concatenate(vs_all, axis=0)
        x = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        x = common.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = (tp.col_in(x, "vocab") @ model._output_weights(params))[0, 0]
        if gather_logits is not None:
            logits = gather_logits(logits)
        first_tok = jnp.argmax(logits).astype(jnp.int32)
        # scatter the prompt K/V (bucket-padded: static shape per bucket)
        num_l = k_all.shape[0]
        ps = pool["k"].shape[2]
        n_pages = s // ps
        kv = k_all.shape[2]
        new_pool = dict(pool)

        def paged(a, tail):
            return a.reshape((num_l, n_pages, ps) + tail)

        if quantized:
            kq, ksc = attention._quantize_kv(k_all)
            vq, vsc = attention._quantize_kv(v_all)
            new_pool["k"] = pool["k"].at[:, page_ids].set(paged(kq, (kv, hd)))
            new_pool["v"] = pool["v"].at[:, page_ids].set(paged(vq, (kv, hd)))
            new_pool["k_scale"] = pool["k_scale"].at[:, page_ids].set(
                paged(ksc, (kv,)))
            new_pool["v_scale"] = pool["v_scale"].at[:, page_ids].set(
                paged(vsc, (kv,)))
        else:
            new_pool["k"] = pool["k"].at[:, page_ids].set(
                paged(k_all, (kv, hd)).astype(pool["k"].dtype))
            new_pool["v"] = pool["v"].at[:, page_ids].set(
                paged(v_all, (kv, hd)).astype(pool["v"].dtype))
        return first_tok, new_pool

    return prefill


# ---------------------------------------------------------------------------
# Tensor-parallel wrappers (mesh 'model' axis, shard_map)
# ---------------------------------------------------------------------------


def tp_pool_specs(plan, quantized: bool) -> Dict[str, P]:
    """PartitionSpecs for the pool buffers: the kv-head axis shards with
    the attention group (wk/wv columns), everything else is replicated."""
    kv_axis = "model" if plan.attn else None
    payload = P(None, None, None, kv_axis, None)
    specs = {"k": payload, "v": payload}
    if quantized:
        scale = P(None, None, None, kv_axis)
        specs.update(k_scale=scale, v_scale=scale)
    return specs


def build_tp_paged_fns(model_cfg, mesh, params_template, *, quantized: bool,
                       use_kernel: bool = False):
    """shard_map'd (prefill, decode) over the mesh 'model' axis.

    Params arrive FULL (gathered, as checkpoints are stored) and are
    sharded by the returned NamedShardings — the same ``tp_param_specs``
    the training engine uses, so a TP-trained checkpoint needs no
    resharding. Returns ``(prefill, decode, plan, param_shardings,
    pool_shardings)``; vocab-sharded logits are all-gathered in-graph
    before the greedy argmax, so tokens match the replicated path
    exactly.
    """
    from repro.distributed import sharding as sharding_lib
    from repro.distributed.spmd_engine import (MODEL_AXIS, _shard_map,
                                               resolve_tp)
    from repro.models import get_model

    plan = resolve_tp(model_cfg, mesh)
    local_cfg = sharding_lib.tp_local_model_cfg(model_cfg, plan)
    local_model = get_model(local_cfg)
    ctx = tp.TPContext(MODEL_AXIS, plan.attn, plan.ffn, plan.vocab)
    param_specs = sharding_lib.tp_param_specs(plan, params_template)
    pool_specs = tp_pool_specs(plan, quantized)

    def gather_vocab(logits):
        if plan.vocab:
            return jax.lax.all_gather(logits, MODEL_AXIS, axis=-1, tiled=True)
        return logits

    decode_core = build_paged_decode(local_model, quantized=quantized,
                                     use_kernel=use_kernel,
                                     gather_logits=gather_vocab)
    prefill_core = build_paged_prefill(local_model, quantized=quantized,
                                       gather_logits=gather_vocab)

    def decode_body(params, state, pool):
        with tp.tensor_parallel(ctx):
            return decode_core(params, state, pool)

    def prefill_body(params, tokens, meta, pool):
        with tp.tensor_parallel(ctx):
            return prefill_core(params, tokens, meta, pool)

    decode = _shard_map(decode_body, mesh,
                        in_specs=(param_specs, P(), pool_specs),
                        out_specs=(P(), pool_specs))
    prefill = _shard_map(prefill_body, mesh,
                         in_specs=(param_specs, P(), P(), pool_specs),
                         out_specs=(P(), pool_specs))
    param_shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_specs,
        is_leaf=lambda x: isinstance(x, P))
    pool_shardings = {n: NamedSharding(mesh, spec)
                      for n, spec in pool_specs.items()}
    return prefill, decode, plan, param_shardings, pool_shardings
