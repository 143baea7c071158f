"""Attention token mixers: GQA (full / sliding window), MLA, KV-cache decode.

Three execution paths:
  * ``gqa_attend``      — dense masked attention (smoke / short sequences)
  * ``gqa_attend_chunked`` — flash-style KV-chunked scan (long sequences;
    O(S·W) memory for window W, never materializes the full score matrix)
  * ``gqa_decode``      — single-token decode against a KV cache; works with
    batch-sharded or sequence-sharded (SP) caches — the softmax reductions
    lower to psums under GSPMD when the cache's S axis is mesh-sharded.

MLA (DeepSeek-V2) is implemented in decomposed form and caches only the
compressed latent + rope key (its memory win) at decode time.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import Params


# ---------------------------------------------------------------------------
# GQA projection parameters
# ---------------------------------------------------------------------------


def gqa_init(key, cfg, dtype=jnp.float32) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = common.split_keys(key, 4)
    p = {
        "wq": common.dense_init(ks[0], d, h * hd, dtype, bias=cfg.use_bias),
        "wk": common.dense_init(ks[1], d, kv * hd, dtype, bias=cfg.use_bias),
        "wv": common.dense_init(ks[2], d, kv * hd, dtype, bias=cfg.use_bias),
        "wo": common.dense_init(ks[3], h * hd, d, dtype, bias=cfg.use_bias,
                                std=1.0 / math.sqrt(h * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = common.rmsnorm_init(hd, dtype)
        p["k_norm"] = common.rmsnorm_init(hd, dtype)
    return p


def _project_qkv(params: Params, cfg, x: jnp.ndarray, positions: jnp.ndarray):
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = common.dense(params["wq"], x).reshape(b, s, h, hd)
    k = common.dense(params["wk"], x).reshape(b, s, kv, hd)
    v = common.dense(params["wv"], x).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        # qk-norm scales are replicated but applied to head-SHARDED q/k
        # under manual TP: tp.shared_param assembles their full gradient
        # from the per-shard (local-heads-only) partial cotangents
        from repro.distributed import tp
        q = common.rmsnorm(tp.shared_param(params["q_norm"], "attn"), q,
                           cfg.norm_eps)
        k = common.rmsnorm(tp.shared_param(params["k_norm"], "attn"), k,
                           cfg.norm_eps)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _expand_kv(k: jnp.ndarray, q_per_kv: int) -> jnp.ndarray:
    if q_per_kv == 1:
        return k
    return jnp.repeat(k, q_per_kv, axis=2)


def _window_ok(diff: jnp.ndarray, window) -> jnp.ndarray:
    """True where `diff` (q_pos - k_pos) is within the lookback window.

    ``window`` may be a Python int or a traced scalar (per-layer windows fed
    through ``lax.scan`` — gemma3's 5:1 local:global pattern). window<=0
    means unlimited.
    """
    window = jnp.asarray(window, jnp.int32)
    return jnp.where(window > 0, diff < window, True)


def make_attention_mask(s_q: int, s_kv: int, *, causal: bool = True,
                        window=0, q_offset: int = 0) -> jnp.ndarray:
    """[s_q, s_kv] boolean mask. window>0 limits lookback to `window` tokens."""
    qpos = jnp.arange(s_q) + q_offset
    kpos = jnp.arange(s_kv)
    diff = qpos[:, None] - kpos[None, :]
    mask = diff >= 0 if causal else jnp.ones((s_q, s_kv), bool)
    return mask & _window_ok(diff, window)


# ---------------------------------------------------------------------------
# Dense path
# ---------------------------------------------------------------------------


def gqa_attend(params: Params, cfg, x: jnp.ndarray, positions: jnp.ndarray,
               *, window=0) -> jnp.ndarray:
    """Full-sequence attention. x: [B, S, d] -> [B, S, d]."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    k = _expand_kv(k, cfg.q_per_kv)
    v = _expand_kv(v, cfg.q_per_kv)
    hd = cfg.resolved_head_dim
    # the attention core, scores to weighted values (what a flash kernel
    # replaces), named on the device trace; the projections stay outside
    with jax.named_scope("attention"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
        scores = common.softcap(scores, cfg.attn_logit_softcap)
        mask = make_attention_mask(s, s, window=window)
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return common.dense(params["wo"], out.reshape(b, s, -1))


# ---------------------------------------------------------------------------
# Chunked (flash-style) path: scan over KV chunks with running softmax stats
# ---------------------------------------------------------------------------


def chunked_attention_core(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window=0, softcap: float = 0.0,
                           q_chunk: int = 2048,
                           kv_chunk: int = 2048) -> jnp.ndarray:
    """Blocked attention on projected q/k/v [B, S, H, D] (KV already
    head-expanded): O(q_chunk·kv_chunk) live scores instead of O(S²).

    Scans query chunks (outer) and KV chunks (inner) keeping running
    (max, sum, weighted-V) accumulators — the standard online-softmax
    recurrence; this is the jnp twin of the Pallas flash kernel in
    ``repro.kernels.flash_attention``. Used by GQA (rotary), whisper
    (learned positions), and long cross-attention.
    """
    b, s, h, hd = q.shape
    s_kv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, s_kv)
    nq = -(-s // q_chunk)
    nk = -(-s_kv // kv_chunk)
    pad_q = nq * q_chunk - s
    pad_k = nk * kv_chunk - s_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    from repro.distributed.context import constrain_dims
    qs = q.reshape(b, nq, q_chunk, h, hd).transpose(1, 0, 3, 2, 4)  # [nq,B,H,qc,hd]
    ks = k.reshape(b, nk, kv_chunk, h, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(b, nk, kv_chunk, h, hd).transpose(1, 0, 3, 2, 4)
    chunk_kinds = (None, "batch", "heads", None, None)
    qs = constrain_dims(qs, chunk_kinds)
    ks = constrain_dims(ks, chunk_kinds)
    vs = constrain_dims(vs, chunk_kinds)

    def q_step(_, qi_q):
        qi, qc = qi_q
        q_off = qi * q_chunk

        def kv_step(carry, ki_kv):
            m, l, acc = carry
            ki, kc, vc = ki_kv
            scores = jnp.einsum("bhqd,bhkd->bhqk", qc, kc).astype(jnp.float32) * scale
            scores = common.softcap(scores, softcap)
            qpos = q_off + jnp.arange(q_chunk)
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            diff = qpos[:, None] - kpos[None, :]
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask = (diff >= 0) & _window_ok(diff, window)
            mask = mask & (kpos < s_kv)[None, :]        # kv padding
            scores = jnp.where(mask[None, None], scores, -1e30)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, constrain_dims(out.astype(qc.dtype),
                                    ("batch", "heads", None, None))

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))  # [nq,B,H,qc,hd]
    return outs.transpose(1, 0, 3, 2, 4).reshape(b, nq * q_chunk, h, hd)[:, :s]


def gqa_attend_chunked(params: Params, cfg, x: jnp.ndarray, positions: jnp.ndarray,
                       *, window=0, q_chunk: int = 2048,
                       kv_chunk: int = 2048) -> jnp.ndarray:
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    k = _expand_kv(k, cfg.q_per_kv)
    v = _expand_kv(v, cfg.q_per_kv)
    with jax.named_scope("attention"):          # as in gqa_attend
        out = chunked_attention_core(q, k, v, causal=True, window=window,
                                     softcap=cfg.attn_logit_softcap,
                                     q_chunk=q_chunk, kv_chunk=kv_chunk)
    return common.dense(params["wo"], out.reshape(b, s, -1))


# ---------------------------------------------------------------------------
# Decode path (KV cache)
# ---------------------------------------------------------------------------


def gqa_init_cache(cfg, batch: int, max_len: int, dtype) -> Dict[str, jnp.ndarray]:
    """KV cache. dtype=jnp.int8 selects the quantized layout: int8 payload
    + per-(position, head) f16 scales (KIVI/KVQuant-style per-token
    scaling) — halves decode's dominant HBM term vs bf16 at <1% logit
    error (tests/test_quant_cache.py)."""
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if dtype == jnp.int8:
        return {
            "k": jnp.zeros((batch, max_len, kv, hd), jnp.int8),
            "v": jnp.zeros((batch, max_len, kv, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, max_len, kv), jnp.float16),
            "v_scale": jnp.zeros((batch, max_len, kv), jnp.float16),
        }
    return {
        "k": jnp.zeros((batch, max_len, kv, hd), dtype),
        "v": jnp.zeros((batch, max_len, kv, hd), dtype),
    }


def _quantize_kv(x: jnp.ndarray):
    """x: [B, 1, kv, hd] -> (int8 payload, f16 per-(pos,head) scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def gqa_decode(params: Params, cfg, x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
               cache_len: jnp.ndarray, *, window=0, write_pos=None,
               update_cache: bool = True) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode. x: [B, 1, d]; cache k/v: [B, S, kv, hd].

    ``cache_len`` is the *true* sequence position of the new token (drives
    RoPE and validity). ``write_pos`` is where its K/V lands in the buffer —
    defaults to cache_len; pass ``cache_len % size`` for ring-buffer local
    (sliding-window) caches, in which case every buffer slot is valid once
    wrapped. The score computation is written with explicit reductions so a
    sequence-sharded cache lowers to partial-softmax psums (sequence
    parallelism).
    """
    b = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    if write_pos is None:
        write_pos = cache_len
    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q, k_new, v_new = _project_qkv(params, cfg, x, pos)
    quantized = cache["k"].dtype == jnp.int8
    if update_cache:
        new_cache = dict(cache)
        if quantized:
            kq, ks = _quantize_kv(k_new)
            vq, vs = _quantize_kv(v_new)
            new_cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], kq, (0, write_pos, 0, 0))
            new_cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], vq, (0, write_pos, 0, 0))
            new_cache["k_scale"] = jax.lax.dynamic_update_slice(
                cache["k_scale"], ks, (0, write_pos, 0))
            new_cache["v_scale"] = jax.lax.dynamic_update_slice(
                cache["v_scale"], vs, (0, write_pos, 0))
        else:
            new_cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], k_new.astype(cache["k"].dtype), (0, write_pos, 0, 0))
            new_cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], v_new.astype(cache["v"].dtype), (0, write_pos, 0, 0))
        cache = new_cache
    if quantized:
        k = _dequantize_kv(cache["k"], cache["k_scale"], x.dtype)
        v = _dequantize_kv(cache["v"], cache["v_scale"], x.dtype)
    else:
        k, v = cache["k"], cache["v"]
    s = k.shape[1]
    q = q.reshape(b, h, hd)
    # grouped: [B, kv, q_per_kv, hd]
    qg = q.reshape(b, kv, cfg.q_per_kv, hd)
    scores = jnp.einsum("bgqd,bsgd->bgqs", qg, k).astype(jnp.float32) / math.sqrt(hd)
    scores = common.softcap(scores, cfg.attn_logit_softcap)
    kpos = jnp.arange(s)
    valid = (kpos <= cache_len) & _window_ok(cache_len - kpos, window)
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgqs,bsgd->bgqd", probs.astype(v.dtype), v)
    out = out.reshape(b, 1, h * hd)
    return common.dense(params["wo"], out), cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype=jnp.float32) -> Params:
    d, h = cfg.d_model, cfg.num_heads
    m = cfg.mla
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    ks = common.split_keys(key, 6)
    p = {
        # query: full-rank (q_lora_rank==0) or low-rank
        "wq": common.dense_init(ks[0], d, h * qk_dim, dtype),
        # compressed kv latent + shared rope key
        "wkv_a": common.dense_init(ks[1], d, m.kv_lora_rank + m.qk_rope_dim, dtype),
        "kv_norm": common.rmsnorm_init(m.kv_lora_rank, dtype),
        "wkv_b": common.dense_init(ks[2], m.kv_lora_rank,
                                   h * (m.qk_nope_dim + m.v_head_dim), dtype),
        "wo": common.dense_init(ks[3], h * m.v_head_dim, d, dtype,
                                std=1.0 / math.sqrt(h * m.v_head_dim)),
    }
    if m.q_lora_rank:
        p["wq_a"] = common.dense_init(ks[4], d, m.q_lora_rank, dtype)
        p["q_norm"] = common.rmsnorm_init(m.q_lora_rank, dtype)
        p["wq_b"] = common.dense_init(ks[5], m.q_lora_rank, h * qk_dim, dtype)
        del p["wq"]
    return p


def _mla_qkv(params: Params, cfg, x: jnp.ndarray, positions: jnp.ndarray):
    b, s, _ = x.shape
    h = cfg.num_heads
    m = cfg.mla
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    if "wq_a" in params:
        q = common.dense(params["wq_b"],
                         common.rmsnorm(params["q_norm"],
                                        common.dense(params["wq_a"], x), cfg.norm_eps))
    else:
        q = common.dense(params["wq"], x)
    q = q.reshape(b, s, h, qk_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = common.apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = common.dense(params["wkv_a"], x)                       # [B,S,rank+rope]
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = common.rmsnorm(params["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = common.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # [B,S,1,rope]
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand_kv(params: Params, cfg, c_kv: jnp.ndarray):
    b, s, _ = c_kv.shape
    h = cfg.num_heads
    m = cfg.mla
    kv = common.dense(params["wkv_b"], c_kv).reshape(b, s, h, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_dim], axis=-1)
    return k_nope, v


def mla_attend(params: Params, cfg, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    b, s, _ = x.shape
    h = cfg.num_heads
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, cfg, x, positions)
    k_nope, v = _mla_expand_kv(params, cfg, c_kv)
    if s > 8192:
        # long sequences: fold (nope ‖ rope) into one head dim and run the
        # blocked online-softmax core — the dense path materializes a full
        # [S, S] score matrix (observed 4.3 GB at 32k prefill). v is padded
        # to the qk width and sliced back (the core is square in D).
        qk = jnp.concatenate([q_nope, q_rope], axis=-1)        # [B,S,H,nope+rope]
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_dim))], -1)
        d_qk = m.qk_nope_dim + m.qk_rope_dim
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, d_qk - m.v_head_dim)))
        out = chunked_attention_core(qk, kk, v_pad, causal=True)
        out = out[..., :m.v_head_dim]
        return common.dense(params["wo"], out.reshape(b, s, -1))
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkld->bhqk", q_rope,
                           jnp.broadcast_to(k_rope, (b, s, 1, m.qk_rope_dim)))
              ).astype(jnp.float32) * scale
    mask = make_attention_mask(s, s)
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return common.dense(params["wo"], out.reshape(b, s, -1))


def mla_init_cache(cfg, batch: int, max_len: int, dtype) -> Dict[str, jnp.ndarray]:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
    }


def mla_decode(params: Params, cfg, x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
               cache_len: jnp.ndarray,
               update_cache: bool = True) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """MLA decode caching only (c_kv, k_rope) — the latent-cache memory win."""
    b = x.shape[0]
    h = cfg.num_heads
    m = cfg.mla
    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q_nope, q_rope, c_new, kr_new = _mla_qkv(params, cfg, x, pos)
    if update_cache:
        cache = {
            "c_kv": jax.lax.dynamic_update_slice(
                cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, cache_len, 0)),
            "k_rope": jax.lax.dynamic_update_slice(
                cache["k_rope"], kr_new[:, :, 0].astype(cache["k_rope"].dtype),
                (0, cache_len, 0)),
        }
    c_kv, k_rope = cache["c_kv"], cache["k_rope"]
    s = c_kv.shape[1]
    # absorb wkv_b into the query (decode-time trick): score_nope =
    # (q_nope @ Wb_k^T) @ c_kv^T  — avoids expanding K per head over S.
    wkv_b = params["wkv_b"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    wb_k = wkv_b[..., :m.qk_nope_dim]                              # [rank,h,nope]
    wb_v = wkv_b[..., m.qk_nope_dim:]                              # [rank,h,v]
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, wb_k)             # [B,1,h,rank]
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_abs, c_kv)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope)).astype(jnp.float32) * scale
    valid = jnp.arange(s) <= cache_len
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", probs.astype(c_kv.dtype), c_kv)  # latent ctx
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, wb_v).reshape(b, 1, -1)
    return common.dense(params["wo"], out), cache
