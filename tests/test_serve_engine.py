"""Serve subsystem: page pool invariants, trace replay, engine-vs-
greedy_generate token parity (fp/int8/sliding-window/MoE), the per-bucket
compile contract, continuous-vs-static scheduling, chaos wiring, and the
checkpoint->serve bridge. The TP decode path is covered by
test_serve_tp.py (subprocess, forced host devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import get_model
from repro.models.attention import _dequantize_kv, _quantize_kv
from repro.serve import (PagePool, PoolConfig, ServeEngine, TraceConfig,
                         bucket_for, make_trace, pages_for, restore_params,
                         supports_paged)
from repro.train.serve_step import bucketed_max_len, greedy_generate


def _trace(n=5, *, seed=0, rate=4.0, max_prompt=12, max_new=6, vocab=128,
           min_new=2):
    return make_trace(TraceConfig(
        num_requests=n, rate=rate, prompt_len_min=2, prompt_len_max=max_prompt,
        max_new_min=min_new, max_new_max=max_new, vocab=vocab, seed=seed))


@pytest.fixture(scope="module")
def qwen():
    cfg = configs.get_smoke_config("qwen3-0.6b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def qwen_engine(qwen):
    cfg, _, params = qwen
    return ServeEngine(cfg, params, num_slots=3, page_size=4,
                      max_prompt_len=12, max_new_cap=8, clock="virtual")


# ---------------------------------------------------------------------------
# Page pool
# ---------------------------------------------------------------------------


def _pool_cfg(**kw):
    base = dict(num_layers=2, kv_heads=2, head_dim=4, num_pages=9,
                page_size=4, num_slots=2, max_pages_per_slot=4,
                quantized=False)
    base.update(kw)
    return PoolConfig(**base)


def test_pool_alloc_free_roundtrip():
    pool = PagePool(_pool_cfg())
    pool.alloc(0, 3)
    row = pool.page_table[0, :3]
    assert (row > 0).all(), "page 0 is the reserved trash page"
    assert len(set(row.tolist())) == 3
    assert (pool.page_table[0, 3:] == 0).all()
    pool.alloc(1, 4)
    assert not pool.can_alloc(2)          # 8 allocatable pages, 7 taken
    pool.free_slot(0)
    assert pool.can_alloc(3)
    assert (pool.page_table[0] == 0).all()


def test_pool_double_alloc_and_exhaustion():
    pool = PagePool(_pool_cfg(max_pages_per_slot=8))
    pool.alloc(0, 2)
    with pytest.raises(ValueError):
        pool.alloc(0, 1)                  # slot already holds pages
    with pytest.raises(MemoryError):
        pool.alloc(1, 8)                  # only 6 pages left
    with pytest.raises(ValueError):
        pool.alloc(1, 9)                  # > max_pages_per_slot


def test_pool_occupancy_accounting():
    pool = PagePool(_pool_cfg())
    pool.alloc(0, 4)
    pool.note_occupancy()
    assert pool.peak_pages == 4
    assert pool.mean_occupancy() == pytest.approx(4 / 8)


def test_pages_for_and_buckets():
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2
    assert bucket_for(3, floor=8) == 8
    assert bucket_for(9, floor=8) == 16
    assert bucket_for(16, floor=8) == 16
    with pytest.raises(ValueError):
        bucket_for(33, floor=8, cap=32)
    assert bucketed_max_len(17) == 32
    with pytest.raises(ValueError):
        bucketed_max_len(0)


# ---------------------------------------------------------------------------
# Traces
# ---------------------------------------------------------------------------


def test_trace_replayable_and_ordered():
    a, b = _trace(8, seed=3), _trace(8, seed=3)
    assert [(r.rid, r.arrival, r.max_new) for r in a] == \
        [(r.rid, r.arrival, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    arr = [r.arrival for r in a]
    assert arr == sorted(arr)
    c = _trace(8, seed=4)
    assert [r.arrival for r in c] != arr


def test_trace_respects_bounds():
    t = _trace(16, max_prompt=9, max_new=5, vocab=32)
    assert all(2 <= r.prompt_len <= 9 for r in t)
    assert all(2 <= r.max_new <= 5 for r in t)
    assert all(0 <= int(r.prompt.max()) < 32 for r in t)


# ---------------------------------------------------------------------------
# Engine vs greedy_generate parity
# ---------------------------------------------------------------------------


def _reference_tokens(model, params, trace):
    out = {}
    for r in trace:
        toks = greedy_generate(model, params, jnp.asarray(r.prompt)[None, :],
                               r.max_new, r.prompt_len + r.max_new + 1)
        out[r.rid] = [int(t) for t in np.asarray(toks)[0]]
    return out


def _assert_parity(cfg, model, params, engine, trace):
    rep = engine.run(trace)
    assert rep.metrics["completed"] == len(trace)
    assert rep.tokens_by_rid() == _reference_tokens(model, params, trace)


def test_engine_matches_greedy_qwen(qwen, qwen_engine):
    cfg, model, params = qwen
    _assert_parity(cfg, model, params, qwen_engine,
                   _trace(5, vocab=cfg.vocab_size))


def test_engine_matches_greedy_sliding_window():
    """gemma3 interleaves sliding-window and global layers: decode past the
    window must mask paged positions exactly like the ring-buffer cache."""
    cfg = configs.get_smoke_config("gemma3-1b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, num_slots=2, page_size=4,
                      max_prompt_len=8, max_new_cap=12, clock="virtual")
    # short prompts + 12 new tokens decode well past the smoke window
    trace = _trace(3, max_prompt=6, max_new=12, min_new=12,
                   vocab=cfg.vocab_size)
    _assert_parity(cfg, model, params, eng, trace)


def test_engine_matches_greedy_moe():
    cfg = configs.get_smoke_config("qwen2-moe-a2.7b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    eng = ServeEngine(cfg, params, num_slots=2, page_size=4,
                      max_prompt_len=8, max_new_cap=5, clock="virtual")
    _assert_parity(cfg, model, params, eng,
                   _trace(3, max_prompt=8, max_new=5, vocab=cfg.vocab_size))


def test_engine_kernel_path_matches_reference(qwen, qwen_engine):
    cfg, _, params = qwen
    eng_k = ServeEngine(cfg, params, num_slots=3, page_size=4,
                        max_prompt_len=12, max_new_cap=8, clock="virtual",
                        use_kernel=True)
    trace = _trace(3, vocab=cfg.vocab_size)
    assert eng_k.run(trace).tokens_by_rid() == \
        qwen_engine.run(trace).tokens_by_rid()


def test_unsupported_family_rejected():
    cfg = configs.get_smoke_config("deepseek-v2-lite-16b")   # MLA cache
    ok, why = supports_paged(cfg)
    assert not ok and why
    with pytest.raises(ValueError, match="paged serving unsupported"):
        ServeEngine(cfg, {}, clock="virtual")


# ---------------------------------------------------------------------------
# The per-bucket compile contract (satellite: no per-shape recompilation)
# ---------------------------------------------------------------------------


def test_mixed_trace_compiles_once_per_bucket(qwen):
    cfg, _, params = qwen
    eng = ServeEngine(cfg, params, num_slots=2, page_size=8,
                      max_prompt_len=16, max_new_cap=4, clock="virtual")
    rng = np.random.RandomState(0)
    reqs = []
    from repro.serve import Request
    for i, plen in enumerate([3, 5, 8, 9, 12, 16, 4, 11]):   # buckets {8,16}
        reqs.append(Request(
            rid=i, arrival=0.0,
            prompt=rng.randint(0, cfg.vocab_size, size=plen).astype(np.int32),
            max_new=3))
    eng.run(reqs)
    assert eng.prefill_compiles == 2      # one per bucket, not per length
    assert eng.decode_compiles == 1
    eng.run(reqs)                         # replay: everything cached
    assert eng.prefill_compiles == 2
    assert eng.decode_compiles == 1


def test_greedy_generate_bucketed_cache(qwen):
    """The toy path satellite: mixed max_len requests share one power-of-
    two cache bucket, and bucketing doesn't change the tokens."""
    cfg, model, params = qwen
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 5), 0,
                                cfg.vocab_size)
    a = greedy_generate(model, params, prompt, 4, 11)
    b = greedy_generate(model, params, prompt, 4, 11, bucket=False)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert bucketed_max_len(11) == 16


# ---------------------------------------------------------------------------
# Int8 paged KV (satellite)
# ---------------------------------------------------------------------------


def test_int8_page_roundtrip_error_bound():
    """Per-(position, head) scales: dequantization error is bounded by half
    a quantization step of the stored (f16) scale, with a hair of slack
    for the scale's own storage rounding."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 2, 8)) * \
        jnp.asarray([0.1, 1.0, 10.0])[:, None, None, None]
    q, scale = _quantize_kv(x)
    assert q.dtype == jnp.int8
    assert scale.dtype == jnp.float16
    deq = _dequantize_kv(q, scale, jnp.float32)
    err = jnp.abs(deq - x)
    step = scale.astype(jnp.float32)[..., None]
    assert bool(jnp.all(err <= 0.52 * step + 1e-8))


def test_int8_token_parity_64_steps(qwen):
    """Greedy decode with the int8 paged pool matches fp token-for-token
    over >= 64 steps (qwen3-0.6b smoke)."""
    cfg, _, params = qwen
    from repro.serve import Request
    rng = np.random.RandomState(5)
    reqs = [Request(rid=0, arrival=0.0,
                    prompt=rng.randint(0, cfg.vocab_size, size=6).astype(
                        np.int32),
                    max_new=64)]
    kw = dict(num_slots=1, page_size=8, max_prompt_len=8, max_new_cap=64,
              clock="virtual")
    fp = ServeEngine(cfg, params, **kw).run(reqs)
    q8 = ServeEngine(cfg, params, cache_int8=True, **kw).run(reqs)
    fp_toks, q8_toks = fp.tokens_by_rid()[0], q8.tokens_by_rid()[0]
    assert len(fp_toks) == 64
    assert fp_toks == q8_toks


# ---------------------------------------------------------------------------
# Scheduling policies
# ---------------------------------------------------------------------------


def test_static_policy_same_tokens_more_steps(qwen, qwen_engine):
    cfg, _, _ = qwen
    trace = _trace(6, rate=100.0, max_new=8, vocab=cfg.vocab_size)
    cont = qwen_engine.run(trace, policy="continuous")
    stat = qwen_engine.run(trace, policy="static")
    assert cont.tokens_by_rid() == stat.tokens_by_rid()
    assert stat.metrics["decode_steps"] >= cont.metrics["decode_steps"]
    # with more requests than slots and mixed lengths, head-of-line
    # blocking costs the static policy strictly more decode steps
    assert stat.metrics["decode_steps"] > cont.metrics["decode_steps"]


def test_request_validation(qwen_engine):
    from repro.serve import Request
    big = Request(rid=0, arrival=0.0,
                  prompt=np.zeros(99, np.int32), max_new=2)
    with pytest.raises(ValueError, match="prompt_len"):
        qwen_engine.run([big])
    greedy = Request(rid=0, arrival=0.0,
                     prompt=np.zeros(4, np.int32), max_new=999)
    with pytest.raises(ValueError, match="max_new"):
        qwen_engine.run([greedy])
    with pytest.raises(ValueError, match="policy"):
        qwen_engine.run(_trace(1), policy="adaptive")


def test_engine_rejects_unknown_knobs(qwen):
    cfg, _, params = qwen
    with pytest.raises(ValueError, match="clock"):
        ServeEngine(cfg, params, clock="lamport")
    with pytest.raises(ValueError, match="fault"):
        ServeEngine(cfg, params, clock="virtual", faults="crash@2")


# ---------------------------------------------------------------------------
# Chaos wiring (satellite): p99 degrades, nothing is lost
# ---------------------------------------------------------------------------


def test_chaos_degrades_p99_but_loses_nothing(qwen, qwen_engine):
    cfg, _, params = qwen
    trace = _trace(6, rate=100.0, vocab=cfg.vocab_size)
    base = qwen_engine.run(trace)
    chaotic = ServeEngine(cfg, params, num_slots=3, page_size=4,
                          max_prompt_len=12, max_new_cap=8, clock="virtual",
                          faults="slowdown@2,preempt@6")
    rep = chaotic.run(trace)
    assert rep.metrics["completed"] == len(trace)          # nothing lost
    assert rep.tokens_by_rid() == base.tokens_by_rid()     # greedy replay
    assert rep.metrics["p99_latency"] > base.metrics["p99_latency"]
    assert rep.metrics["preemptions"] == 1
    assert {e["event"] for e in rep.events} == {"slowdown", "preempt"}
    assert max(c.preemptions for c in rep.completed) >= 1


# ---------------------------------------------------------------------------
# Checkpoint -> serve bridge (satellite)
# ---------------------------------------------------------------------------


def test_train_then_serve_roundtrip(tmp_path):
    from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                    OptimizerConfig, ShapeConfig, TrainConfig)
    from repro.train.loop import Trainer

    cfg = configs.get_smoke_config("qwen3-0.6b")
    tcfg = TrainConfig(
        model=cfg, shape=ShapeConfig("tiny", 16, 8, "train"),
        aggregation=AggregationConfig(strategy="full_sync", num_workers=2),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False,
                                  ema_decay=0.9),
        checkpoint=CheckpointConfig(directory=str(tmp_path), every_steps=2),
        log_every=10)
    tr = Trainer(tcfg)
    tr.init_state()
    tr.run(4)
    tr.save_checkpoint()

    params, manifest = restore_params(str(tmp_path), cfg)
    assert manifest["step"] >= 4
    trained = np.asarray(jax.tree_util.tree_leaves(tr.params)[0])
    served = np.asarray(jax.tree_util.tree_leaves(params)[0])
    np.testing.assert_array_equal(trained, served)

    eng = ServeEngine(cfg, params, num_slots=2, page_size=4,
                      max_prompt_len=8, max_new_cap=4, clock="virtual")
    rep = eng.run(_trace(3, max_prompt=8, max_new=4, vocab=cfg.vocab_size))
    assert rep.metrics["completed"] == 3

    ema_params, _ = restore_params(str(tmp_path), cfg, use_ema=True)
    ema_leaf = np.asarray(jax.tree_util.tree_leaves(ema_params)[0])
    assert not np.array_equal(ema_leaf, served)            # ema != raw


def test_restore_missing_checkpoint(tmp_path):
    cfg = configs.get_smoke_config("qwen3-0.6b")
    with pytest.raises(FileNotFoundError):
        restore_params(str(tmp_path / "nope"), cfg)


# ---------------------------------------------------------------------------
# Graceful degradation: overflow/exhaustion reject, never wedge
# ---------------------------------------------------------------------------


def test_strict_capacity_still_raises_by_default(qwen):
    cfg, _, params = qwen
    with pytest.raises(ValueError, match="strict_capacity=False"):
        ServeEngine(cfg, params, num_slots=2, page_size=4,
                    max_prompt_len=12, max_new_cap=8, num_pages=3)


def test_undersized_pool_rejects_long_prompts_structured(qwen):
    """strict_capacity=False permits a pool too small for the longest
    admissible request; those requests are rejected with a structured
    reason while everything that fits still completes."""
    cfg, _, params = qwen
    eng = ServeEngine(cfg, params, num_slots=2, page_size=4,
                      max_prompt_len=16, max_new_cap=8, num_pages=4,
                      strict_capacity=False, clock="virtual")
    assert eng.page_capacity == 3
    short = _trace(3, max_prompt=6, max_new=4)      # needs <= 3 pages
    long = make_trace(TraceConfig(
        num_requests=2, rate=4.0, prompt_len_min=13, prompt_len_max=16,
        max_new_min=4, max_new_max=8, vocab=128, seed=7))
    trace = sorted(short + [type(r)(r.rid + 100, r.arrival, r.prompt,
                                    r.max_new) for r in long],
                   key=lambda r: (r.arrival, r.rid))
    rep = eng.run(trace)
    assert rep.metrics["completed"] == 3
    assert rep.metrics["rejected"] == 2
    assert rep.metrics["rejected_pool_exhausted"] == 2
    assert all(r["reason"] == "pool_exhausted" and r["rid"] >= 100
               for r in rep.rejected)
    assert {c.rid for c in rep.completed} == {r.rid for r in short}


def test_queue_overflow_rejects_structured(qwen):
    cfg, _, params = qwen
    eng = ServeEngine(cfg, params, num_slots=1, page_size=4,
                      max_prompt_len=8, max_new_cap=8, max_queue=2,
                      clock="virtual")
    trace = _trace(8, rate=1000.0, max_prompt=8, min_new=6, max_new=8)
    rep = eng.run(trace)
    over = [r for r in rep.rejected if r["reason"] == "queue_overflow"]
    assert over and rep.metrics["rejected_queue_overflow"] == len(over)
    assert rep.metrics["completed"] + rep.metrics["rejected"] == len(trace)
    assert rep.metrics["completed"] >= 1
    # rejection is part of the deterministic virtual-time replay
    rep2 = eng.run(trace)
    assert rep2.rejected == rep.rejected
