"""The serve engine: continuous batching over the paged KV cache.

One engine owns two jitted functions (``paged_model``): a bucketed prefill
(compiled once per power-of-two prompt bucket) and a single decode step
over all ``num_slots`` decode slots (compiled once). The host loop is the
scheduler: it admits requests from the open-loop arrival queue whenever a
slot AND enough pool pages are free (continuous batching), or only when
the whole batch has drained (``policy="static"``, the toy baseline), and
evicts at decode-step granularity — on completion, and under the chaos
engine's ``preempt`` fault, which throws every in-flight request back to
the queue (recomputed on readmission; greedy decode makes the retry
token-identical, so preemption costs latency, never correctness).

Two clocks: ``"wall"`` (real seconds — the benchmark path; chaos
slowdowns stretch each decode step by sleeping the residual) and
``"virtual"`` (deterministic units per step — the test path, where p99
assertions must not depend on host speed).

``restore_params`` is the checkpoint→serve bridge: it pulls just the
``params`` (or ``ema``) subtree of a training checkpoint through
``train/checkpoint.py``'s verified restore — replicated, TP-sharded and
sim checkpoints are all stored gathered, so one template fits all three.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as faults_lib
from repro.models import get_model
from repro.obs.trace import as_tracer
from repro.serve import pages as pages_lib
from repro.serve import trace as trace_lib
from repro.serve.paged_model import (build_paged_decode, build_paged_prefill,
                                     build_tp_paged_fns, supports_paged)

SERVE_FAULT_KINDS = ("slowdown", "preempt")
SERVE_POLICIES = ("continuous", "static")


@dataclasses.dataclass
class CompletedRequest:
    rid: int
    arrival: float
    admitted: float
    first_token: float
    finish: float
    prompt_len: int
    tokens: List[int]
    preemptions: int = 0

    @property
    def latency(self) -> float:
        return self.finish - self.arrival

    @property
    def ttft(self) -> float:
        return self.first_token - self.arrival


@dataclasses.dataclass
class ServeReport:
    policy: str
    completed: List[CompletedRequest]
    metrics: Dict[str, float]
    events: List[Dict[str, Any]]
    # graceful-degradation records: requests the engine refused instead of
    # wedging on — each entry {"rid", "reason", "t"} (reasons:
    # "queue_overflow", "pool_exhausted")
    rejected: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def tokens_by_rid(self) -> Dict[int, List[int]]:
        return {c.rid: list(c.tokens) for c in self.completed}


class _Slot:
    __slots__ = ("req", "admitted", "first_token", "tokens", "last_token",
                 "length", "produced", "preemptions")

    def __init__(self, req, admitted, first_token, first_tok_id, preemptions):
        self.req = req
        self.admitted = admitted
        self.first_token = first_token
        self.tokens = [first_tok_id]
        self.last_token = first_tok_id
        self.length = req.prompt_len      # positions with K/V written
        self.produced = 1                 # prefill samples the first token
        self.preemptions = preemptions


class ServeEngine:
    """Continuous-batching inference over a paged, optionally int8, pool."""

    def __init__(self, model_cfg, params, *, num_slots: int = 4,
                 page_size: int = 8, max_prompt_len: int = 32,
                 max_new_cap: int = 32, num_pages: Optional[int] = None,
                 cache_int8: bool = False, mesh_model: int = 1,
                 use_kernel: bool = False,
                 clock: str = "wall", step_time: float = 1.0,
                 prefill_time: float = 1.0, faults: Optional[str] = None,
                 fault_horizon: int = 256, fault_seed: int = 0,
                 eos_id: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 strict_capacity: bool = True,
                 slo=None, tracer=None, metrics=None):
        ok, why = supports_paged(model_cfg)
        if not ok:
            raise ValueError(f"paged serving unsupported: {why}")
        if clock not in ("wall", "virtual"):
            raise ValueError(f"clock must be 'wall' or 'virtual' (got {clock})")
        self.cfg = model_cfg
        self.model = get_model(model_cfg)
        # engine-level SLO admission (serve/slo.py SLOConfig): under
        # clock='wall' the gate controls on *measured* request latency —
        # the wall-clock SLO loop; under clock='virtual' it stays
        # replay-deterministic. tracer/metrics are pure observability.
        self.slo_cfg = slo
        self.tracer = as_tracer(tracer)
        self.registry = metrics
        self._prefill_s = 0.0
        self._decode_s = 0.0
        self.clock = clock
        self.step_time = step_time
        self.prefill_time = prefill_time
        self.eos_id = eos_id
        self.page_size = page_size
        self.max_queue = max_queue
        self.max_bucket = trace_lib.bucket_for(max_prompt_len,
                                               floor=page_size, cap=1 << 30)
        self.max_new_cap = max_new_cap
        max_pages = pages_lib.pages_for(self.max_bucket + max_new_cap,
                                        page_size)
        if num_pages is None:
            num_pages = num_slots * max_pages + 1
        if strict_capacity and num_pages - 1 < max_pages:
            # strict (default): every request the caps admit must fit an
            # idle pool. strict_capacity=False permits deliberately
            # undersized pools — unfittable requests are then *rejected*
            # with a structured reason at admission, never wedged on.
            raise ValueError(
                f"num_pages={num_pages} cannot hold even one request "
                f"({max_pages} pages + the trash page); pass "
                f"strict_capacity=False to degrade to rejection instead")
        self.pool_cfg = pages_lib.PoolConfig(
            num_layers=model_cfg.num_layers,
            kv_heads=model_cfg.num_kv_heads,
            head_dim=model_cfg.resolved_head_dim,
            num_pages=num_pages, page_size=page_size, num_slots=num_slots,
            max_pages_per_slot=max_pages, quantized=cache_int8)
        self.mesh_model = mesh_model
        if mesh_model > 1:
            from repro.launch.mesh import make_host_mesh
            if mesh_model > jax.device_count():
                raise ValueError(
                    f"mesh_model={mesh_model} needs {mesh_model} devices "
                    f"but only {jax.device_count()} present (force host "
                    f"devices with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=N)")
            self.mesh = make_host_mesh(1, mesh_model)
            template = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
            prefill, decode, plan, param_sh, pool_sh = build_tp_paged_fns(
                model_cfg, self.mesh, template, quantized=cache_int8,
                use_kernel=use_kernel)
            self.params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(jnp.asarray(x), s),
                params, param_sh)
            self.tp_plan = plan
            self._pool_shardings = pool_sh
        else:
            decode = build_paged_decode(self.model, quantized=cache_int8,
                                        use_kernel=use_kernel)
            prefill = build_paged_prefill(self.model, quantized=cache_int8)
            self.params = jax.tree_util.tree_map(jnp.asarray, params)
            self.tp_plan = None
            self._pool_shardings = None
        self._decode = jax.jit(decode)
        self._prefill = jax.jit(prefill)
        self.fault_plan = None
        if faults:
            plan_f = faults_lib.plan_from_spec(
                faults, num_steps=fault_horizon, num_workers=num_slots,
                seed=fault_seed)
            bad = sorted({e.kind for e in plan_f.events
                          if e.kind not in SERVE_FAULT_KINDS})
            if bad:
                raise ValueError(
                    f"serve wires only {SERVE_FAULT_KINDS} of the fault "
                    f"taxonomy (decode is lockstep — no per-worker crash/"
                    f"restart/ckpt_io surface); got {bad}")
            self.fault_plan = plan_f

    # -- compile counters (the bucket contract) -------------------------------

    @property
    def prefill_compiles(self) -> int:
        return int(self._prefill._cache_size())

    @property
    def decode_compiles(self) -> int:
        return int(self._decode._cache_size())

    # -- clock ----------------------------------------------------------------

    def _now(self) -> float:
        if self.clock == "wall":
            return time.perf_counter() - self._t0
        return self._vnow

    def _advance_to(self, t: float) -> None:
        if self.clock == "wall":
            dt = t - self._now()
            if dt > 0:
                time.sleep(dt)
        else:
            self._vnow = max(self._vnow, t)

    def _advance_decode(self, elapsed: float, factor: float) -> None:
        if self.clock == "wall":
            extra = elapsed * (factor - 1.0)
            if extra > 0:
                time.sleep(extra)
        else:
            self._vnow += self.step_time * factor

    def _advance_prefill(self, elapsed: float) -> None:
        if self.clock == "virtual":
            self._vnow += self.prefill_time

    # -- request geometry ------------------------------------------------------

    def validate_request(self, r: trace_lib.Request) -> None:
        if r.prompt_len > self.max_bucket:
            raise ValueError(f"request {r.rid}: prompt_len "
                             f"{r.prompt_len} > bucket cap "
                             f"{self.max_bucket}")
        if not 1 <= r.max_new <= self.max_new_cap:
            raise ValueError(f"request {r.rid}: max_new {r.max_new} "
                             f"outside [1, {self.max_new_cap}]")

    def pages_needed(self, req: trace_lib.Request) -> int:
        """Pages a request holds for its whole lifetime: the prefill
        scatter needs the full bucket, the decode tail the rest."""
        return max(
            trace_lib.bucket_for(req.prompt_len, floor=self.page_size,
                                 cap=self.max_bucket) // self.page_size,
            pages_lib.pages_for(req.prompt_len + req.max_new,
                                self.page_size))

    @property
    def page_capacity(self) -> int:
        """Most pages any single request can ever be granted."""
        return min(self.pool_cfg.num_pages - 1,
                   self.pool_cfg.max_pages_per_slot)

    # -- the serving loop -----------------------------------------------------

    def run(self, trace: Sequence[trace_lib.Request],
            policy: str = "continuous") -> ServeReport:
        if policy not in SERVE_POLICIES:
            raise ValueError(f"policy must be one of {SERVE_POLICIES}")
        for r in trace:
            self.validate_request(r)
        pool = pages_lib.PagePool(self.pool_cfg, dtype=self.model.dtype,
                                  shardings=self._pool_shardings)
        self._bufs = pool.buffers
        pending = collections.deque(
            sorted(trace, key=lambda r: (r.arrival, r.rid)))
        queue: collections.deque = collections.deque()
        active: Dict[int, _Slot] = {}
        free_slots = list(range(self.pool_cfg.num_slots - 1, -1, -1))
        completed: List[CompletedRequest] = []
        events: List[Dict[str, Any]] = []
        rejected: List[Dict[str, Any]] = []
        preempt_counts: Dict[int, int] = {}
        from repro.serve.slo import SLOController
        slo = SLOController(self.slo_cfg) if self.slo_cfg else None
        held: List[trace_lib.Request] = []     # SLO "queue" holding pen
        self._prefill_s = 0.0
        self._decode_s = 0.0
        wall_t0 = time.perf_counter()
        self._t0 = time.perf_counter()
        self._vnow = 0.0
        step_idx = 0
        slow_factor, slow_until = 1.0, -1

        def complete(slot: int, st: _Slot, now: float) -> None:
            if slo is not None:
                slo.observe(now - st.req.arrival)
            pool.free_slot(slot)
            free_slots.append(slot)
            completed.append(CompletedRequest(
                rid=st.req.rid, arrival=st.req.arrival, admitted=st.admitted,
                first_token=st.first_token, finish=now,
                prompt_len=st.req.prompt_len, tokens=st.tokens,
                preemptions=st.preemptions))

        def reject(req: trace_lib.Request, reason: str, now: float) -> None:
            rejected.append({"rid": req.rid, "reason": reason,
                             "t": float(now)})
            events.append({"event": "reject", "rid": req.rid,
                           "reason": reason, "step": step_idx})

        while pending or queue or active or held:
            now = self._now()
            while pending and pending[0].arrival <= now:
                req = pending.popleft()
                if (self.max_queue is not None
                        and len(queue) >= self.max_queue):
                    # admission-queue overflow: shed at the door with a
                    # structured reason (requeued preemptions bypass this
                    # — they re-enter at the queue head, never shed)
                    reject(req, "queue_overflow", now)
                    continue
                verdict = slo.admit(now) if slo is not None else "admit"
                if verdict == "shed":
                    reject(req, "slo_shed", now)
                elif verdict == "queue":
                    held.append(req)
                else:
                    queue.append(req)
            if held and not slo.violating:
                # gate re-opened under hysteresis: release the pen in
                # arrival order behind whatever is already queued
                queue.extend(held)
                held.clear()
            elif held and not queue and not active and not pending:
                # gate shut but the engine is idle: nothing in flight can
                # ever feed the estimator — probe with the oldest held
                # request instead of deadlocking
                queue.append(held.pop(0))
            # -- admission ---------------------------------------------------
            may_admit = bool(queue) and (policy == "continuous"
                                         or not active)
            while may_admit and queue and free_slots:
                req = queue[0]
                need = self.pages_needed(req)
                if need > self.page_capacity:
                    # can never fit, even into an idle pool (undersized
                    # strict_capacity=False pools): degrade to rejection
                    queue.popleft()
                    reject(req, "pool_exhausted", now)
                    continue
                if not pool.can_alloc(need):
                    break
                queue.popleft()
                slot = free_slots.pop()
                st = self._admit(req, slot, need, pool,
                                 preempt_counts.get(req.rid, 0))
                if st.produced >= req.max_new or (
                        self.eos_id is not None
                        and st.last_token == self.eos_id):
                    complete(slot, st, self._now())
                else:
                    active[slot] = st
            if not active:
                if pending:
                    self._advance_to(pending[0].arrival)
                    continue
                if queue:          # pool can hold any valid request when idle
                    raise RuntimeError("scheduler wedged: empty slots but "
                                       "queue not admissible")
                continue
            # -- chaos at decode-step granularity ----------------------------
            if self.fault_plan:
                for ev in self.fault_plan.events:
                    if ev.step != step_idx:
                        continue
                    if ev.kind == "slowdown":
                        slow_factor, slow_until = ev.factor, \
                            step_idx + ev.duration
                        events.append({"event": "slowdown", "step": step_idx,
                                       "factor": ev.factor,
                                       "duration": ev.duration})
                    elif ev.kind == "preempt":
                        evicted = sorted(active.items())
                        for slot, st in evicted:
                            pool.free_slot(slot)
                            free_slots.append(slot)
                            preempt_counts[st.req.rid] = st.preemptions + 1
                        active.clear()
                        for _, st in reversed(evicted):
                            queue.appendleft(st.req)
                        events.append({"event": "preempt", "step": step_idx,
                                       "evicted": len(evicted)})
                        self.tracer.instant("serve/evict", step=step_idx,
                                            evicted=len(evicted))
                if not active:
                    step_idx += 1
                    continue
            factor = slow_factor if step_idx <= slow_until else 1.0
            # -- one decode step over every slot -----------------------------
            # [last_token, len, *page_table_row] per slot, one transfer:
            # at smoke scale the loop is host-dispatch-bound, so the packed
            # state (and the in-graph argmax) is what makes continuous
            # batching's fewer-steps advantage show up in wall clock.
            n_slots = self.pool_cfg.num_slots
            state = np.zeros((n_slots, 2 + self.pool_cfg.max_pages_per_slot),
                             np.int32)
            for slot, st in active.items():
                state[slot, 0] = st.last_token
                state[slot, 1] = st.length
            state[:, 2:] = pool.page_table
            t_start = time.perf_counter()
            with self.tracer.span("serve/decode", step=step_idx,
                                  n_active=len(active)):
                toks_dev, self._bufs = self._decode(self.params, state,
                                                    self._bufs)
                next_tokens = np.asarray(toks_dev)
            dt = time.perf_counter() - t_start
            self._decode_s += dt
            if self.registry is not None:
                self.registry.histogram("serve/decode_s").observe(dt)
            self._advance_decode(dt, factor)
            pool.note_occupancy()
            now = self._now()
            for slot in sorted(active):
                st = active[slot]
                st.length += 1
                tok = int(next_tokens[slot])
                st.tokens.append(tok)
                st.last_token = tok
                st.produced += 1
                if st.produced >= st.req.max_new or (
                        self.eos_id is not None and tok == self.eos_id):
                    del active[slot]
                    complete(slot, st, now)
            step_idx += 1

        metrics = self._metrics(trace, completed, pool, step_idx, events,
                                rejected=rejected)
        metrics["wall_time_s"] = time.perf_counter() - wall_t0
        metrics["prefill_s"] = self._prefill_s
        metrics["decode_s"] = self._decode_s
        metrics["rejected_slo_shed"] = sum(
            1 for r in rejected if r["reason"] == "slo_shed")
        if slo is not None:
            metrics["slo_trips"] = slo.trips
            metrics["slo_estimate"] = slo.estimate()
        if self.registry is not None:
            reg = self.registry
            reg.counter("serve/completed").inc(len(completed))
            reg.counter("serve/rejected").inc(len(rejected))
            reg.counter("serve/slo_shed").inc(
                metrics["rejected_slo_shed"])
            reg.counter("serve/tokens").inc(
                sum(len(c.tokens) for c in completed))
            hl = reg.histogram("serve/latency")
            ht = reg.histogram("serve/ttft")
            for c in completed:
                hl.observe(c.latency)
                ht.observe(c.ttft)
            reg.gauge("serve/wall_time_s").set(metrics["wall_time_s"])
        return ServeReport(policy=policy, completed=completed,
                           metrics=metrics,
                           events=events, rejected=rejected)

    def _admit(self, req, slot: int, need: int, pool: pages_lib.PagePool,
               preemptions: int) -> _Slot:
        pool.alloc(slot, need)
        bucket = trace_lib.bucket_for(req.prompt_len, floor=self.page_size,
                                      cap=self.max_bucket)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :req.prompt_len] = req.prompt
        meta = np.empty((1 + bucket // self.page_size,), np.int32)
        meta[0] = req.prompt_len
        meta[1:] = pool.page_table[slot, :bucket // self.page_size]
        admitted = self._now()
        with self.tracer.span("serve/admit", rid=req.rid):
            t_start = time.perf_counter()
            with self.tracer.span("serve/prefill", rid=req.rid,
                                  prompt_len=req.prompt_len):
                tok_dev, self._bufs = self._prefill(self.params, tokens,
                                                    meta, self._bufs)
                first_tok = int(np.asarray(tok_dev))
            dt = time.perf_counter() - t_start
        self._prefill_s += dt
        if self.registry is not None:
            self.registry.histogram("serve/prefill_s").observe(dt)
        self._advance_prefill(dt)
        return _Slot(req, admitted, self._now(), first_tok, preemptions)

    def _metrics(self, trace, completed, pool, decode_steps, events,
                 rejected=()):
        lats = np.array([c.latency for c in completed] or [0.0])
        ttfts = np.array([c.ttft for c in completed] or [0.0])
        total_tokens = sum(len(c.tokens) for c in completed)
        t_end = max((c.finish for c in completed), default=0.0)
        t_start = min((r.arrival for r in trace), default=0.0)
        duration = max(t_end - t_start, 1e-9)
        return {
            "completed": len(completed),
            "total_tokens": total_tokens,
            "duration": duration,
            "tokens_per_s": total_tokens / duration,
            "p50_latency": float(np.percentile(lats, 50)),
            "p99_latency": float(np.percentile(lats, 99)),
            "p50_ttft": float(np.percentile(ttfts, 50)),
            "p99_ttft": float(np.percentile(ttfts, 99)),
            "mean_occupancy": pool.mean_occupancy(),
            "peak_pages": pool.peak_pages,
            "decode_steps": decode_steps,
            "preemptions": sum(1 for e in events if e["event"] == "preempt"),
            "prefill_compiles": self.prefill_compiles,
            "decode_compiles": self.decode_compiles,
            "rejected": len(rejected),
            "rejected_queue_overflow": sum(
                1 for r in rejected if r["reason"] == "queue_overflow"),
            "rejected_pool_exhausted": sum(
                1 for r in rejected if r["reason"] == "pool_exhausted"),
        }


# ---------------------------------------------------------------------------
# Incremental per-replica surface (the router drives R of these)
# ---------------------------------------------------------------------------


class StepSession:
    """One serving replica as an incremental admit/tick surface.

    Sessions share a single engine's jitted prefill/decode and weights —
    they are R production replicas of one server build — but each owns
    its KV pool, page table and decode slots, so replicas fail and drain
    independently. The *caller* owns all timekeeping: ``admit`` takes
    explicit timestamps and ``tick`` only reports which requests finished,
    so the router's virtual clock fully determines every report and
    same-seed replays are bit-identical (ISSUE 8 tentpole contract).
    Greedy decode makes a request's token stream identical no matter
    which replica (or how many hedged copies) ran it.
    """

    def __init__(self, engine: ServeEngine, name: str = ""):
        self.engine = engine
        self.name = name
        self.pool = pages_lib.PagePool(engine.pool_cfg,
                                       dtype=engine.model.dtype,
                                       shardings=engine._pool_shardings)
        self._bufs = self.pool.buffers
        self.free_slots = list(range(engine.pool_cfg.num_slots - 1, -1, -1))
        self.active: Dict[int, _Slot] = {}
        self._slot_of: Dict[int, int] = {}

    @property
    def n_active(self) -> int:
        return len(self.active)

    def can_admit(self, req: trace_lib.Request) -> bool:
        need = self.engine.pages_needed(req)
        return (bool(self.free_slots) and need <= self.engine.page_capacity
                and self.pool.can_alloc(need))

    def admit(self, req: trace_lib.Request, admitted_t: float,
              first_token_t: float, preemptions: int = 0) -> _Slot:
        """Prefill ``req`` into a free slot (caller checked ``can_admit``
        and stamps both times). The returned slot state may already be
        ``done()`` — single-token requests finish at prefill."""
        need = self.engine.pages_needed(req)
        slot = self.free_slots.pop()
        self.pool.alloc(slot, need)
        eng = self.engine
        bucket = trace_lib.bucket_for(req.prompt_len, floor=eng.page_size,
                                      cap=eng.max_bucket)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :req.prompt_len] = req.prompt
        meta = np.empty((1 + bucket // eng.page_size,), np.int32)
        meta[0] = req.prompt_len
        meta[1:] = self.pool.page_table[slot, :bucket // eng.page_size]
        with eng.tracer.span("serve/prefill", rid=req.rid,
                             replica=self.name):
            tok_dev, self._bufs = eng._prefill(eng.params, tokens, meta,
                                               self._bufs)
        st = _Slot(req, admitted_t, first_token_t, int(np.asarray(tok_dev)),
                   preemptions)
        self.active[slot] = st
        self._slot_of[req.rid] = slot
        return st

    def done(self, st: _Slot) -> bool:
        return st.produced >= st.req.max_new or (
            self.engine.eos_id is not None
            and st.last_token == self.engine.eos_id)

    def release(self, rid: int) -> _Slot:
        """Free ``rid``'s slot and pages — completion, a hedge loser being
        cancelled, or an unhealthy replica draining. Returns the slot
        state so the caller can keep (or drop) its tokens."""
        slot = self._slot_of.pop(rid)
        st = self.active.pop(slot)
        self.pool.free_slot(slot)
        self.free_slots.append(slot)
        return st

    def evict_all(self) -> List[_Slot]:
        """Crash/preempt: drop every in-flight request, freeing all pages.
        Returns slot states in slot order for deterministic requeue."""
        sts = [st for _, st in sorted(self.active.items())]
        for slot in list(self.active):
            self.pool.free_slot(slot)
            self.free_slots.append(slot)
        self.active.clear()
        self._slot_of.clear()
        return sts

    def tick(self) -> List[int]:
        """One decode step over every active slot (one token each).
        Returns the rids that finished this step; the caller stamps their
        finish time and calls :meth:`release`."""
        if not self.active:
            return []
        eng = self.engine
        n_slots = eng.pool_cfg.num_slots
        state = np.zeros((n_slots, 2 + eng.pool_cfg.max_pages_per_slot),
                         np.int32)
        for slot, st in self.active.items():
            if self.done(st):
                continue   # finished at prefill; holds its slot until the
                           # caller's scheduled release — never decodes
            state[slot, 0] = st.last_token
            state[slot, 1] = st.length
        state[:, 2:] = self.pool.page_table
        with eng.tracer.span("serve/decode", replica=self.name,
                             n_active=len(self.active)):
            toks_dev, self._bufs = eng._decode(eng.params, state, self._bufs)
        next_tokens = np.asarray(toks_dev)
        finished: List[int] = []
        for slot in sorted(self.active):
            st = self.active[slot]
            if self.done(st):
                continue
            st.length += 1
            tok = int(next_tokens[slot])
            st.tokens.append(tok)
            st.last_token = tok
            st.produced += 1
            if self.done(st):
                finished.append(st.req.rid)
        self.pool.note_occupancy()
        return finished


# ---------------------------------------------------------------------------
# Checkpoint -> serve bridge
# ---------------------------------------------------------------------------


def restore_params(directory: str, model_cfg, *, step: Optional[int] = None,
                   use_ema: bool = False):
    """Load just the weights of a training checkpoint for serving.

    Checkpoints are stored gathered (full shapes) by every backend — sim,
    replicated SPMD, and TP-sharded alike (PR 5's interchangeability
    contract) — so a single eval_shape template restores all three; the
    engine re-shards on admission when ``mesh_model > 1``. Goes through
    ``checkpoint.restore``'s CRC-verified, walk-back path. Returns
    ``(params, manifest)``.
    """
    from repro.train import checkpoint as ckpt_lib
    model = get_model(model_cfg)
    template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    key = "ema" if use_ema else "params"
    tree, manifest = ckpt_lib.restore(directory, {key: template}, step)
    return tree[key], manifest
