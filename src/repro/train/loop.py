"""The Trainer: every coordination regime behind one entry point.

The strategy (built from ``cfg.aggregation`` by
``repro.core.registry.get_strategy`` — the Trainer's only construction
path) picks the execution mode:

**Mask mode** (full_sync / backup / timeout) — SPMD steps driven by the
straggler simulator. Per step:
  1. the StragglerSimulator samples worker arrival times and the strategy
     selects the mask + iteration time (simulated seconds);
  2. the data pipeline emits the global batch (worker-sharded rows);
  3. the jitted SPMD step applies the masked aggregation + optimizer + EMA;
  4. on checkpoint cadence, state is committed atomically.

With ``cfg.chunk_size > 1`` the hot loop is fused: K iterations run in a
single ``lax.scan`` dispatch (see docs/perf.md); chunk boundaries are
forced at checkpoint / kill-injection / rescale steps so resume semantics
are unchanged.

With ``cfg.execution.backend == 'spmd'`` mask strategies execute on the
SPMD engine (``repro.distributed.spmd_engine``, docs/spmd.md): the W
workers map onto a real mesh 'data' axis, per-worker gradients live on
their shard, and masked aggregation is a collective — with the same
host-planned masks, checkpoint format, and chunking rules as the
simulated backend. ``mesh_model > 1`` additionally shards params/opt
state/EMA over the mesh 'model' axis and computes each worker's
gradient tensor-parallel (``sharding.tp_plan`` decides which groups
shard; checkpoints stay interchangeable — state is gathered at save
and re-sharded on restore). Strategies without SPMD support
(``registry.supports_spmd``; TP opt-out ``spmd_tp_supported``) fall
back to 'sim' with a warning.

**Event mode** (async / softsync / staleness) — the discrete-event
parameter-server loop: the scheduler pops gradient arrivals per the
latency model, the strategy decides apply-or-buffer per arrival
(paper Alg. 1/2 semantics for async), and each applied update advances
``step``. Event regimes get checkpoint/resume (exact replay: worker
parameter copies, scheduler queue and RNG are all checkpointed), EMA,
failure injection, and the same metrics schema as mask mode.

With ``cfg.chunk_size > 1`` event mode is fused too: the host scheduler
cheaply precomputes a block of arrivals into flat arrays
(``coordination.plan_events`` — the apply/staleness verdicts of every
built-in event strategy are gradient-independent), and a single
``lax.scan`` (``build_event_chunk_step``) runs gradients, strategy
application, optimizer and EMA on device, with the per-worker read
copies held as ONE stacked ``[W, ...]`` device pytree updated by
scatter. Chunk boundaries always land on PS-update counts and are
forced at checkpoint/kill steps, so resume/failure semantics — and the
on-disk checkpoint format — are identical to the per-arrival path.

Unified per-update metrics (both modes, see docs/api.md):
    ``step, loss, sim_time, selected, staleness``
plus ``TrainResult.mean_selected`` (the *actual* mean aggregated-worker
count — for Timeout this is the realized per-step mean, not the
``effective_n()`` upper bound) and ``TrainResult.mean_staleness``.

Failure handling (mask mode): a dead worker's gradient never arrives.
While alive >= N the protocol absorbs it with zero downtime (the paper's
point); below that the Trainer executes an elastic restart from the last
checkpoint. In event mode a killed worker simply stops producing
arrivals. ``run_experiment(cfg)`` is the one-call entry point used by the
CLI, the examples, and the benchmarks.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.core import coordination
from repro.core import ema as ema_lib
from repro.core import faults as faults_lib
from repro.core import registry
from repro.core import straggler_jax
from repro.core.events import StragglerSimulator
from repro.core.straggler import LatencyModel, PaperCalibrated
from repro.data.synthetic_lm import (ChunkPrefetcher, PipelineState,
                                     SyntheticLMConfig, SyntheticLMPipeline,
                                     device_batch_fn, worker_batch)
from repro.distributed import spmd_engine
from repro.models import get_model
from repro.obs.trace import as_tracer
from repro.optim import make_optimizer, schedules
from repro.train import checkpoint as ckpt_lib
from repro.train import elastic
from repro.train.train_step import (build_chunk_step, build_event_chunk_step,
                                    build_train_step)


@dataclasses.dataclass
class TrainResult:
    params: Any
    ema: Any
    metrics: List[Dict]
    sim_time: float
    steps: int
    restarts: int
    # realized coordination statistics (unified across mask/event modes):
    # mean gradients aggregated per update (Timeout reports its *actual*
    # per-step mean, not the effective_n() upper bound), and the mean
    # staleness of applied gradients (0 for synchronous strategies).
    mean_selected: float = 0.0
    mean_staleness: float = 0.0
    # structured fault/recovery events (chaos engine + supervisor) — the
    # schema is docs/api.md "Recovery events"; empty without fault injection.
    # Deterministic in (fault spec, fault seed): no wall-clock fields.
    recovery_log: List[Dict] = dataclasses.field(default_factory=list)
    # host wall-clock of run() (always measured — two clock reads) and,
    # when observability is on (tracer/metrics/measured mode), the
    # fenced per-phase breakdown {dispatch_s, data_s, ckpt_s}
    wall_time_s: float = 0.0
    phase_times: Dict[str, float] = dataclasses.field(default_factory=dict)


def _normalize_kills(kill_worker_at: Optional[Dict[int, Any]]
                     ) -> Dict[int, List[int]]:
    """{step: worker | [workers]} -> {step: [workers]} (back-compat: the
    original API took one worker id per step)."""
    out: Dict[int, List[int]] = {}
    for s, ws in (kill_worker_at or {}).items():
        if isinstance(ws, (list, tuple, np.ndarray)):
            out[int(s)] = [int(w) for w in ws]
        else:
            out[int(s)] = [int(ws)]
    return out


class Trainer:
    def __init__(self, cfg: TrainConfig, latency: Optional[LatencyModel] = None,
                 data_cfg: Optional[SyntheticLMConfig] = None,
                 model=None, batch_fn: Optional[Callable] = None,
                 injector: Optional[faults_lib.FaultInjector] = None,
                 tracer=None, metrics=None):
        """``model``/``batch_fn`` override the config-derived model and
        per-worker batch source (event mode only) — how non-LM rigs like
        the §2.1 MNIST staleness experiment route through run_experiment.
        batch_fn(worker, draw_index) -> batch dict.

        ``injector`` attaches a chaos-engine fault plan (repro.core.faults);
        the supervisor owns it across restarts so faults fire at most once.

        ``tracer`` (repro.obs.Tracer) records train/chunk, train/step,
        train/data_wait, train/device_wait and train/ckpt_save spans;
        ``metrics`` (repro.obs.MetricsRegistry) accumulates the train/*
        schema. Either being set — or the strategy running with
        ``latency_source='measured'`` — turns on block_until_ready
        fences at chunk edges (never inside the fused scan), so chunk
        timings are real; with both unset the loop is untouched (the
        no-op tracer path, held under 2%% overhead by tests/test_obs.py).
        """
        self.cfg = cfg
        self.latency = latency or PaperCalibrated()
        self.injector = injector
        self.restarts = 0
        self.sim_time = 0.0
        self.metrics: List[Dict] = []
        self._model_override = model
        self._batch_fn_override = batch_fn
        # realized selected/staleness accumulators behind TrainResult's
        # mean_selected / mean_staleness (persisted across checkpoints)
        self._sel_sum = 0.0
        self._sel_count = 0
        self._stal_sum = 0.0
        self._stal_count = 0
        w = cfg.aggregation.total_workers
        self.data_cfg = data_cfg or SyntheticLMConfig(
            vocab_size=cfg.model.vocab_size, seq_len=cfg.shape.seq_len,
            global_batch=cfg.shape.global_batch, num_workers=w, seed=cfg.seed)
        self.tracer = as_tracer(tracer)
        self.registry = metrics
        self._wall_s = 0.0
        self._phase = {"dispatch_s": 0.0, "data_s": 0.0, "ckpt_s": 0.0}
        self._build()
        # measured mode: feed fenced wall-clock per-worker rows into the
        # strategy's adaptation window (dynamic_backup, docs/observability)
        self._measured_feed = (
            getattr(self.strategy, "latency_source", "sim") == "measured")
        self._obs = (self.tracer.enabled or self.registry is not None
                     or self._measured_feed)

    # -- construction ---------------------------------------------------------

    def _build(self) -> None:
        # the registry is the ONLY config->strategy construction path
        self.strategy = registry.get_strategy(self.cfg.aggregation)
        backend = self.cfg.execution.backend
        if backend not in ("sim", "spmd"):
            raise ValueError(f"unknown execution backend {backend!r} "
                             f"(valid: sim, spmd)")
        # the supports_spmd gate: strategies without SPMD support (event
        # regimes, opted-out plugins — incl. TP-specific opt-outs when
        # mesh_model > 1) fall back to the simulated backend
        self._spmd = backend == "spmd"
        if self._spmd and not registry.supports_spmd(self.strategy,
                                                     self.cfg.execution):
            warnings.warn(
                f"strategy {self.cfg.aggregation.strategy!r} has no SPMD "
                "support (registry.supports_spmd); falling back to the "
                "single-device simulated backend", stacklevel=2)
            self._spmd = False
        if self.strategy.kind == "mask":
            self._build_mask()
        elif self.strategy.kind == "event":
            self._build_event()
        else:
            raise ValueError(f"strategy {self.cfg.aggregation.strategy!r} has "
                             f"unknown kind {self.strategy.kind!r}")

    def _build_mask(self) -> None:
        cfg = self.cfg
        self.model = self._model_override or get_model(cfg.model)
        if self._batch_fn_override is not None:
            raise ValueError("batch_fn overrides are only supported for "
                             "event strategies (async/softsync/staleness)")
        self.sim = StragglerSimulator(self.strategy, self.latency, cfg.seed)
        sched = schedules.from_config(cfg.optimizer, cfg.aggregation.num_workers)
        self.optimizer = make_optimizer(cfg.optimizer, sched)
        self.pipeline = SyntheticLMPipeline(
            dataclasses.replace(self.data_cfg,
                                num_workers=cfg.aggregation.total_workers))
        step_kwargs = dict(
            num_workers=cfg.aggregation.total_workers,
            n_aggregate=cfg.aggregation.num_workers,
            ema_decay=cfg.optimizer.ema_decay,
            clip_norm=cfg.optimizer.clip_global_norm)
        if cfg.straggler_backend not in ("host", "device"):
            raise ValueError(f"unknown straggler_backend "
                             f"{cfg.straggler_backend!r} (host|device)")
        if (cfg.straggler_backend == "device"
                and not getattr(self.strategy, "device_select_supported", True)):
            raise ValueError(
                f"strategy {cfg.aggregation.strategy!r} selects on the host "
                "(stateful adaptation has no traceable select_jax); use "
                "straggler_backend='host'")
        if self.injector is not None and cfg.straggler_backend == "device":
            raise ValueError(
                "fault injection composes with host-planned arrivals only: "
                "straggler_backend must be 'host' when cfg.faults is active")
        if self._spmd:
            # SPMD execution engine: workers over the mesh 'data' axis,
            # masked aggregation as a collective (docs/spmd.md). Masks
            # stay host-planned, so the straggler simulator/prefetcher
            # plumbing is shared with the simulated backend.
            if cfg.straggler_backend == "device":
                raise ValueError(
                    "straggler_backend='device' applies to the simulated "
                    "backend only: the spmd engine consumes host-planned "
                    "masks (use straggler_backend='host')")
            self.mesh = spmd_engine.build_mesh(cfg.execution)
            spmd_engine.validate_layout(cfg.aggregation.total_workers,
                                        cfg.shape.global_batch,
                                        cfg.execution.mesh_data)
            # mesh_model > 1 shards params/opt/EMA over the 'model' axis
            # (tensor parallelism inside the per-worker gradient) when the
            # model config permits — sharding.tp_plan decides; a model
            # override has no config, so the axis stays replicated there
            engine_kwargs = dict(step_kwargs,
                                 use_kernel=cfg.execution.use_kernel,
                                 grad_batch=cfg.execution.grad_batch,
                                 bucket_size=cfg.execution.bucket_size,
                                 model_cfg=(None if self._model_override
                                            else cfg.model))
            engine_tracer = (self.tracer
                             if getattr(self, "tracer", None) is not None
                             and self.tracer.enabled else None)
            self._state_shardings = spmd_engine.state_shardings(
                self.model, self.optimizer, self.mesh,
                ema_decay=cfg.optimizer.ema_decay,
                model_cfg=engine_kwargs["model_cfg"])
            self.train_step = spmd_engine.make_train_step(
                self.model, self.optimizer, self.mesh,
                tracer=engine_tracer, **engine_kwargs)
            if cfg.chunk_size > 1:
                self.chunk_step = spmd_engine.make_chunk_step(
                    self.model, self.optimizer, self.mesh,
                    tracer=engine_tracer, **engine_kwargs)
                self.prefetcher = ChunkPrefetcher(
                    self.pipeline.cfg, depth=cfg.prefetch_depth)
            self.step = 0
            return
        step_fn = build_train_step(self.model, self.optimizer, **step_kwargs)
        self.train_step = jax.jit(step_fn, donate_argnums=(0, 1, 2))
        # fused chunked path: K steps per dispatch via lax.scan (see
        # docs/perf.md). 'host' backend replays the numpy straggler streams
        # bit-exactly; 'device' samples arrivals inside the scan body.
        if cfg.chunk_size > 1:
            self.chunk_step = jax.jit(
                build_chunk_step(self.model, self.optimizer, **step_kwargs),
                donate_argnums=(0, 1, 2))
            if cfg.straggler_backend == "device":
                self.chunk_step_device = jax.jit(
                    build_chunk_step(
                        self.model, self.optimizer, **step_kwargs,
                        sample_fn=straggler_jax.sampler_for(self.latency),
                        select_fn=self.strategy.select_jax,
                        data_fn=device_batch_fn(self.pipeline.cfg)),
                    static_argnums=(4,), donate_argnums=(0, 1, 2))
            self.prefetcher = ChunkPrefetcher(self.pipeline.cfg,
                                              depth=cfg.prefetch_depth)
            # domain-separated from device_batch_fn's data key stream
            self._chunk_key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.seed), 0x57A6)
        elif cfg.straggler_backend == "device":
            raise ValueError(
                "straggler_backend='device' requires chunk_size > 1 — the "
                "device backend lives inside the fused chunk dispatch")
        self.step = 0

    def _build_event(self) -> None:
        cfg = self.cfg
        if cfg.straggler_backend != "host":
            raise ValueError(
                "event strategies (async/softsync/staleness) schedule "
                "arrivals on the host: straggler_backend must be 'host'")
        self._event_fused = cfg.chunk_size > 1
        if self._event_fused and not registry.supports_event_scan(self.strategy):
            # plugins that only implement on_arrival still run — on the
            # legacy per-arrival path, with a warning instead of an error
            warnings.warn(
                f"strategy {cfg.aggregation.strategy!r} does not implement "
                "the chunked plan/scan protocol (plan_arrival + "
                "on_arrival_scan); falling back to the legacy per-arrival "
                "path (chunk_size=1 semantics)", stacklevel=2)
            self._event_fused = False
        self.model = self._model_override or get_model(cfg.model)
        sched = schedules.from_config(cfg.optimizer, cfg.aggregation.num_workers)
        self.optimizer = make_optimizer(cfg.optimizer, sched)
        self._grad_fn = coordination.make_grad_fn(self.model)
        self._update_fn = coordination.make_update_fn(
            self.optimizer, cfg.optimizer.clip_global_norm)
        if self._event_fused:
            # fused event engine: K arrivals per lax.scan dispatch; the
            # carry (params/opt/ema/stacked workers/strategy aux) stays
            # device-resident between chunks, so donate all of it
            self._event_chunk = jax.jit(
                build_event_chunk_step(self._grad_fn, self._update_fn,
                                       self.strategy,
                                       ema_decay=cfg.optimizer.ema_decay),
                donate_argnums=(0, 1, 2, 3, 4))
        if self._batch_fn_override is not None:
            self._event_batch = self._batch_fn_override
            # fused stacking has to pull override batches back to host
            self._event_batch_host = lambda w, d: {
                k: np.asarray(v)
                for k, v in self._batch_fn_override(w, d).items()}
        else:
            data_cfg = dataclasses.replace(
                self.data_cfg, num_workers=self.strategy.total_workers)

            def _batch(worker: int, draw: int) -> Dict:
                b = worker_batch(data_cfg, worker, draw)
                return {k: jnp.asarray(v) for k, v in b.items()}

            self._event_batch = _batch
            # numpy twin for the fused path: the chunk is stacked on host
            # and uploaded ONCE, instead of K per-arrival device uploads
            # immediately pulled back for stacking
            self._event_batch_host = (
                lambda w, d: worker_batch(data_cfg, w, d))
        self.step = 0

    def init_state(self, seed: Optional[int] = None) -> None:
        key = jax.random.PRNGKey(self.cfg.seed if seed is None else seed)
        ema_on = self.cfg.optimizer.ema_decay > 0
        if self._spmd:
            # born on the engine's mesh shardings: built on one device and
            # resharded by the first step, a full-width state would sit on
            # that device twice over
            psh, osh, esh = self._state_shardings
            self.params = jax.jit(self.model.init, out_shardings=psh)(key)
            self.opt_state = jax.jit(self.optimizer.init,
                                     out_shardings=osh)(self.params)
            self.ema = (jax.jit(ema_lib.init, out_shardings=esh)(self.params)
                        if ema_on else None)
        else:
            self.params = self.model.init(key)
            self.opt_state = self.optimizer.init(self.params)
            self.ema = ema_lib.init(self.params) if ema_on else None
        if self.strategy.kind == "event":
            self._init_event_state()

    def _init_event_state(self) -> None:
        w = self.strategy.total_workers
        self._read_version = np.zeros(w, dtype=np.int64)
        self._draws = np.zeros(w, dtype=np.int64)
        self._arrival_count = 0
        self._event_dead: set = set()
        if self.strategy.uses_clock:
            self._sched = coordination.EventScheduler(
                w, self.latency, self.cfg.seed)
        else:
            self._sched = coordination.SerialScheduler()
        if self._event_fused:
            # device form: one stacked [W, ...] tree of worker read
            # copies + the strategy's scan carry; host form: plan state
            # (counters, staleness tags/rng) only — no gradient trees
            self._ev_state = None
            self._plan_state = self.strategy.init_plan_state(self.cfg.seed)
            self._workers_stacked = jax.tree_util.tree_map(
                lambda p: jnp.stack([p] * w), self.params)
            self._scan_aux = self.strategy.init_scan_state(self.params)
        else:
            self._read_params = [self.params for _ in range(w)]
            self._ev_state = self.strategy.init_state(self.cfg.seed)

    # -- checkpointing --------------------------------------------------------

    def _state_tree(self):
        tree = {"params": self.params, "opt": self.opt_state}
        if self.ema is not None:
            tree["ema"] = self.ema
        if self.strategy.kind == "event":
            if self._event_fused:
                if self.strategy.uses_clock:
                    tree["workers"] = self._workers_stacked
                slots = [s for _, s in getattr(self._plan_state, "fifo", [])]
                if slots:
                    # gather the ring in FIFO order -> same on-disk layout
                    # as the legacy stacked old-gradient buffer
                    idx = jnp.asarray(slots, jnp.int32)
                    tree["stale_buffer"] = jax.tree_util.tree_map(
                        lambda r: r[idx], self._scan_aux)
            else:
                if self.strategy.uses_clock:
                    tree["workers"] = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *self._read_params)
                buf = getattr(self._ev_state, "buffer", None)
                if buf:
                    tree["stale_buffer"] = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *[g for _, g in buf])
        return tree

    def _mean_meta(self) -> Dict:
        return {"sel_sum": self._sel_sum, "sel_count": self._sel_count,
                "stal_sum": self._stal_sum, "stal_count": self._stal_count}

    def save_checkpoint(self) -> str:
        meta = {
            "num_workers": self.cfg.aggregation.num_workers,
            "backup_workers": self.cfg.aggregation.backup_workers,
            "strategy": self.cfg.aggregation.strategy,
            "sim_time": self.sim_time,
            "restarts": self.restarts,
            "means": self._mean_meta(),
        }
        # adaptive strategies (dynamic_backup) persist their window/cutoff
        # so a supervisor restore resumes the adapted n, not the config's
        if hasattr(self.strategy, "state_dict"):
            meta["strategy_state"] = self.strategy.state_dict()
        if self.strategy.kind == "event":
            # the run loop checkpoints right after an applied update, where
            # the softsync window is empty by construction; a mid-window
            # snapshot would silently lose the buffered gradients on resume
            strat_state = self._plan_state if self._event_fused else self._ev_state
            if getattr(strat_state, "pending", None) or getattr(
                    strat_state, "pending_stals", None):
                raise RuntimeError(
                    "event checkpoint with a non-empty softsync window — "
                    "checkpoint only lands right after an applied update")
            if self._event_fused:
                tags = [int(tag) for tag, _ in
                        getattr(strat_state, "fifo", [])]
            else:
                tags = [int(tag) for tag, _ in
                        getattr(strat_state, "buffer", [])]
            meta["event"] = {
                "sched": self._sched.state_dict(),
                "read_version": [int(v) for v in self._read_version],
                "draws": [int(d) for d in self._draws],
                "arrival_count": int(self._arrival_count),
                "dead": sorted(int(w) for w in self._event_dead),
                "buffer_tags": tags,
                "strategy_rng": coordination.encode_rng(
                    getattr(strat_state, "rng", None)),
            }
        else:
            meta["data_state"] = self.pipeline.state.save()
            meta["dead_workers"] = [int(w) for w in
                                    np.nonzero(self.sim.dead)[0]]
        inj = self.injector
        t0 = self._now()
        with self.tracer.span("train/ckpt_save", step=int(self.step)):
            path = ckpt_lib.save(
                self.cfg.checkpoint.directory, self.step, self._state_tree(),
                meta, self.cfg.checkpoint.keep,
                retries=getattr(self.cfg.checkpoint, "write_retries", 3),
                backoff_s=getattr(self.cfg.checkpoint,
                                  "retry_backoff_s", 0.01),
                max_backoff_s=getattr(self.cfg.checkpoint,
                                      "retry_max_backoff_s", 0.25),
                jitter=getattr(self.cfg.checkpoint, "retry_jitter", 0.5),
                backoff_seed=self.cfg.seed,
                io_check=inj.ckpt_io_check if inj is not None else None,
                on_retry=(inj.on_ckpt_retry(self.step)
                          if inj is not None else None))
        if t0 is not None:
            self._phase["ckpt_s"] += time.perf_counter() - t0
        return path

    def restore_checkpoint(self, step: Optional[int] = None) -> None:
        # manifest first: the event-mode template depends on saved metadata
        # (stale-buffer length); pin the resolved step so a concurrent save
        # cannot shift "latest" between the two reads
        manifest = ckpt_lib.read_manifest(self.cfg.checkpoint.directory, step)
        tree, manifest = ckpt_lib.restore(
            self.cfg.checkpoint.directory,
            self._template(len(manifest.get("event", {}).get("buffer_tags",
                                                             []))),
            int(manifest["step"]))
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self.ema = tree.get("ema")
        self.step = int(manifest["step"])
        self.sim_time = float(manifest.get("sim_time", 0.0))
        self.restarts = int(manifest.get("restarts", 0))
        means = manifest.get("means", {})
        self._sel_sum = float(means.get("sel_sum", 0.0))
        self._sel_count = int(means.get("sel_count", 0))
        self._stal_sum = float(means.get("stal_sum", 0.0))
        self._stal_count = int(means.get("stal_count", 0))
        if (hasattr(self.strategy, "load_state_dict")
                and manifest.get("strategy_state")):
            self.strategy.load_state_dict(manifest["strategy_state"])
        if self.strategy.kind == "event":
            self._restore_event_state(tree, manifest["event"])
        else:
            self.pipeline.state = PipelineState.restore(manifest["data_state"])
            # replay-exact resume: the straggler simulator is deterministic
            # in (seed, step), so aligning its step restores the arrivals
            self.sim.reset_to_step(self.step)
            # re-apply recorded deaths — but only while the cluster shape
            # is unchanged: a rescale renumbers workers, and its rebuild
            # intentionally restarts with everyone alive
            if (manifest.get("num_workers") == self.cfg.aggregation.num_workers
                    and manifest.get("backup_workers")
                    == self.cfg.aggregation.backup_workers):
                for w in manifest.get("dead_workers", []):
                    if 0 <= int(w) < self.strategy.total_workers:
                        self.sim.kill_worker(int(w))

    def _restore_event_state(self, tree, ev_meta: Dict) -> None:
        self._init_event_state()
        w = self.strategy.total_workers
        self._read_version = np.array(ev_meta["read_version"], np.int64)
        self._draws = np.array(ev_meta["draws"], np.int64)
        self._arrival_count = int(ev_meta["arrival_count"])
        self._event_dead = set(ev_meta.get("dead", []))
        self._sched.load_state_dict(ev_meta["sched"])
        tags = ev_meta.get("buffer_tags", [])
        if self._event_fused:
            if self.strategy.uses_clock:
                self._workers_stacked = tree["workers"]
            if tags:
                # scatter the FIFO-ordered buffer into ring slots 0..n-1
                # and rebase the round-robin write pointer after them
                self._scan_aux = jax.tree_util.tree_map(
                    lambda r, b: r.at[:len(tags)].set(b),
                    self._scan_aux, tree["stale_buffer"])
                self._plan_state.fifo = [(int(tag), i)
                                         for i, tag in enumerate(tags)]
                self._plan_state.writes = len(tags)
            strat_state = self._plan_state
        else:
            if self.strategy.uses_clock:
                # share one reference per distinct read version: workers
                # at the current version get the live params; a copy is
                # gathered only per divergent version (memory fix for
                # large-W async runs)
                by_version: Dict[int, Any] = {}
                self._read_params = []
                for i in range(w):
                    v = int(self._read_version[i])
                    if v not in by_version:
                        by_version[v] = (
                            self.params if v == self.step else
                            jax.tree_util.tree_map(lambda x, i=i: x[i],
                                                   tree["workers"]))
                    self._read_params.append(by_version[v])
            else:
                self._read_params = [self.params]
            if tags:
                self._ev_state.buffer = [
                    (int(tag),
                     jax.tree_util.tree_map(lambda x, i=i: x[i],
                                            tree["stale_buffer"]))
                    for i, tag in enumerate(tags)]
            strat_state = self._ev_state
        rng = getattr(strat_state, "rng", None)
        if rng is not None and ev_meta.get("strategy_rng"):
            coordination.decode_rng(rng, ev_meta["strategy_rng"])

    def _template(self, buffer_len: int = 0):
        key = jax.random.PRNGKey(0)
        params_t = jax.eval_shape(self.model.init, key)
        opt_t = jax.eval_shape(self.optimizer.init, params_t)
        tree = {"params": params_t, "opt": opt_t}
        if self.cfg.optimizer.ema_decay > 0:
            tree["ema"] = jax.eval_shape(ema_lib.init, params_t)

        def stack_t(n):
            return jax.tree_util.tree_map(
                lambda t: jax.ShapeDtypeStruct((n,) + tuple(t.shape), t.dtype),
                params_t)

        if self.strategy.kind == "event":
            if self.strategy.uses_clock:
                tree["workers"] = stack_t(self.strategy.total_workers)
            if buffer_len:
                tree["stale_buffer"] = stack_t(buffer_len)
        return tree

    # -- elastic rescale ------------------------------------------------------

    def rescale(self, new_total: int) -> None:
        """Checkpoint, rebuild for `new_total` workers, restore, continue.

        new_total is rounded down to a divisor of the global batch so the
        per-worker shard stays integral. Mask strategies only — event
        regimes absorb worker loss natively (fewer arrival sources).
        """
        if self.strategy.kind != "mask":
            raise NotImplementedError("elastic rescale applies to mask "
                                      "strategies only")
        w = max(1, new_total)
        while self.cfg.shape.global_batch % w:
            w -= 1
        self.save_checkpoint()
        prev_restarts = self.restarts
        prev_total = self.cfg.aggregation.total_workers
        plan = elastic.plan_rescale(self.cfg, w)
        self.cfg = elastic.apply_rescale(self.cfg, plan)
        if self._spmd:
            # shrink the worker axis to the largest size the new worker
            # count still divides over — the freed devices idle rather
            # than crash the run (they rejoin on the next scale-up)
            md = self.cfg.execution.mesh_data
            while w % md:
                md -= 1
            if md != self.cfg.execution.mesh_data:
                self.cfg = dataclasses.replace(
                    self.cfg, execution=dataclasses.replace(
                        self.cfg.execution, mesh_data=md))
        self._build()
        self.restore_checkpoint()
        self.restarts = prev_restarts + 1
        if self.injector is not None:
            self.injector.record("rescale", step=self.step,
                                 from_workers=prev_total,
                                 to_workers=self.cfg.aggregation.total_workers)
            # the rescaled cluster is renumbered and starts healthy: the
            # injector's per-worker effects refer to ids that no longer exist
            self.injector.dead.clear()
            self.injector.slow_active.clear()

    # -- fault injection (the chaos engine's Trainer-side primitives) ---------

    def fault_kill(self, worker: int) -> None:
        """Permanent worker crash, in whichever mode is running."""
        if self.strategy.kind == "mask":
            self.sim.kill_worker(worker)
        else:
            self._kill_event_worker(worker)

    def fault_slowdown(self, worker: int, factor: float) -> None:
        """Latency spike on one worker (factor=1.0 restores health)."""
        if self.strategy.kind == "mask":
            self.sim.set_slowdown(worker, factor)
        else:
            self._sched.set_slowdown(worker, factor)

    def fault_revive(self, worker: int) -> None:
        """A crashed worker rejoins with the *current* params."""
        if self.strategy.kind == "mask":
            self.sim.revive_worker(worker)
            return
        self._event_dead.discard(worker)
        # fresh read copy at the live version; next arrival from now
        if self._event_fused:
            self._workers_stacked = jax.tree_util.tree_map(
                lambda ws, p: ws.at[worker].set(p),
                self._workers_stacked, self.params)
        else:
            self._read_params[worker] = self.params
        self._read_version[worker] = self.step
        self._sched.revive_worker(worker, self.sim_time)

    def _event_window_empty(self) -> bool:
        """True when no softsync-style window is buffering gradients — the
        precondition for an event-mode checkpoint (see save_checkpoint)."""
        if self.strategy.kind != "event":
            return True
        state = self._plan_state if self._event_fused else self._ev_state
        return not (getattr(state, "pending", None)
                    or getattr(state, "pending_stals", None))

    def _apply_faults(self, step: int) -> None:
        """Fire every due fault from the chaos plan (repro.core.faults).

        Called at chunk boundaries in every run loop; ``_chunk_len_at``
        forces a boundary at each pending fault step, so faults land on
        the same step in the per-step, fused, and SPMD backends."""
        if self.injector is None:
            return
        inj = self.injector
        w_total = self.strategy.total_workers
        for ev in inj.take_due(step):
            w = ev.worker % w_total if ev.worker >= 0 else ev.worker
            if (ev.kind in ("crash", "slowdown", "restart")
                    and self.strategy.kind == "event"
                    and not self.strategy.uses_clock):
                raise ValueError("failure injection does not apply to serial "
                                 "rigs (the staleness strategy has a single "
                                 "logical worker)")
            if ev.kind == "crash":
                if w not in inj.dead:
                    self.fault_kill(w)
                    inj.note_crash(step, w)
            elif ev.kind == "slowdown":
                self.fault_slowdown(w, ev.factor)
                inj.note_slowdown(step, w, ev.factor, ev.duration)
            elif ev.kind == "slow_end":
                inj.note_slow_end(w)
                self.fault_slowdown(w, 1.0)
            elif ev.kind == "restart":
                if w in inj.dead:
                    self.fault_revive(w)
                    inj.note_restart(step, w)
            elif ev.kind == "ckpt_io":
                inj.arm_ckpt_failures(step, ev.fails)
            elif ev.kind == "preempt":
                if not self._event_window_empty():
                    # an event checkpoint is only legal right after an
                    # applied update; push the notice to the next one
                    inj.defer(ev, step + 1)
                    continue
                ckpted = False
                if ev.grace:
                    self.save_checkpoint()
                    ckpted = True
                inj.record("preempt", step=step, grace=ckpted)
                raise faults_lib.Preemption(step, ckpted)

    # -- the loop -------------------------------------------------------------

    def run(self, num_steps: int, kill_worker_at: Optional[Dict[int, Any]] = None,
            min_alive_behavior: str = "rescale") -> TrainResult:
        """kill_worker_at: {step: worker_id | [worker_ids]} failure
        injections (a correlated outage kills several workers at once)."""
        t0 = time.perf_counter()
        step0 = self.step
        try:
            res = self._run(num_steps, kill_worker_at, min_alive_behavior)
        finally:
            self._wall_s += time.perf_counter() - t0
            if self.registry is not None:
                self.registry.counter("train/steps").inc(self.step - step0)
                self.registry.gauge("train/wall_time_s").set(self._wall_s)
                for key, v in self._phase.items():
                    self.registry.gauge(f"train/{key}").set(v)
        # _result() ran before the finally accumulated this run's wall
        # time: restamp so the returned report carries the final figure
        return dataclasses.replace(
            res, wall_time_s=self._wall_s,
            phase_times=dict(self._phase) if self._obs else {})

    def _run(self, num_steps: int, kill_worker_at, min_alive_behavior
             ) -> TrainResult:
        kill_worker_at = _normalize_kills(kill_worker_at)
        target = self.step + num_steps
        if self.strategy.kind == "event":
            if self._event_fused:
                self._run_event_chunked(target, kill_worker_at)
            else:
                self._run_event(target, kill_worker_at)
            return self._result()
        while self.step < target:
            self._apply_faults(self.step)
            if self.step in kill_worker_at:
                # pop on application (as the event loop does): a rescale
                # renumbers the workers, so the entry must not re-apply
                # to the rebuilt, smaller simulator on the next pass
                for w in kill_worker_at.pop(self.step):
                    self.sim.kill_worker(w)
            # adaptive strategies (dynamic_backup) expose a lower liveness
            # floor than N — the protocol itself degrades gracefully
            min_alive = getattr(self.strategy, "min_alive",
                                self.cfg.aggregation.num_workers)
            if self.sim.alive < min_alive:
                if min_alive_behavior == "rescale":
                    self.rescale(self.sim.alive)
                    continue
                raise RuntimeError("insufficient live workers")
            k = self._chunk_len_at(self.step, target, kill_worker_at)
            if self.cfg.chunk_size > 1:
                # k == 1 still goes through the chunk path so the device
                # backend's streams stay invariant to chunk partitioning
                self._run_chunk(k, target, kill_worker_at)
            else:
                self._run_one_step(target)
            if (self.cfg.checkpoint.every_steps > 0
                    and self.step % self.cfg.checkpoint.every_steps == 0):
                self.save_checkpoint()
        return self._result()

    def _result(self) -> TrainResult:
        return TrainResult(
            self.params, self.ema, self.metrics, self.sim_time, self.step,
            self.restarts,
            mean_selected=self._sel_sum / max(self._sel_count, 1),
            mean_staleness=self._stal_sum / max(self._stal_count, 1),
            recovery_log=(list(self.injector.log)
                          if self.injector is not None else []),
            wall_time_s=self._wall_s,
            phase_times=dict(self._phase) if self._obs else {})

    def _chunk_len_at(self, step: int, target: int,
                      kill_worker_at: Dict[int, int]) -> int:
        """Steps from ``step`` until the next forced boundary: run target,
        checkpoint cadence, kill injection, or a pending chaos-plan fault
        — so failure handling and replay-exact resume semantics are
        untouched by chunking. Also used to predict the NEXT chunk's
        length for the prefetcher."""
        k = min(self.cfg.chunk_size, target - step)
        every = self.cfg.checkpoint.every_steps
        if every > 0:
            k = min(k, every - step % every)
        for s in kill_worker_at:
            if step < s < step + k:
                k = s - step
        if self.injector is not None:
            for s in self.injector.upcoming_steps():
                if step < s < step + k:
                    k = s - step
        return max(k, 1)

    def _next_chunk_specs(self, k: int, target: int,
                          kill_worker_at: Dict[int, int]) -> List:
        """Predicted (data_step, length) of the next ``prefetch_depth``
        chunks after the current one — what the prefetcher speculates on
        while the device runs this dispatch. Positions are data-pipeline
        steps; lengths follow the same boundary rules as the dispatch
        itself (``_chunk_len_at``), so speculation normally hits even at
        ragged checkpoint/kill boundaries — and a miss only costs the
        speculated work (generation is pure in (cfg, step))."""
        specs = []
        s = self.step + k
        d = self.pipeline.state.step + k
        for _ in range(max(self.cfg.prefetch_depth, 0)):
            if s >= target:
                break
            kk = self._chunk_len_at(s, target, kill_worker_at)
            specs.append((d, kk))
            s += kk
            d += kk
        return specs

    # -- observability hooks (no-ops unless tracer/metrics/measured) --------

    def _now(self) -> Optional[float]:
        return time.perf_counter() if self._obs else None

    def _fence(self) -> None:
        """block_until_ready at the chunk edge — the only place device
        work is ever awaited for observability, so the fused scan stays
        one dispatch and async dispatch is untouched when off."""
        with self.tracer.span("train/device_wait"):
            jax.block_until_ready(self.params)

    def _observe_chunk(self, k: int, t0: Optional[float],
                       data_s: float) -> None:
        if t0 is None:
            return
        dt = time.perf_counter() - t0
        self._phase["dispatch_s"] += dt - data_s
        self._phase["data_s"] += data_s
        if self.registry is not None:
            self.registry.histogram("train/chunk_time_s").observe(dt)
            self.registry.histogram("train/step_time_s").observe(dt / k)
        if self._measured_feed:
            # one measured per-worker row per dispatch: on a lockstep
            # mesh every live worker spends the fenced per-step wall
            # time; dead workers arrive at +inf (the estimator's
            # routing-around-crashes convention)
            per_step = (dt - data_s) / k
            row = np.where(self.sim.dead, np.inf, per_step)
            self.strategy.observe_measured(row)
            if self.registry is not None:
                h = self.registry.histogram("spmd/worker_step_s")
                for v in row[np.isfinite(row)]:
                    h.observe(float(v))

    def _run_one_step(self, target: int) -> None:
        """Legacy per-step path: one dispatch + one metrics sync per step."""
        t0 = self._now()
        with self.tracer.span("train/step", step=int(self.step)):
            td0 = self._now()
            with self.tracer.span("train/data_wait"):
                ev = self.sim.next_event()
                batch_np = self.pipeline.next()
                batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            data_s = time.perf_counter() - td0 if td0 is not None else 0.0
            mask = jnp.asarray(ev.mask)
            self.params, self.opt_state, self.ema, m = self.train_step(
                self.params, self.opt_state, self.ema,
                jnp.asarray(self.step, jnp.int32), batch, mask)
            if self._obs:
                self._fence()
        self._observe_chunk(1, t0, data_s)
        self.sim_time += ev.iteration_time
        self.step += 1
        selected = int(ev.mask.sum())
        self._sel_sum += selected
        self._sel_count += 1
        if self.step % self.cfg.log_every == 0 or self.step == target:
            rec = {"step": self.step, "sim_time": self.sim_time,
                   "selected": selected, "staleness": 0.0,
                   **{k: float(v) for k, v in m.items()}}
            self.metrics.append(rec)

    def _run_chunk(self, k: int, target: int,
                   kill_worker_at: Dict[int, int]) -> None:
        """Fused path: K steps in one lax.scan dispatch, one host sync."""
        step0 = jnp.asarray(self.step, jnp.int32)
        t0 = self._now()
        data_s = 0.0
        if self.cfg.straggler_backend == "device":
            # fully device-resident: batches, arrivals and masks are all
            # produced inside the scan body — no per-chunk host transfer
            with self.tracer.span("train/chunk", k=k, step=int(self.step)):
                self.pipeline.state.step += k
                dead = jnp.asarray(self.sim.dead)
                (self.params, self.opt_state, self.ema, ms, masks_dev,
                 times_dev) = self.chunk_step_device(
                    self.params, self.opt_state, self.ema, step0, k,
                    dead, self._chunk_key)
                if self._obs:
                    self._fence()
            masks = masks_dev                 # converted lazily iff logging
            times = np.asarray(times_dev, np.float64)
            self._sel_sum += float(jnp.sum(masks_dev))
            self.sim.reset_to_step(self.sim.step + k)
        else:
            with self.tracer.span("train/chunk", k=k, step=int(self.step)):
                td0 = self._now()
                with self.tracer.span("train/data_wait"):
                    chunk_np = self.prefetcher.get(
                        self.pipeline.state.step, k,
                        next_specs=self._next_chunk_specs(k, target,
                                                          kill_worker_at))
                    self.pipeline.state.step += k
                    batches = {key: jnp.asarray(v)
                               for key, v in chunk_np.items()}
                data_s = (time.perf_counter() - td0
                          if td0 is not None else 0.0)
                events = self.sim.next_events(k)
                masks = events.masks
                times = events.times
                self._sel_sum += float(masks.sum())
                self.params, self.opt_state, self.ema, ms = self.chunk_step(
                    self.params, self.opt_state, self.ema, step0, batches,
                    jnp.asarray(masks))
                if self._obs:
                    self._fence()
        self._observe_chunk(k, t0, data_s)
        self._sel_count += k
        # metrics sync only when a log record falls inside this chunk
        logged = [i for i in range(k)
                  if (self.step + i + 1) % self.cfg.log_every == 0
                  or (self.step + i + 1) == target]
        if logged:
            if not isinstance(masks, np.ndarray):
                masks = np.asarray(masks)
            ms_np = {key: np.asarray(v) for key, v in ms.items()}
        for i in range(k):
            self.sim_time += float(times[i])
            self.step += 1
            if logged and i == logged[0]:
                logged.pop(0)
                rec = {"step": self.step, "sim_time": self.sim_time,
                       "selected": int(masks[i].sum()), "staleness": 0.0,
                       **{key: float(v[i]) for key, v in ms_np.items()}}
                self.metrics.append(rec)

    # -- the event loop -------------------------------------------------------

    def _event_alive(self) -> int:
        return self.strategy.total_workers - len(self._event_dead)

    def _kill_event_worker(self, worker: int) -> None:
        if worker in self._event_dead:
            return
        self._event_dead.add(worker)
        self._sched.drop_worker(worker)
        if self._event_alive() == 0 or not self._sched.queue:
            raise RuntimeError("insufficient live workers")

    def _run_event(self, target: int,
                   kill_worker_at: Dict[int, int]) -> None:
        """Discrete-event parameter-server loop (async/softsync/staleness).

        Mirrors ``coordination.run_events`` arrival-for-arrival (the
        bit-exactness tests hold the two to the identical update and
        staleness sequence) and adds checkpoint cadence, kill injection,
        and the unified metrics records on top.
        """
        every = self.cfg.checkpoint.every_steps
        ema_decay = self.cfg.optimizer.ema_decay
        if kill_worker_at and not self.strategy.uses_clock:
            raise ValueError("failure injection does not apply to serial "
                             "rigs (the staleness strategy has a single "
                             "logical worker)")
        while self.step < target:
            self._apply_faults(self.step)
            if self.step in kill_worker_at:
                for kw in kill_worker_at.pop(self.step):
                    self._kill_event_worker(kw)
            t, w = self._sched.pop()
            batch = self._event_batch(w, int(self._draws[w]))
            self._draws[w] += 1
            loss, grads = self._grad_fn(self._read_params[w], batch)
            arrival = coordination.Arrival(
                index=self._arrival_count, worker=w, time=float(t),
                staleness=int(self.step - self._read_version[w]),
                version=self.step)
            self._arrival_count += 1
            if self.strategy.stals_per_arrival:
                self._stal_sum += arrival.staleness
                self._stal_count += 1
            ready = self.strategy.on_arrival(self._ev_state, grads, arrival)
            updated = False
            if ready is not None:
                self.params, self.opt_state, _ = self._update_fn(
                    self.params, self.opt_state, ready.grads,
                    jnp.asarray(self.step, jnp.int32))
                if ema_decay > 0:
                    self.ema = ema_lib.update(self.ema, self.params, ema_decay)
                # simulated seconds; for the serial rig the scheduler's
                # clock IS the arrival index (the legacy convention)
                self.sim_time = float(t)
                if not self.strategy.stals_per_arrival:
                    self._stal_sum += ready.staleness
                    self._stal_count += 1
                self._sel_sum += ready.selected
                self._sel_count += 1
                self.step += 1
                updated = True
                if (self.step % self.cfg.log_every == 0
                        or self.step == target):
                    self.metrics.append({
                        "step": self.step, "loss": float(loss),
                        "sim_time": self.sim_time,
                        "selected": ready.selected,
                        "staleness": float(ready.staleness)})
            # worker reads the fresh params and starts its next mini-batch
            self._read_params[w] = self.params
            self._read_version[w] = self.step
            self._sched.push(t, w)
            if updated and every > 0 and self.step % every == 0:
                self.save_checkpoint()

    def _run_event_chunked(self, target: int,
                           kill_worker_at: Dict[int, int]) -> None:
        """Fused event path: a host-planned block of arrivals per
        ``lax.scan`` dispatch (see ``coordination.plan_events`` and
        ``build_event_chunk_step``).

        Chunk lengths are counted in PS *updates* (``_chunk_len_at`` —
        the same boundary rules as mask mode), and every chunk's plan
        ends exactly on its last update, so checkpoints and kill
        injections land on identical steps, with identical state, as the
        per-arrival path.
        """
        every = self.cfg.checkpoint.every_steps
        if kill_worker_at and not self.strategy.uses_clock:
            raise ValueError("failure injection does not apply to serial "
                             "rigs (the staleness strategy has a single "
                             "logical worker)")
        while self.step < target:
            self._apply_faults(self.step)
            if self.step in kill_worker_at:
                for kw in kill_worker_at.pop(self.step):
                    self._kill_event_worker(kw)
            u = self._chunk_len_at(self.step, target, kill_worker_at)
            plan = coordination.plan_events(
                self.strategy, self._sched, self._plan_state,
                self._read_version, self._draws,
                version0=self.step, arrival0=self._arrival_count,
                num_updates=u)
            self._arrival_count += len(plan)
            batches = [self._event_batch_host(int(wk), int(d))
                       for wk, d in zip(plan.worker, plan.draw)]
            chunk_batches = {
                k: jnp.asarray(np.stack([b[k] for b in batches]))
                for k in batches[0]}
            (self.params, self.opt_state, self.ema, self._workers_stacked,
             self._scan_aux, losses) = self._event_chunk(
                self.params, self.opt_state, self.ema, self._workers_stacked,
                self._scan_aux, chunk_batches, plan.rows())
            # host bookkeeping straight off the plan — no device sync
            if self.strategy.stals_per_arrival:
                self._stal_sum += float(plan.arrival_staleness.sum())
                self._stal_count += len(plan)
            else:
                self._stal_sum += float(plan.update_staleness[plan.apply].sum())
                self._stal_count += plan.updates
            self._sel_sum += float(plan.selected[plan.apply].sum())
            self._sel_count += plan.updates
            losses_np = None          # read back only if a record logs
            for k in np.nonzero(plan.apply)[0]:
                self.step += 1
                self.sim_time = float(plan.time[k])
                if self.step % self.cfg.log_every == 0 or self.step == target:
                    if losses_np is None:
                        losses_np = np.asarray(losses)
                    self.metrics.append({
                        "step": self.step, "loss": float(losses_np[k]),
                        "sim_time": self.sim_time,
                        "selected": int(plan.selected[k]),
                        "staleness": float(plan.update_staleness[k])})
            if every > 0 and self.step % every == 0:
                self.save_checkpoint()


# ---------------------------------------------------------------------------
# The one-call entry point
# ---------------------------------------------------------------------------


def run_experiment(cfg: TrainConfig, *, latency: Optional[LatencyModel] = None,
                   data_cfg: Optional[SyntheticLMConfig] = None,
                   model=None, batch_fn: Optional[Callable] = None,
                   resume: bool = False, save_final: bool = False,
                   kill_worker_at: Optional[Dict[int, Any]] = None,
                   min_alive_behavior: str = "rescale",
                   injector: Optional[faults_lib.FaultInjector] = None,
                   tracer=None, metrics=None) -> TrainResult:
    """Run any coordination regime — full_sync, backup, timeout,
    dynamic_backup, async, softsync, staleness — from ``cfg.aggregation``
    alone.

    Builds the Trainer (strategy via the registry), initializes or resumes
    state, runs ``cfg.total_steps`` steps (PS updates in event mode), and
    returns the unified :class:`TrainResult`. ``model``/``batch_fn`` plug
    non-LM problems into event regimes (e.g. the MNIST staleness rig).

    ``cfg.faults.spec`` attaches a chaos plan (an ``injector`` argument
    overrides it — the supervisor passes its own so faults fire at most
    once across restarts). An injected ``preempt``/crash propagates out of
    this call; ``repro.train.supervisor.run_supervised`` is the entry
    point that catches it, restores, and continues.
    """
    if injector is None:
        injector = faults_lib.build_injector(
            getattr(cfg, "faults", None), num_steps=cfg.total_steps,
            num_workers=cfg.aggregation.total_workers)
    tr = Trainer(cfg, latency=latency, data_cfg=data_cfg, model=model,
                 batch_fn=batch_fn, injector=injector, tracer=tracer,
                 metrics=metrics)
    if resume and ckpt_lib.latest_step(cfg.checkpoint.directory) is not None:
        tr.restore_checkpoint()
        if injector is not None:
            injector.resync(tr)
    else:
        tr.init_state()
    res = tr.run(cfg.total_steps, kill_worker_at=kill_worker_at,
                 min_alive_behavior=min_alive_behavior)
    if save_final:
        tr.save_checkpoint()
    return res
