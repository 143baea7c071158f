"""The step's phases on a device trace, and the loop's spans on its clock.

* the compiled step program carries the named phases in its ``op_name``
  metadata, on the sim backend and on the mesh (one parametrised test);
* a profile of a ``Trainer.run`` with no ``Tracer`` holds the loop's
  spans, nested, on the host plane, and the loop fences nothing;
* ``repro.obs.profile`` reads ms per step of each phase, the ops left
  outside them and the gaps named by program span, from a small fixture
  shaped as a TPU profile reads.
"""
import json
import os
import re

import jax
import pytest

from repro.obs import Tracer
from repro.obs import profile

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_scopes.json")


def _cfg(tmp_path, backend="sim", chunk=1, remat="none"):
    from benchmarks.common import tiny_lm_config
    from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                    ExecutionConfig, OptimizerConfig,
                                    ShapeConfig, TrainConfig, replace)
    return TrainConfig(
        model=replace(tiny_lm_config(), remat=remat),
        shape=ShapeConfig("t", 16, 8, "train"),
        aggregation=AggregationConfig(strategy="backup", num_workers=3,
                                      backup_workers=1),
        optimizer=OptimizerConfig(name="rmsprop_momentum", learning_rate=1e-3,
                                  ema_decay=0.99),
        checkpoint=CheckpointConfig(directory=str(tmp_path), every_steps=0),
        execution=ExecutionConfig(backend=backend, mesh_data=1),
        log_every=2, chunk_size=chunk, straggler_backend="host")


def _trainer(tmp_path, **kw):
    from repro.core.straggler import Uniform
    from repro.train.loop import Trainer
    tr = Trainer(_cfg(tmp_path, **kw), latency=Uniform(1.0, 2.0))
    tr.init_state()
    return tr


# ---------------------------------------------------------------------------
# Device side: the named phases in the compiled step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["sim", "spmd"])
def test_step_program_names_its_phases(tmp_path, backend):
    import jax.numpy as jnp
    tr = _trainer(tmp_path, backend=backend, remat="full")
    batch = {k: jnp.asarray(v) for k, v in tr.pipeline.next().items()}
    mask = jnp.ones((tr.cfg.aggregation.total_workers,), bool)
    text = tr.train_step.lower(
        tr.params, tr.opt_state, tr.ema, jnp.asarray(0, jnp.int32), batch,
        mask).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]+)"', text))

    def some(pred):
        return any(pred(p) for p in paths)

    def grad(p):        # the mesh vmaps its per-worker grad over workers
        return bool({"grad", "vmap(grad)"} & set(p.split("/")))

    assert some(lambda p: grad(p) and "jvp(" in p
                and "transpose(" not in p)                    # forward
    assert some(lambda p: grad(p) and "transpose(jvp(" in p)
    assert some(lambda p: "rematted_computation" in p)
    # the attention core in forward, backward and recompute alike
    for phase in ("forward", "backward", "recompute"):
        assert some(lambda p: profile.PHASES[phase](p)
                    and profile.PHASES["attention"](p)), phase
    assert some(profile.PHASES["update"])
    assert some(lambda p: "/optimizer/" in p)
    assert some(lambda p: "/ema/" in p)
    on_mesh = backend == "spmd"
    assert some(profile.PHASES["grad_stack"]) == on_mesh
    assert some(profile.PHASES["reduce"]) == on_mesh


# ---------------------------------------------------------------------------
# Host side: the loop's spans on the profiler's clock, and no fences
# ---------------------------------------------------------------------------


def test_untraced_run_puts_loop_spans_on_the_profile(tmp_path):
    tr = _trainer(tmp_path)
    tr.run(1)                                     # compile outside
    prof_dir = str(tmp_path / "prof")
    with jax.profiler.trace(prof_dir):
        tr.run(2)
    spans = profile.load(prof_dir)["program"]
    steps = [s for s in spans if s[0] == "train/step"]
    assert len(steps) == 2
    inner = ("train/select", "train/data_wait", "train/dispatch",
             "train/metrics_sync")
    for name, start, dur in steps:
        # both steps are logged (log_every=2, and the run's last step)
        held = {n for n, s, d in spans
                if n in inner and start <= s and s + d <= start + dur}
        assert held == set(inner)


@pytest.mark.parametrize("chunk", [1, 4])
def test_loop_without_tracer_fences_nothing(tmp_path, monkeypatch, chunk):
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    tr = _trainer(tmp_path / "a", chunk=chunk)
    tr.run(4)
    assert calls == []
    # the same loop with a Tracer fences once per dispatch
    from repro.core.straggler import Uniform
    from repro.train.loop import Trainer
    traced = Trainer(_cfg(tmp_path / "b", chunk=chunk),
                     latency=Uniform(1.0, 2.0), tracer=Tracer())
    traced.init_state()
    traced.run(4)
    assert len(calls) == (4 if chunk == 1 else 1)


# ---------------------------------------------------------------------------
# The op's scope path, from the event metadata of a profile's device plane
# ---------------------------------------------------------------------------


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """Protobuf wire bytes of (field, value) pairs: int -> varint,
    str/bytes -> length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_metadata_reads_tf_op_from_the_protobuf(tmp_path):
    text = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    stat_names = [(5, _msg((1, i), (2, _msg((1, i), (2, name)))))
                  for i, name in ((1, "tf_op"), (2, "hlo_category"),
                                  (3, "loop fusion"))]
    event = _msg((1, 7), (2, text), (4, "fusion.1"),
                 (5, _msg((1, 1), (5, "jit(step_fn)/optimizer/add:"))),
                 (5, _msg((1, 2), (7, 3))))           # a reference stat
    device = _msg((1, 1), (2, "/device:TPU:0"),
                  (4, _msg((1, 7), (2, event))), *stat_names)
    host = _msg((1, 2), (2, "/host:CPU"), (4, _msg((1, 7), (2, event))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))

    meta = profile.op_metadata(str(path))
    assert list(meta) == ["/device:TPU:0"]         # device planes only
    stats = meta["/device:TPU:0"]
    assert stats[text] is stats["fusion.1"]          # name and display name
    assert stats[text] == {"tf_op": "jit(step_fn)/optimizer/add:",
                           "hlo_category": "loop fusion"}
    assert profile._path(text, stats) == "jit(step_fn)/optimizer/add"
    assert profile._path("%copy.2 = f32[8]{0} copy(%p)", stats) == ""


# ---------------------------------------------------------------------------
# The reduction, on a fixture shaped as a TPU profile
# ---------------------------------------------------------------------------


@pytest.fixture()
def trace():
    with open(FIXTURE) as f:
        return json.load(f)


# ms per step of each phase in the fixture: ns summed per chip, averaged
# over TPU:0 and TPU:1, over its 2 steps (1 ns = 1e-6 ms)
EXPECTED_MS = {
    "forward": (300 + 700) / 2 / 2 * 1e-6,
    "backward": (300 + 0) / 2 / 2 * 1e-6,
    "recompute": (150 + 0) / 2 / 2 * 1e-6,
    "update": (150 + 300) / 2 / 2 * 1e-6,
    "attention": (650 + 200) / 2 / 2 * 1e-6,
    "grad_stack": (100 + 0) / 2 / 2 * 1e-6,
    "reduce": (350 + 0) / 2 / 2 * 1e-6,
}


@pytest.mark.parametrize("phase", sorted(EXPECTED_MS))
def test_phase_ms_per_step(trace, phase):
    assert profile.count_steps(trace) == 2
    got = profile.phase_ms(trace, 2)[phase]
    assert got == pytest.approx(EXPECTED_MS[phase])


def test_phase_reads_nothing_where_no_op_matches(trace):
    for dev in trace["devices"]:
        trace["devices"][dev] = [e for e in trace["devices"][dev]
                                 if not e[0].startswith(("concatenate",
                                                         "shard_map",
                                                         "psum"))]
    got = profile.phase_ms(trace, 2)
    assert got["grad_stack"] is None and got["reduce"] is None
    assert got["forward"] == pytest.approx(EXPECTED_MS["forward"])


def test_unscoped_ops_count_nowhere(trace):
    summary = profile.summary(trace, 2)
    # the loop around the body ops counts nowhere, and neither does
    # copy.3, which has no path; fusion.21 counts though a zero-length
    # copy-start sits inside it
    assert [op for op, _, _ in summary["unscoped"]] == ["copy.3 copy"]
    assert summary["unscoped"][0][1] == pytest.approx(50 / 2 * 1e-9)
    busy = (1400 + 1000) / 2 / 2 * 1e-6
    assert summary["busy_ms_per_step"] == pytest.approx(busy)
    covered = sum(EXPECTED_MS[p] for p in profile.PARTITION)
    assert summary["covered_share"] == pytest.approx(covered / busy)
    assert summary["covered_share"] < 1.0


def test_gap_named_by_innermost_program_span(trace):
    gaps = sorted((dev, name, round(s * 1e9))
                  for dev, name, s in profile.idle_gaps(trace))
    assert gaps == [
        ("TPU:0", "train/metrics_sync", 100),     # 1800-1900
        ("TPU:0", "train/metrics_sync", 500),     # 2500-3000
        ("TPU:1", "train/metrics_sync", 500),     # 2500-3000
        ("TPU:1", "train/step", 500),             # 1500-2000: no inner span
    ]
