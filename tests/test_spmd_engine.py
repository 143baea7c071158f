"""SPMD execution engine: mesh parity with the simulated backend.

Mesh semantics run in subprocesses with xla_force_host_platform_device_count
(the main test process keeps 1 device per the dry-run contract — see
tests/conftest.py); the engine's degenerate mesh_data=1 case and the pure
helpers run in process so tier-1 covers the engine on every change.
"""
from pathlib import Path

import numpy as np
import pytest

from test_spmd_subprocess import run_py as _run_py

_ROOT = Path(__file__).resolve().parents[1]


def run_py(code: str, devices: int = 8, timeout: int = 600) -> str:
    return _run_py(code, devices=devices, timeout=timeout)


# ---------------------------------------------------------------------------
# In-process: pure helpers + the degenerate single-device mesh
# ---------------------------------------------------------------------------


def test_flatten_unflatten_roundtrip():
    import jax
    import jax.numpy as jnp
    from repro.distributed import spmd_engine

    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(2, 3, 2),
            "b": {"w": jnp.ones((2, 5), jnp.float32),
                  "s": jnp.asarray([2.0, 3.0])}}
    flat, spec = spmd_engine.flatten_stacked(tree)
    assert flat.shape == (2, 6 + 5 + 1)
    rec = spmd_engine.unflatten_vector(flat[1], spec)
    for a, b in zip(jax.tree_util.tree_leaves(rec),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[1])


def test_layout_validation_errors():
    from repro.configs.base import ExecutionConfig
    from repro.distributed import spmd_engine

    with pytest.raises(ValueError, match="divisible by"):
        spmd_engine.validate_layout(6, 24, 4)         # 6 workers on 4 shards
    with pytest.raises(ValueError, match="global_batch"):
        spmd_engine.validate_layout(4, 22, 4)
    assert spmd_engine.validate_layout(8, 16, 4) == 2
    # asking for more devices than exist names the XLA_FLAGS escape hatch
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        spmd_engine.build_mesh(ExecutionConfig(backend="spmd", mesh_data=64))


def test_unknown_execution_backend_rejected(tmp_path):
    from repro import configs
    from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                    ExecutionConfig, ShapeConfig, TrainConfig)
    from repro.train.loop import Trainer

    cfg = TrainConfig(model=configs.get_smoke_config("qwen3-0.6b"),
                      shape=ShapeConfig("t", 16, 8, "train"),
                      aggregation=AggregationConfig(strategy="backup",
                                                    num_workers=3,
                                                    backup_workers=1),
                      checkpoint=CheckpointConfig(directory=str(tmp_path)),
                      execution=ExecutionConfig(backend="tpu_pod"))
    with pytest.raises(ValueError, match="unknown execution backend"):
        Trainer(cfg)


def _tiny_model_cfg():
    from repro import configs
    from repro.configs.base import replace
    return replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                   d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                   d_ff=64, vocab_size=64, vocab_pad_multiple=16)


def _train_cfg(backend, tmp_path, *, strategy="backup", workers=6, backups=2,
               deadline=0.5, mesh_data=1, mesh_model=1, chunk=1, every=0,
               use_kernel=True, grad_batch=0, bucket_size=0):
    from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                    ExecutionConfig, OptimizerConfig,
                                    ShapeConfig, TrainConfig)
    total = workers + backups
    return TrainConfig(
        model=_tiny_model_cfg(),
        shape=ShapeConfig("t", 16, 2 * total, "train"),
        aggregation=AggregationConfig(strategy=strategy, num_workers=workers,
                                      backup_workers=backups,
                                      deadline_s=deadline),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False,
                                  ema_decay=0.99),
        checkpoint=CheckpointConfig(directory=str(tmp_path), every_steps=every),
        execution=ExecutionConfig(backend=backend, mesh_data=mesh_data,
                                  mesh_model=mesh_model,
                                  use_kernel=use_kernel,
                                  grad_batch=grad_batch,
                                  bucket_size=bucket_size),
        seed=0, total_steps=6, log_every=1, chunk_size=chunk)


def _assert_close_trees(a, b, rtol=2e-4, atol=2e-5):
    import jax
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("chunk", [1, 3])
def test_spmd_single_device_mesh_matches_sim(tmp_path, chunk):
    """mesh_data=1 runs the full engine (shard_map + kernel reduce + psum)
    on the real single device — in-process tier-1 coverage of the code
    path the multi-device subprocess tests exercise at scale."""
    from repro.core.straggler import Uniform
    from repro.train.loop import Trainer

    lat = Uniform(1.0, 2.0)
    ta = Trainer(_train_cfg("sim", tmp_path / "a", chunk=chunk), latency=lat)
    ta.init_state()
    ra = ta.run(6)
    tb = Trainer(_train_cfg("spmd", tmp_path / "b", chunk=chunk), latency=lat)
    tb.init_state()
    rb = tb.run(6)
    _assert_close_trees(ra.params, rb.params)
    _assert_close_trees(ra.ema, rb.ema)
    np.testing.assert_allclose([m["loss"] for m in ra.metrics],
                               [m["loss"] for m in rb.metrics],
                               rtol=2e-4, atol=2e-5)
    assert ra.sim_time == rb.sim_time
    assert [m["selected"] for m in ra.metrics] == \
        [m["selected"] for m in rb.metrics]


def test_grad_batch_validation_errors():
    """ExecutionConfig.grad_batch: structured errors on bad worker-batch
    sizes — negatives and non-divisors of W_local (listing the valid
    divisors), with 0 resolving to the full-vmap fast path."""
    from repro.distributed.spmd_engine import validate_grad_batch

    assert validate_grad_batch(0, 4) == 4       # vmap ALL local workers
    assert validate_grad_batch(1, 4) == 1       # sequential lax.map
    assert validate_grad_batch(2, 4) == 2       # microbatches of 2
    assert validate_grad_batch(6, 6) == 6
    with pytest.raises(ValueError, match="non-negative"):
        validate_grad_batch(-1, 4)
    with pytest.raises(ValueError, match=r"0 \(vmap all\) or one of "
                                         r"\[1, 2, 3, 6\]"):
        validate_grad_batch(4, 6)
    with pytest.raises(ValueError, match="does not divide"):
        validate_grad_batch(8, 4)


@pytest.mark.parametrize("grad_batch", [1, 2, 4])
def test_spmd_grad_batch_paths_match_vmap(tmp_path, grad_batch):
    """The three per-worker batching strategies (full vmap, sequential
    lax.map, vmapped microbatches) are the SAME function: identical
    trajectories on the single-device mesh, in-process for tier-1.
    W_local = 8 here (6 workers + 2 backups on mesh_data=1), so
    grad_batch=2 and 4 are genuine microbatches."""
    from repro.core.straggler import Uniform
    from repro.train.loop import Trainer

    lat = Uniform(1.0, 2.0)
    tv = Trainer(_train_cfg("spmd", tmp_path / "v", chunk=2, grad_batch=0),
                 latency=lat)
    tv.init_state()
    rv = tv.run(4)
    tb = Trainer(_train_cfg("spmd", tmp_path / "b", chunk=2,
                            grad_batch=grad_batch), latency=lat)
    tb.init_state()
    rb = tb.run(4)
    _assert_close_trees(rv.params, rb.params, rtol=1e-5, atol=1e-6)
    _assert_close_trees(rv.ema, rb.ema, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose([m["loss"] for m in rv.metrics],
                               [m["loss"] for m in rb.metrics],
                               rtol=1e-5, atol=1e-6)


def test_spmd_bucketed_psum_matches_single_bucket(tmp_path):
    """bucket_size > 0 cuts the fused flatten into several collectives
    (the tail scalars riding the last); the trajectory must not move."""
    from repro.core.straggler import Uniform
    from repro.train.loop import Trainer

    lat = Uniform(1.0, 2.0)
    t1 = Trainer(_train_cfg("spmd", tmp_path / "one", chunk=2),
                 latency=lat)
    t1.init_state()
    r1 = t1.run(4)
    t2 = Trainer(_train_cfg("spmd", tmp_path / "many", chunk=2,
                            bucket_size=5000), latency=lat)
    t2.init_state()
    r2 = t2.run(4)
    _assert_close_trees(r1.params, r2.params, rtol=1e-5, atol=1e-6)
    _assert_close_trees(r1.ema, r2.ema, rtol=1e-5, atol=1e-6)


def test_spmd_kernel_and_jnp_reduce_agree(tmp_path):
    """The Pallas backup_reduce in-shard reduction == the jnp reference."""
    from repro.core.straggler import Uniform
    from repro.train.loop import Trainer

    lat = Uniform(1.0, 2.0)
    tk = Trainer(_train_cfg("spmd", tmp_path / "k", chunk=2, use_kernel=True),
                 latency=lat)
    tk.init_state()
    rk = tk.run(4)
    tj = Trainer(_train_cfg("spmd", tmp_path / "j", chunk=2, use_kernel=False),
                 latency=lat)
    tj.init_state()
    rj = tj.run(4)
    _assert_close_trees(rk.params, rj.params, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Subprocess: real multi-device meshes (the acceptance parity matrix)
# ---------------------------------------------------------------------------

# Parity + checkpoint/resume for one mesh, all three mask strategies.
# The mesh run must match the single-device simulated Trainer's loss and
# param trajectory (allclose — the engine sums explicit per-worker
# gradients where the sim backend differentiates one weighted loss), and
# resume from a checkpoint taken mid-run must land on the same state.
# On the (4, 2) mesh the 'model' axis does REAL work: params/opt/EMA are
# sharded and the per-worker gradient is computed tensor-parallel
# (docs/spmd.md) — the same parity bars apply unchanged.
_PARITY_CODE = r"""
import numpy as np, jax
from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                ExecutionConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig, replace)
from repro.core.straggler import Uniform
from repro.distributed.sharding import tp_plan
from repro.train.loop import Trainer

MESH_DATA, MESH_MODEL = __MESH__
model_cfg = replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                    d_ff=64, vocab_size=64, vocab_pad_multiple=16)
if MESH_MODEL > 1:
    # the tiny config divides: every TP group must actually shard
    plan = tp_plan(model_cfg, MESH_MODEL)
    assert plan.attn and plan.ffn and plan.vocab, plan

def cfg(backend, strategy, ck, workers, backups, every=0, chunk=3):
    return TrainConfig(
        model=model_cfg,
        shape=ShapeConfig("t", 16, 16, "train"),
        aggregation=AggregationConfig(strategy=strategy, num_workers=workers,
                                      backup_workers=backups, deadline_s=0.5),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False, ema_decay=0.99),
        checkpoint=CheckpointConfig(directory=ck, every_steps=every),
        execution=ExecutionConfig(backend=backend, mesh_data=MESH_DATA,
                                  mesh_model=MESH_MODEL),
        seed=0, total_steps=8, log_every=1, chunk_size=chunk)

def close(a, b, rtol=2e-4, atol=2e-5):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                                   atol=atol)

lat = Uniform(1.0, 2.0)
for strategy, workers, backups in (("full_sync", 8, 0), ("backup", 6, 2),
                                   ("timeout", 8, 0)):
    ta = Trainer(cfg("sim", strategy, f"/tmp/spmd_sim_{strategy}", workers,
                     backups), latency=lat)
    ta.init_state(); ra = ta.run(8)
    tb = Trainer(cfg("spmd", strategy, f"/tmp/spmd_mesh_{strategy}", workers,
                     backups), latency=lat)
    tb.init_state(); rb = tb.run(8)
    if MESH_MODEL > 1:
        # state genuinely sharded over 'model' (not just allowed to be)
        spec = tb.params["seg_dense"]["attn"]["wq"]["w"].sharding.spec
        assert "model" in tuple(spec), spec
        spec = tb.opt_state["m"]["embed"]["embedding"].sharding.spec
        assert "model" in tuple(spec), spec
    close(ra.params, rb.params)
    close(ra.ema, rb.ema)
    np.testing.assert_allclose([m["loss"] for m in ra.metrics],
                               [m["loss"] for m in rb.metrics],
                               rtol=2e-4, atol=2e-5)
    assert ra.sim_time == rb.sim_time
    assert [m["selected"] for m in ra.metrics] == \
        [m["selected"] for m in rb.metrics]
    print(strategy, "parity OK")

# checkpoint/resume THROUGH a mesh-executed chunk: every_steps=3 with
# chunk_size=2 puts a forced chunk boundary inside the scan cadence; the
# resumed mesh trainer must rejoin the uninterrupted sim trajectory.
ck = "/tmp/spmd_resume"
t1 = Trainer(cfg("spmd", "backup", ck, 6, 2, every=3, chunk=2), latency=lat)
t1.init_state(); t1.run(3)                       # checkpoints at step 3
t2 = Trainer(cfg("spmd", "backup", ck, 6, 2, every=3, chunk=2), latency=lat)
t2.restore_checkpoint()
assert t2.step == 3
r2 = t2.run(5)                                   # -> step 8
ref = Trainer(cfg("sim", "backup", "/tmp/spmd_resume_ref", 6, 2), latency=lat)
ref.init_state(); rr = ref.run(8)
close(rr.params, r2.params)
close(rr.ema, r2.ema)
assert rr.sim_time == r2.sim_time
print("resume-through-chunk parity OK")
"""


def test_spmd_parity_mesh_4x2():
    out = run_py(_PARITY_CODE.replace("__MESH__", "(4, 2)"))
    assert "resume-through-chunk parity OK" in out


def test_spmd_parity_mesh_8x1():
    out = run_py(_PARITY_CODE.replace("__MESH__", "(8, 1)"))
    assert "resume-through-chunk parity OK" in out


def test_spmd_grad_batch_parity_matrix():
    """The acceptance matrix on a real TP (2, 2) mesh (W_local = 4):
    for every mask strategy, the vmapped (grad_batch=0), sequential
    (grad_batch=1) and microbatched (grad_batch=2, with a multi-bucket
    fused psum) engines all match the single-device sim trajectory —
    batching and bucketing are execution detail, never semantics."""
    run_py(r"""
import numpy as np, jax
from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                ExecutionConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig, replace)
from repro.core.straggler import Uniform
from repro.train.loop import Trainer

model_cfg = replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                    d_ff=64, vocab_size=64, vocab_pad_multiple=16)

def cfg(backend, strategy, ck, workers, backups, grad_batch=0, bucket=0):
    return TrainConfig(
        model=model_cfg, shape=ShapeConfig("t", 16, 16, "train"),
        aggregation=AggregationConfig(strategy=strategy, num_workers=workers,
                                      backup_workers=backups, deadline_s=0.5),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False, ema_decay=0.99),
        checkpoint=CheckpointConfig(directory=ck, every_steps=0),
        execution=ExecutionConfig(backend=backend, mesh_data=2, mesh_model=2,
                                  grad_batch=grad_batch, bucket_size=bucket),
        seed=0, total_steps=6, log_every=1, chunk_size=3)

def close(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-4, atol=2e-5)

lat = Uniform(1.0, 2.0)
for strategy, workers, backups in (("full_sync", 8, 0), ("backup", 6, 2),
                                   ("timeout", 8, 0)):
    ref = Trainer(cfg("sim", strategy, f"/tmp/gbm_sim_{strategy}", workers,
                      backups), latency=lat)
    ref.init_state(); rr = ref.run(6)
    for gb, bucket in ((0, 0), (1, 0), (2, 5000)):
        tr = Trainer(cfg("spmd", strategy, f"/tmp/gbm_{strategy}_{gb}",
                         workers, backups, gb, bucket), latency=lat)
        tr.init_state(); rt = tr.run(6)
        close(rr.params, rt.params)
        close(rr.ema, rt.ema)
        np.testing.assert_allclose([m["loss"] for m in rr.metrics],
                                   [m["loss"] for m in rt.metrics],
                                   rtol=2e-4, atol=2e-5)
        assert rr.sim_time == rt.sim_time
        assert [m["selected"] for m in rr.metrics] == \
            [m["selected"] for m in rt.metrics]
        print(strategy, "gb", gb, "bucket", bucket, "parity OK")
print("grad-batch matrix OK")
""")


def test_spmd_grad_batch_resume_through_chunk_tp():
    """Checkpoint/resume THROUGH a mesh chunk with grad_batch=2 on the
    TP (4, 2) mesh: the batched-gradient engine rejoins the
    uninterrupted sim trajectory exactly like the default engine."""
    run_py(r"""
import numpy as np, jax
from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                ExecutionConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig, replace)
from repro.core.straggler import Uniform
from repro.train.loop import Trainer

model_cfg = replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                    d_ff=64, vocab_size=64, vocab_pad_multiple=16)

def cfg(backend, ck, mesh=(1, 1), grad_batch=0, every=0):
    return TrainConfig(
        model=model_cfg, shape=ShapeConfig("t", 16, 16, "train"),
        aggregation=AggregationConfig(strategy="backup", num_workers=6,
                                      backup_workers=2),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False, ema_decay=0.99),
        checkpoint=CheckpointConfig(directory=ck, every_steps=every),
        execution=ExecutionConfig(backend=backend, mesh_data=mesh[0],
                                  mesh_model=mesh[1], grad_batch=grad_batch),
        seed=0, total_steps=8, log_every=1, chunk_size=2)

def close(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-4, atol=2e-5)

lat = Uniform(1.0, 2.0)
ck = "/tmp/gb_resume"
t1 = Trainer(cfg("spmd", ck, (4, 2), grad_batch=2, every=3), latency=lat)
t1.init_state(); t1.run(3)                       # checkpoints at step 3
t2 = Trainer(cfg("spmd", ck, (4, 2), grad_batch=2, every=3), latency=lat)
t2.restore_checkpoint()
assert t2.step == 3
r2 = t2.run(5)                                   # -> step 8
ref = Trainer(cfg("sim", "/tmp/gb_resume_ref"), latency=lat)
ref.init_state(); rr = ref.run(8)
close(rr.params, r2.params)
close(rr.ema, r2.ema)
assert rr.sim_time == r2.sim_time
print("grad-batch resume-through-chunk parity OK")
""")


def test_spmd_rescale_shrinks_worker_axis():
    """When failures push alive below N, the elastic rescale shrinks the
    mesh 'data' axis to the largest size the new worker count divides —
    the run continues instead of crashing in layout validation."""
    run_py(r"""
import numpy as np
from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                ExecutionConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig, replace)
from repro.core.straggler import Uniform
from repro.train.loop import Trainer

model_cfg = replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                    d_ff=64, vocab_size=64, vocab_pad_multiple=16)
cfg = TrainConfig(
    model=model_cfg,
    shape=ShapeConfig("t", 16, 16, "train"),
    aggregation=AggregationConfig(strategy="full_sync", num_workers=8),
    optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                              scale_lr_with_workers=False, ema_decay=0.0),
    checkpoint=CheckpointConfig(directory="/tmp/spmd_rescale", every_steps=0),
    execution=ExecutionConfig(backend="spmd", mesh_data=8),
    seed=0, total_steps=6, log_every=1, chunk_size=2)
tr = Trainer(cfg, latency=Uniform(1.0, 2.0))
tr.init_state()
res = tr.run(6, kill_worker_at={2: 3})
assert res.restarts == 1
# 7 alive -> rounded to 4 (divisor of batch 16); mesh axis follows
assert tr.cfg.aggregation.total_workers == 4
assert tr.cfg.execution.mesh_data == 4
assert res.steps == 6
assert all(np.isfinite([m["loss"] for m in res.metrics]))
print("spmd rescale OK")
""")


def test_spmd_cli_smoke():
    """--execution spmd --mesh-data N end to end through the launcher."""
    run_py(r"""
from repro.launch import train as train_cli
train_cli.main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "4",
                "--workers", "3", "--backups", "1", "--batch-per-worker", "2",
                "--seq", "16", "--ckpt", "/tmp/spmd_cli_ck",
                "--optimizer", "momentum", "--lr", "0.05",
                "--execution", "spmd", "--mesh-data", "4",
                "--chunk-size", "2"])
import os
assert os.path.exists(os.path.join("/tmp/spmd_cli_ck", "LATEST"))
print("spmd cli OK")
""", devices=4)


@pytest.mark.parametrize("argv", [
    ["--strategy", "backup", "--mesh-data", "2"],              # no spmd
    ["--strategy", "backup", "--mesh-model", "2"],             # no spmd
    ["--strategy", "async", "--execution", "spmd"],            # event regime
    ["--strategy", "backup", "--execution", "spmd",
     "--straggler-backend", "device"],                         # device masks
    ["--strategy", "backup", "--workers", "3", "--backups", "0",
     "--execution", "spmd", "--mesh-data", "2"],               # 3 % 2 != 0
    ["--strategy", "backup", "--grad-batch", "2"],             # no spmd
    ["--strategy", "backup", "--bucket-size", "4096"],         # no spmd
    ["--strategy", "backup", "--workers", "6", "--backups", "2",
     "--execution", "spmd", "--mesh-data", "2",
     "--grad-batch", "3"],                                     # 4 % 3 != 0
    ["--strategy", "backup", "--execution", "spmd",
     "--grad-batch", "-1"],                                    # negative
])
def test_spmd_cli_rejects_mismatched_args(argv):
    from repro.launch import train as train_cli
    with pytest.raises(SystemExit):
        train_cli.main(argv + ["--smoke", "--steps", "1"])


def test_grad_batch_cli_error_names_valid_divisors(capsys):
    """The argparse error surfaces the engine's structured message: the
    offending value AND the divisors that would work."""
    from repro.launch import train as train_cli
    with pytest.raises(SystemExit):
        train_cli.main(["--strategy", "backup", "--workers", "6",
                        "--backups", "2", "--execution", "spmd",
                        "--mesh-data", "2", "--grad-batch", "3",
                        "--smoke", "--steps", "1"])
    err = capsys.readouterr().err
    assert "--grad-batch: grad_batch: 3 does not divide" in err
    assert "W_local=4" in err
    assert "[1, 2, 4]" in err


def test_spmd_grad_batch_cli_smoke():
    """--grad-batch / --bucket-size thread from argv to the engine."""
    run_py(r"""
from repro.launch import train as train_cli
train_cli.main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "4",
                "--workers", "3", "--backups", "1", "--batch-per-worker", "2",
                "--seq", "16", "--ckpt", "/tmp/gb_cli_ck",
                "--optimizer", "momentum", "--lr", "0.05",
                "--execution", "spmd", "--mesh-data", "2",
                "--grad-batch", "2", "--bucket-size", "4096",
                "--chunk-size", "2"])
import os
assert os.path.exists(os.path.join("/tmp/gb_cli_ck", "LATEST"))
print("grad-batch cli OK")
""", devices=4)


def test_spmd_regression_guard(tmp_path):
    """check_spmd_regression: ratios guard against DROPS, the bytes axis
    against GROWTH, small drift passes, >20% fails with exit 1."""
    import importlib
    import json
    import sys as _sys

    _sys.path.insert(0, str(_ROOT / "benchmarks"))
    guard = importlib.import_module("check_spmd_regression")

    base = {"bench": "spmd",
            "spmd_vs_sim_w8_chunk32_m1": 0.50,
            "spmd_bytes_per_step_w8_chunk32_m1": 50000.0}

    def check(fresh):
        b, f = tmp_path / "base.json", tmp_path / "fresh.json"
        b.write_text(json.dumps(base))
        f.write_text(json.dumps({"bench": "spmd", **fresh}))
        return guard.main([str(b), str(f)])

    assert check({"spmd_vs_sim_w8_chunk32_m1": 0.45,          # -10%: ok
                  "spmd_bytes_per_step_w8_chunk32_m1": 55000.0}) == 0
    assert check({"spmd_vs_sim_w8_chunk32_m1": 0.65,          # improvement
                  "spmd_bytes_per_step_w8_chunk32_m1": 30000.0}) == 0
    assert check({"spmd_vs_sim_w8_chunk32_m1": 0.39,          # -22%: fail
                  "spmd_bytes_per_step_w8_chunk32_m1": 50000.0}) == 1
    assert check({"spmd_vs_sim_w8_chunk32_m1": 0.50,
                  "spmd_bytes_per_step_w8_chunk32_m1": 65000.0}) == 1  # +30%
    # new cells in fresh / cells only in baseline never fail the guard
    assert check({"spmd_vs_sim_w8_chunk32_m1": 0.50,
                  "spmd_vs_sim_w16_chunk64_m4": 0.9}) == 0


def _import_bench_spmd():
    """Import benchmarks/bench_spmd.py, undoing its import-time forced
    host-device XLA_FLAGS so later in-process tests are unaffected."""
    import importlib
    import os as _os
    import sys as _sys

    saved = _os.environ.get("XLA_FLAGS")
    _sys.path.insert(0, str(_ROOT / "benchmarks"))
    try:
        return importlib.import_module("bench_spmd")
    finally:
        if saved is None:
            _os.environ.pop("XLA_FLAGS", None)
        else:
            _os.environ["XLA_FLAGS"] = saved


def test_bench_run_forwards_flags(monkeypatch, tmp_path):
    """bench_spmd.run() re-execs itself in a fresh subprocess (the forced
    device count must precede jax init); trace/metrics/platform requests
    must survive that hop — forwarded from env to the child's argv."""
    import subprocess as _sp

    bench_spmd = _import_bench_spmd()
    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        return _sp.CompletedProcess(cmd, 0)

    monkeypatch.setattr(bench_spmd.subprocess, "run", fake_run)
    monkeypatch.setenv("REPRO_BENCH_TRACE", str(tmp_path / "t.json"))
    monkeypatch.setenv("REPRO_BENCH_METRICS", str(tmp_path / "m.jsonl"))
    monkeypatch.setenv("REPRO_BENCH_PLATFORM", "cpu")
    rows = bench_spmd.run(quick=True)
    (cmd,) = calls
    assert "--quick" in cmd
    assert cmd[cmd.index("--trace") + 1] == str(tmp_path / "t.json")
    assert cmd[cmd.index("--metrics") + 1] == str(tmp_path / "m.jsonl")
    assert cmd[cmd.index("--platform") + 1] == "cpu"
    # rows come from the committed BENCH payload (the child was faked)
    assert any(name.startswith("spmd.spmd_vs_sim") for name, _, _ in rows)


def test_bench_run_in_process_on_accelerator(monkeypatch):
    """On a non-CPU backend the harness process holds the chips, so
    run() starts no child: it runs main() in-process on the real devices
    when there are enough, and otherwise fails naming both counts."""
    import jax

    bench_spmd = _import_bench_spmd()

    def no_child(cmd, **kw):
        raise AssertionError(f"started a child on an accelerator: {cmd}")

    monkeypatch.setattr(bench_spmd.subprocess, "run", no_child)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    with pytest.raises(RuntimeError, match="needs 16 tpu devices.*has 4"):
        bench_spmd.run(quick=True)

    argvs = []
    payload = {"results": [{"backend": "spmd", "workers": 8,
                            "chunk_size": 1, "mesh_model": 2,
                            "steps_per_s": 10.0}],
               "spmd_vs_sim_w8_chunk1_m2": 0.5}
    monkeypatch.setattr(jax, "device_count", lambda: 16)
    monkeypatch.setattr(bench_spmd, "main",
                        lambda argv: argvs.append(argv) or payload)
    rows = bench_spmd.run(quick=True)
    assert argvs == [["--quick"]]
    assert ("spmd.spmd_w8_chunk1_m2", 1e5, "10.0steps/s") in rows


# ---------------------------------------------------------------------------
# Tensor parallelism over the 'model' axis (subprocess — needs >= 8 devices)
# ---------------------------------------------------------------------------


def test_spmd_tp_triple_parity_and_checkpoint_interchange():
    """The acceptance triangle: the (4,2) TENSOR-PARALLEL run, the (8,1)
    replicated mesh run, and the single-device sim agree (allclose params/
    EMA/losses, identical masks/sim_time) — and a checkpoint written by
    the sharded run resumes in all three (gather happens only at the
    save/restore boundary, so the on-disk format is one format)."""
    run_py(r"""
import numpy as np, jax, shutil
from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                ExecutionConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig, replace)
from repro.core.straggler import Uniform
from repro.train.loop import Trainer

model_cfg = replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                    d_ff=64, vocab_size=64, vocab_pad_multiple=16)

def cfg(backend, ck, mesh=(1, 1), chunk=2, every=3):
    return TrainConfig(
        model=model_cfg, shape=ShapeConfig("t", 16, 16, "train"),
        aggregation=AggregationConfig(strategy="backup", num_workers=6,
                                      backup_workers=2),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False, ema_decay=0.99),
        checkpoint=CheckpointConfig(directory=ck, every_steps=every),
        execution=ExecutionConfig(backend=backend, mesh_data=mesh[0],
                                  mesh_model=mesh[1]),
        seed=0, total_steps=8, log_every=1, chunk_size=chunk)

lat = Uniform(1.0, 2.0)
def close(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-4, atol=2e-5)

# -- triple parity, full 8 steps -------------------------------------------
runs = {}
for name, backend, mesh in (("sim", "sim", (1, 1)),
                            ("rep", "spmd", (8, 1)),
                            ("tp", "spmd", (4, 2))):
    tr = Trainer(cfg(backend, f"/tmp/tp3_{name}", mesh, every=0), latency=lat)
    tr.init_state()
    runs[name] = (tr, tr.run(8))
(_, rs), (_, rr), (ttp, rt) = runs["sim"], runs["rep"], runs["tp"]
assert "model" in tuple(
    ttp.params["seg_dense"]["mlp"]["w_down"]["w"].sharding.spec)
assert "model" in tuple(ttp.ema["embed"]["embedding"].sharding.spec)
for a, b in ((rs, rr), (rs, rt), (rr, rt)):
    close(a.params, b.params); close(a.ema, b.ema)
    np.testing.assert_allclose([m["loss"] for m in a.metrics],
                               [m["loss"] for m in b.metrics],
                               rtol=2e-4, atol=2e-5)
    assert a.sim_time == b.sim_time
    assert [m["selected"] for m in a.metrics] == \
        [m["selected"] for m in b.metrics]
print("triple parity OK")

# -- sharded checkpoint -> each of the three backends ----------------------
shutil.rmtree("/tmp/tp3_ck", ignore_errors=True)
t1 = Trainer(cfg("spmd", "/tmp/tp3_ck", (4, 2)), latency=lat)
t1.init_state(); t1.run(3)                     # checkpoints (sharded) at 3
for name, backend, mesh in (("tp", "spmd", (4, 2)),
                            ("rep", "spmd", (8, 1)),
                            ("sim", "sim", (1, 1))):
    d = f"/tmp/tp3_resume_{name}"
    shutil.rmtree(d, ignore_errors=True); shutil.copytree("/tmp/tp3_ck", d)
    t2 = Trainer(cfg(backend, d, mesh), latency=lat)
    t2.restore_checkpoint()
    assert t2.step == 3
    r2 = t2.run(5)                             # resume THROUGH sharded chunks
    close(rs.params, r2.params); close(rs.ema, r2.ema)
    assert rs.sim_time == r2.sim_time
    print(f"resume into {name} OK")
print("sharded checkpoint interchange OK")
""")


def test_spmd_tp_kernel_and_jnp_reduce_agree():
    """The Pallas backup_reduce over each shard's LOCAL [W_local, P_local]
    flatten == the jnp reference reduction, on a tensor-parallel mesh."""
    run_py(r"""
import numpy as np, jax
from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                ExecutionConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig, replace)
from repro.core.straggler import Uniform
from repro.train.loop import Trainer

model_cfg = replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                    d_ff=64, vocab_size=64, vocab_pad_multiple=16)

def cfg(ck, use_kernel):
    return TrainConfig(
        model=model_cfg, shape=ShapeConfig("t", 16, 16, "train"),
        aggregation=AggregationConfig(strategy="backup", num_workers=6,
                                      backup_workers=2),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False, ema_decay=0.99),
        checkpoint=CheckpointConfig(directory=ck, every_steps=0),
        execution=ExecutionConfig(backend="spmd", mesh_data=4, mesh_model=2,
                                  use_kernel=use_kernel),
        seed=0, total_steps=4, log_every=1, chunk_size=2)

lat = Uniform(1.0, 2.0)
tk = Trainer(cfg("/tmp/tpk_k", True), latency=lat); tk.init_state()
rk = tk.run(4)
tj = Trainer(cfg("/tmp/tpk_j", False), latency=lat); tj.init_state()
rj = tj.run(4)
for x, y in zip(jax.tree_util.tree_leaves(rk.params),
                jax.tree_util.tree_leaves(rj.params)):
    np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                               rtol=1e-5, atol=1e-6)
print("tp kernel == jnp reduce OK")
""")


def test_spmd_tp_unshardable_model_falls_back_replicated():
    """mesh_model=2 with an indivisible config: the engine warns, carries
    the 'model' axis replicated (pre-TP semantics), and parity with the
    sim backend still holds."""
    run_py(r"""
import warnings
import numpy as np, jax
from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                ExecutionConfig, OptimizerConfig, ShapeConfig,
                                TrainConfig, replace)
from repro.core.straggler import Uniform
from repro.train.loop import Trainer

# 3 heads / 3 kv heads, odd d_ff, odd padded vocab: nothing divides by 2
model_cfg = replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                    d_model=32, num_heads=3, num_kv_heads=3, head_dim=8,
                    d_ff=65, vocab_size=63, vocab_pad_multiple=9)

def cfg(backend, ck, mesh=(1, 1)):
    return TrainConfig(
        model=model_cfg, shape=ShapeConfig("t", 16, 16, "train"),
        aggregation=AggregationConfig(strategy="backup", num_workers=6,
                                      backup_workers=2),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False, ema_decay=0.0),
        checkpoint=CheckpointConfig(directory=ck, every_steps=0),
        execution=ExecutionConfig(backend=backend, mesh_data=mesh[0],
                                  mesh_model=mesh[1]),
        seed=0, total_steps=4, log_every=1, chunk_size=1)

lat = Uniform(1.0, 2.0)
ta = Trainer(cfg("sim", "/tmp/tpf_sim"), latency=lat); ta.init_state()
ra = ta.run(4)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    tb = Trainer(cfg("spmd", "/tmp/tpf_mesh", (4, 2)), latency=lat)
assert any("carried (replicated)" in str(x.message) for x in w), \
    [str(x.message) for x in w]
tb.init_state()
rb = tb.run(4)
# replicated over the whole mesh: no 'model' entry in any param spec
spec = tb.params["seg_dense"]["attn"]["wq"]["w"].sharding.spec
assert "model" not in tuple(spec), spec
for x, y in zip(jax.tree_util.tree_leaves(ra.params),
                jax.tree_util.tree_leaves(rb.params)):
    np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                               rtol=2e-4, atol=2e-5)
print("unshardable fallback OK")
""")


def test_spmd_tp_cli_smoke():
    """--execution spmd --mesh-data 4 --mesh-model 2 through the launcher."""
    run_py(r"""
from repro.launch import train as train_cli
train_cli.main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "4",
                "--workers", "3", "--backups", "1", "--batch-per-worker", "2",
                "--seq", "16", "--ckpt", "/tmp/tp_cli_ck",
                "--optimizer", "momentum", "--lr", "0.05",
                "--execution", "spmd", "--mesh-data", "4", "--mesh-model", "2",
                "--chunk-size", "2"])
import os
assert os.path.exists(os.path.join("/tmp/tp_cli_ck", "LATEST"))
print("tp cli OK")
""")
