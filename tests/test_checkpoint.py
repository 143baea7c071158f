"""Checkpoint/restore, atomicity, keep-k, elastic resume, data-state resume,
and the self-healing layer: checksums, write retries, crash-mid-save
survival, and walk-back restore past corrupt checkpoints."""
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                OptimizerConfig, ShapeConfig, TrainConfig)
from repro.core.straggler import Uniform
from repro.train import checkpoint as ckpt
from repro.train.loop import Trainer


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "nested": {"b": jnp.arange(6, dtype=jnp.int32),
                       "c": [jnp.ones(3), jnp.zeros(2)]}}


def test_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 7, t, {"note": "x"})
    template = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), t)
    restored, manifest = ckpt.restore(str(tmp_path), template)
    assert manifest["step"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_roundtrip_bfloat16(tmp_path):
    """bf16 leaves (every full-width model's params) come back bit-exact
    with their dtype: npz alone returns them as raw void bytes, which
    failed the checksum on restore."""
    t = {"w": jax.random.normal(jax.random.PRNGKey(3), (5, 7)
                                ).astype(jnp.bfloat16),
         "m": jnp.ones(4, jnp.float32)}
    ckpt.save(str(tmp_path), 2, t)
    assert ckpt.verify(str(tmp_path), 2)
    restored, _ = ckpt.restore(str(tmp_path),
                               jax.tree_util.tree_map(jnp.zeros_like, t))
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(restored["w"]).view(np.uint16),
                                  np.asarray(t["w"]).view(np.uint16))
    np.testing.assert_array_equal(restored["m"], t["m"])


def test_latest_and_keep(tmp_path):
    t = _tree()
    for step in (1, 2, 3, 4):
        ckpt.save(str(tmp_path), step, t, keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 4
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000003", "step_00000004"]


def test_restore_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.ones((2, 2))})
    with pytest.raises(ValueError):
        ckpt.restore(str(tmp_path), {"a": jnp.ones((3, 3))})


def test_missing_key_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.ones(2)})
    with pytest.raises(KeyError):
        ckpt.restore(str(tmp_path), {"a": jnp.ones(2), "b": jnp.ones(2)})


def _trainer(tmp_path, workers=4, backups=1, steps_ck=5):
    cfg = TrainConfig(
        model=configs.get_smoke_config("qwen3-0.6b"),
        shape=ShapeConfig("tiny", 16, 20, "train"),
        aggregation=AggregationConfig(strategy="backup", num_workers=workers,
                                      backup_workers=backups),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.05,
                                  scale_lr_with_workers=False,
                                  ema_decay=0.999),
        checkpoint=CheckpointConfig(directory=str(tmp_path),
                                    every_steps=steps_ck),
        log_every=1)
    return Trainer(cfg, latency=Uniform(1.0, 2.0))


def test_trainer_checkpoint_resume_exact(tmp_path):
    """Kill/restart: a restored trainer continues bit-identically."""
    tr = _trainer(tmp_path)
    tr.init_state()
    tr.run(10)
    tr.save_checkpoint()
    ref_res = tr.run(5)
    ref_loss = [m["loss"] for m in ref_res.metrics[-5:]]

    tr2 = _trainer(tmp_path)
    tr2.restore_checkpoint(step=10)   # the cadence also saved step 15
    assert tr2.step == 10
    res2 = tr2.run(5)
    loss2 = [m["loss"] for m in res2.metrics[-5:]]
    np.testing.assert_allclose(ref_loss, loss2, rtol=1e-5)


def test_elastic_rescale_on_failures(tmp_path):
    """Backups absorb one death; further deaths trigger elastic rescale
    with the lr rule re-applied, and training continues finitely."""
    tr = _trainer(tmp_path, workers=4, backups=1)
    tr.init_state()
    tr.run(3)
    tr.sim.kill_worker(0)           # 4 alive >= N=4: absorbed
    res = tr.run(3)
    assert res.restarts == 0
    tr.sim.kill_worker(1)           # 3 alive < 4 -> rescale
    res = tr.run(4)
    assert res.restarts == 1
    assert tr.cfg.aggregation.total_workers <= 3
    assert all(np.isfinite(m["loss"]) for m in res.metrics)


# ---------------------------------------------------------------------------
# Self-healing layer (docs/robustness.md)
# ---------------------------------------------------------------------------


def test_latest_dangling_pointer_falls_back(tmp_path):
    """A LATEST pointing at a deleted dir must not strand the good
    checkpoints still on disk."""
    t = _tree()
    ckpt.save(str(tmp_path), 3, t)
    ckpt.save(str(tmp_path), 7, t)
    import shutil
    shutil.rmtree(tmp_path / "step_00000007")   # LATEST now dangles
    assert ckpt.latest_step(str(tmp_path)) == 3
    template = jax.tree_util.tree_map(jnp.zeros_like, t)
    _, manifest = ckpt.restore(str(tmp_path), template)
    assert manifest["step"] == 3


def test_latest_missing_falls_back_to_scan(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 5, t)
    os.remove(tmp_path / "LATEST")
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_restore_walks_back_past_corruption(tmp_path):
    """A truncated arrays.npz in the newest checkpoint falls back to the
    last verified-good one instead of failing the restore."""
    ckpt.save(str(tmp_path), 1, _tree(seed=1))
    ckpt.save(str(tmp_path), 2, _tree(seed=2))
    with open(tmp_path / "step_00000002" / "arrays.npz", "wb") as f:
        f.write(b"not a zip file")
    assert ckpt.find_good_step(str(tmp_path)) == 1
    template = jax.tree_util.tree_map(jnp.zeros_like, _tree())
    restored, manifest = ckpt.restore(str(tmp_path), template)
    assert manifest["step"] == 1
    ref = jax.tree_util.tree_leaves(_tree(seed=1))
    for a, b in zip(ref, jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checksum_detects_silent_bitflip(tmp_path):
    """A bit-flip that keeps the npz readable is caught by the per-array
    CRC32, not silently loaded."""
    t = {"a": jnp.ones((4,), jnp.float32)}
    ckpt.save(str(tmp_path), 1, t)
    path = tmp_path / "step_00000001" / "arrays.npz"
    flat = dict(np.load(path))
    flat["a"][0] = 123.0                       # corrupt, same shape/dtype
    np.savez(path, **flat)
    assert not ckpt.verify(str(tmp_path), 1)
    with pytest.raises(ckpt.CheckpointCorruption):
        ckpt.restore(str(tmp_path), {"a": jnp.zeros(4)}, 1, fallback=False)


def test_save_retries_transient_write_failures(tmp_path):
    """io_check failures below the retry budget back off and succeed;
    each retry is observable via on_retry."""
    fails = {"n": 2}

    def io_check():
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient")

    seen = []
    ckpt.save(str(tmp_path), 1, _tree(), retries=3,
              io_check=io_check, on_retry=lambda a, e: seen.append(a),
              sleep=lambda s: None)
    assert seen == [0, 1]
    assert ckpt.verify(str(tmp_path), 1)
    # no abandoned tmp dirs
    assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]


def test_save_raises_after_retry_budget(tmp_path):
    def io_check():
        raise OSError("disk on fire")

    with pytest.raises(OSError):
        ckpt.save(str(tmp_path), 1, _tree(), retries=2,
                  io_check=io_check, sleep=lambda s: None)
    assert ckpt.latest_step(str(tmp_path)) is None


def test_retry_delays_jittered_capped_seeded():
    d = ckpt.retry_delays(6, 0.01, max_backoff_s=0.05, jitter=0.5, seed=3)
    assert len(d) == 6
    base = [min(0.01 * 2 ** a, 0.05) for a in range(6)]
    for got, b in zip(d, base):
        assert b <= got <= b * 1.5          # within [base, base*(1+jitter)]
    assert d[-1] <= 0.05 * 1.5              # the cap holds at the tail
    assert len(set(round(x / b, 6) for x, b in zip(d, base))) > 1, \
        "jitter must decorrelate the schedule"
    assert d == ckpt.retry_delays(6, 0.01, max_backoff_s=0.05, jitter=0.5,
                                  seed=3), "same seed, same schedule"
    assert d != ckpt.retry_delays(6, 0.01, max_backoff_s=0.05, jitter=0.5,
                                  seed=4)
    assert ckpt.retry_delays(3, 0.01, jitter=0.0) == [0.01, 0.02, 0.04]


def test_save_sleeps_the_jittered_schedule(tmp_path):
    """save's actual sleeps match retry_delays for the same knobs — the
    backoff is observable, capped, and replayable."""
    fails = {"n": 3}

    def io_check():
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient")

    slept = []
    ckpt.save(str(tmp_path), 1, _tree(), retries=4, backoff_s=0.01,
              max_backoff_s=0.02, jitter=0.5, backoff_seed=9,
              io_check=io_check, sleep=slept.append)
    assert slept == ckpt.retry_delays(4, 0.01, max_backoff_s=0.02,
                                      jitter=0.5, seed=9)[:3]
    assert max(slept) <= 0.02 * 1.5
    assert ckpt.verify(str(tmp_path), 1)


def test_crash_mid_save_leaves_previous_checkpoint_good(tmp_path):
    """SIGKILL during a checkpoint write (a real process death, not an
    exception) must leave the previous checkpoint restorable."""
    code = f"""
import os, signal
import jax.numpy as jnp
from repro.train import checkpoint as ckpt
d = {str(tmp_path)!r}
tree = {{"a": jnp.arange(8, dtype=jnp.float32)}}
ckpt.save(d, 1, tree)

def die():
    os.kill(os.getpid(), signal.SIGKILL)   # mid-save, tmp dir exists

ckpt.save(d, 2, tree, io_check=die)
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == -signal.SIGKILL
    # the tmp dir from the killed write is on disk; step 1 is intact
    assert [d for d in os.listdir(tmp_path) if d.startswith(".tmp_ckpt_")]
    assert ckpt.latest_step(str(tmp_path)) == 1
    restored, manifest = ckpt.restore(str(tmp_path),
                                      {"a": jnp.zeros(8)})
    assert manifest["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(8.0))
    # the next successful save sweeps the abandoned tmp dir
    ckpt.save(str(tmp_path), 3, {"a": jnp.arange(8, dtype=jnp.float32)})
    assert not [d for d in os.listdir(tmp_path)
                if d.startswith(".tmp_ckpt_")]


def test_data_pipeline_state_resumes(tmp_path):
    from repro.data.synthetic_lm import SyntheticLMConfig, SyntheticLMPipeline, PipelineState
    cfg = SyntheticLMConfig(vocab_size=64, seq_len=8, global_batch=4,
                            num_workers=2)
    p1 = SyntheticLMPipeline(cfg)
    for _ in range(3):
        p1.next()
    saved = p1.state.save()
    expect = p1.next()
    p2 = SyntheticLMPipeline(cfg, PipelineState.restore(saved))
    got = p2.next()
    np.testing.assert_array_equal(expect["tokens"], got["tokens"])
