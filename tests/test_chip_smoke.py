"""chip_smoke.py rehearsed on the CPU: its phase functions at --smoke
widths (the platform check the script makes is made here instead), and
the script itself refusing a host without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from test_spmd_subprocess import run_py

ROOT = os.path.join(os.path.dirname(__file__), "..")
SMOKE = ["--arch", "qwen3-0.6b", "--smoke"]


def test_refuses_cpu_backend():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.require_tpu()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "[smoke]" not in out.stdout            # no phase ran


def test_phases_at_smoke_widths(tmp_path, capsys):
    workdir = str(tmp_path)
    chip_smoke.phase_trainer(workdir, model_args=SMOKE, steps=8)
    chip_smoke.phase_resume(workdir, model_args=SMOKE, steps=8, more=4)
    _, n_params = chip_smoke.model_widths(SMOKE)
    chip_smoke.phase_kernel(n_params, interpret=True)
    out = capsys.readouterr().out
    # the repo's CPU contract: chunked and per-step runs are identical
    assert "losses bit-identical" in out
    assert "[train] resumed at step 8" in out
    assert out.count("matches the jnp masked mean") == 2


def test_compare_runs_rejects_divergence():
    base = [{"step": 1, "loss": 10.0, "selected": 6, "sim_time": 1.5}]
    assert chip_smoke.compare_runs(base, base, "same") == 0.0
    for key, value in (("loss", 11.0), ("loss", float("nan")),
                       ("selected", 5), ("sim_time", 1.25)):
        other = [dict(base[0], **{key: value})]
        with pytest.raises(AssertionError):
            chip_smoke.compare_runs(other, base, key)


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed <repo>/.jax_cache."""
    from repro.launch.mesh import init_compile_cache
    saved = jax.config.jax_compilation_cache_dir
    try:
        outside = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        jax.config.update("jax_compilation_cache_dir", outside)
        assert init_compile_cache() == outside
        assert jax.config.jax_compilation_cache_dir == outside
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        here = os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert init_compile_cache() == here
        assert jax.config.jax_compilation_cache_dir == here
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_spmd_phase_on_four_host_devices(tmp_path):
    """The --chips 4 phase on four forced host devices: the spmd engine
    over a 4-way 'data' mesh against the sim backend."""
    out = run_py(f"""
import sys
sys.path.insert(0, {os.path.abspath(ROOT)!r})
import jax, chip_smoke
chip_smoke.peak_bytes = lambda d: 0       # host devices report no stats
peaks = chip_smoke.phase_spmd({str(tmp_path)!r}, jax.devices()[:4],
                              model_args={SMOKE!r})
assert len(peaks) == 4
""", devices=4)
    assert "spmd mesh-data 4 vs sim: selected and sim_time identical" in out
