"""``correct`` on the SPMD engine at a size a CPU test run holds, on
four virtual devices in a child process: a sound run passes, and each
fault the mesh cell can have fails, the exchange between chips left
out among them."""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORKLOAD = "qwen3-0.6b.spmd4.seq64x4"
CASES = ("sound", "state_unchanged", "half_batch", "no_exchange")


def _child():
    sys.path.insert(0, HERE)
    sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                    "src"))
    from chipbench import faults, harness, tiny
    out = {}
    for case in CASES:
        run = harness.run_cell(tiny.cell(WORKLOAD), 31, 0.2, False,
                               t0=time.perf_counter(), require_chip=False,
                               mutate=getattr(faults, case, None))
        out[case] = run.result["correct"]
    print(json.dumps(out))


def test_mesh_faults_are_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "state_unchanged": False,
                   "half_batch": False, "no_exchange": False}


if __name__ == "__main__":
    _child()
