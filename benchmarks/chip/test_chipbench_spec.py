"""BENCHMARK.json against the benchmark's contract, and every name in it
resolved to its file, so that a cell, traffic mix, metric or
architecture is added by adding files and an entry."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from chipbench import check, feed, flops, harness, model_spec  # noqa: E402
from chipbench import reference, spec, tiny  # noqa: E402

BENCH = spec.load_benchmark()
TEXT_RE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_command_and_paths():
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert TEXT_RE.match(word)
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(spec.ROOT, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def _names():
    yield from (c["name"] for c in BENCH["configs"])
    for w in BENCH["workloads"]:
        yield from (w["name"], w["config"], w["traffic"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        yield m["name"]
    for c in BENCH["configs"]:
        yield from c["reduced"]


def test_names_units_and_text():
    for name in _names():
        assert spec.NAME_RE.match(name), name
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert spec.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert TEXT_RE.match(entry["why"]), entry["why"]
    for c in BENCH["configs"]:
        assert TEXT_RE.match(c["source"])
    for m in BENCH["per_layer"]:
        assert TEXT_RE.match(m["layer"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group


def test_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(workload):
    cell = spec.resolve(workload)
    model_spec.sizes(cell.config)
    feed.traffic(cell.traffic)
    assert cell.chips == int(cell.config["run"]["mesh_data"]) or \
        cell.config["run"]["execution"] == "sim"
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    assert set(cell.limits["limits"]) <= set(check.NUMBERS)


def test_every_config_is_used_and_its_file_lies_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert used == {c["name"] for c in BENCH["configs"]}
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in BENCH["paths"])


def test_moves_names_an_end_to_end_metric_every_such_cell_reports():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in BENCH["workloads"]:
            if spec.reports(m, w["name"]):
                assert spec.reports(e2e[m["moves"]], w["name"])
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in BENCH["workloads"]}


def test_a_cell_is_added_by_files_and_an_entry(tmp_path):
    """A new traffic mix, metric and cell, written as new files in a
    copy of the benchmark, resolve with no existing file edited."""
    root = tmp_path / "checkout"
    bench_dir = root / BENCH["paths"][0]
    shutil.copytree(HERE, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__", ".*"))
    (bench_dir / "traffic" / "n3b1.seq32x2.json").write_text(json.dumps(
        {"workers": 3, "backups": 1, "rows_per_worker": 2, "seq_len": 32,
         "tokens": "uniform", "steps_per_block": 8}))
    (bench_dir / "metrics" / "steps.count.py").write_text(
        "def read(ctx):\n    return ctx.steps\n")
    (bench_dir / "limits" / "qwen3-0.6b.sim1.seq32x2.json").write_text(
        json.dumps({"limits": {"loss_gap": 0.1}}))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "qwen3-0.6b.sim1.seq32x2",
                               "config": "qwen3-0.6b.sim-1chip",
                               "traffic": "n3b1.seq32x2", "chips": 1,
                               "why": "a test cell"})
    bench["per_layer"].append({"name": "steps.count", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "trainer loop",
                               "moves": "train_tokens_per_s",
                               "workloads": ["qwen3-0.6b.sim1.seq32x2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("qwen3-0.6b.sim1.seq32x2", root=str(root))
    assert feed.traffic(cell.traffic).total_workers == 4
    assert [m["name"] for m in cell.per_layer][-1] == "steps.count"
    reader = spec.metric_reader("steps.count", str(bench_dir))
    assert reader(type("ctx", (), {"steps": 7})) == 7


def _hashes(top):
    """sha256 of every file under ``top``, by its path there."""
    out = {}
    for d, _, files in os.walk(top):
        if "__pycache__" not in d:
            for f in files:
                path = os.path.join(d, f)
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, top)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


UNTIED = "qwen3-untied.sim1.seq32x2"
# The tiny cell's limits, set by calibrate.limits from CPU readings of
# it: seeds 31-42, the control on all 12, the faults on the first 3.
UNTIED_TINY_LIMITS = {"loss_gap": 0.00043, "grad_norm_gap": 0.084,
                      "param_change_gap": 0.1, "ema_change_gap": 0.082,
                      "grad_sketch_gap": 0.065, "param_sketch_gap": 0.16,
                      "ema_sketch_gap": 0.17}


@pytest.fixture(scope="module")
def untied(tmp_path_factory):
    """A copy of the benchmark with a test-only architecture added by
    files alone: ``archs/qwen3_untied.py`` (fixtures/archs), a
    configuration of that ``model_type`` with an untied head, a traffic
    mix, the cell's limits, its tiny limits and the BENCHMARK.json
    entries. Returns the copy's root and the hashes of the files the
    benchmark's directory had before."""
    root = tmp_path_factory.mktemp("untied") / "checkout"
    bench_dir = root / BENCH["paths"][0]
    shutil.copytree(HERE, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__", ".*"))
    before = _hashes(bench_dir)
    shutil.copy(os.path.join(HERE, "fixtures", "archs", "qwen3_untied.py"),
                bench_dir / "archs")
    with open(os.path.join(HERE, "configs", "qwen3-0.6b.sim-1chip.json")) as f:
        config = json.load(f)
    config.update(model_type="qwen3_untied", tie_word_embeddings=False)
    (bench_dir / "configs" / "qwen3-untied.sim-1chip.json").write_text(
        json.dumps(config, indent=1))
    (bench_dir / "traffic" / "n3b1.seq32x2.json").write_text(json.dumps(
        {"workers": 3, "backups": 1, "rows_per_worker": 2, "seq_len": 32,
         "tokens": "uniform", "steps_per_block": 8}))
    (bench_dir / "limits" / f"{UNTIED}.json").write_text(json.dumps(
        {"limits": {"loss_gap": 0.001}}))
    (bench_dir / "fixtures" / "tiny" / f"{UNTIED}.json").write_text(
        json.dumps({"limits": UNTIED_TINY_LIMITS}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "qwen3-untied.sim-1chip", "source": "a test",
        "file": f"{BENCH['paths'][0]}/configs/qwen3-untied.sim-1chip.json",
        "reduced": ["tie_word_embeddings"], "why": "a test architecture"})
    bench["workloads"].append({"name": UNTIED,
                               "config": "qwen3-untied.sim-1chip",
                               "traffic": "n3b1.seq32x2", "chips": 1,
                               "why": "a test cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec.arch("qwen3_untied", str(bench_dir))
    return root, before


def _unedited(root, before):
    """No file the benchmark had is edited; BENCHMARK.json only gains
    entries."""
    after = _hashes(root / BENCH["paths"][0])
    assert {k: after[k] for k in before} == before
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for group, value in BENCH.items():
        grows = group in ("configs", "workloads", "end_to_end", "per_layer")
        assert (bench[group][:len(value)] if grows else bench[group]) == value


def test_an_architecture_is_added_by_files_and_an_entry(untied):
    """The new cell resolves, and its architecture gives sizes, a
    layout, a FLOP count and the reference's readings; no file the
    benchmark had is edited, and BENCHMARK.json only gains entries."""
    root, before = untied
    cell = spec.resolve(UNTIED, root=str(root))
    s = model_spec.sizes(cell.config, cell.bench_dir)
    assert s.model_type == "qwen3_untied"
    # qwen3-0.6b's 596,049,920 and a 1,024 x 151,936 head
    assert s.param_count == 596_049_920 + 1024 * 151_936
    shapes = model_spec.leaf_shapes(s)
    assert shapes["lm_head"] == {"w": (1024, 151_936)}
    assert sum(int(np.prod(x)) for x in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))) == s.param_count
    # the head's logits count the same, tied or not
    assert flops.train_flops_per_token(s, 1024) == 3_928_571_904
    small = tiny.cell(UNTIED, root=str(root))
    t = feed.traffic(small.traffic)
    data = feed.Feed(t, small.config["vocab_size"], 5)
    got = reference.run(small.config, t, jax.random.PRNGKey(5),
                        [data.batch(i) for i in range(3)],
                        [np.arange(t.total_workers, dtype=float)] * 3)
    assert len(got["losses"]) == 3 and all(map(np.isfinite, got["losses"]))
    assert "['lm_head']['w']" in got["grad"]
    assert "['seg_dense']['mlp']['w_up']['w'][1]" in got["param_change"]
    _unedited(root, before)


def test_an_added_architecture_runs_correct_and_its_control_does_not(untied):
    """The new cell through ``harness.run_cell`` at the tiny size, the
    program's qwen3 with an untied head: correct; the fp8 control in
    the program's place: not correct."""
    root, before = untied
    cell = tiny.cell(UNTIED, root=str(root))
    run = harness.run_cell(cell, 31, 0.2, False, t0=time.perf_counter(),
                           require_chip=False)
    assert run.result["correct"], run.report
    s = harness.set_up(cell, 32, require_chip=False)
    assert "lm_head" in s.tr.params
    arrivals = list(s.rec.arrivals)
    s.tr = s.rec = None
    ref = harness.reference_readings(cell, s, 32, arrivals)
    ctl = harness.reference_readings(cell, s, 32, arrivals, precision="low")
    limits = cell.limits["limits"]
    assert check.passed(check.judge(check.gaps(s.prog, ref), limits))
    assert not check.passed(check.judge(check.gaps(ctl, ref), limits))
    _unedited(root, before)


def test_an_unknown_model_type_names_its_file():
    config = dict(spec.resolve(BENCH["workloads"][0]["name"]).config,
                  model_type="no_such_arch")
    with pytest.raises(ValueError, match=r"archs/no_such_arch\.py"):
        model_spec.sizes(config)


def test_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", w,
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout
