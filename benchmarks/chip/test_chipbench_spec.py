"""BENCHMARK.json against the benchmark's contract, and every name in it
resolved to its file, so that a cell, traffic mix or metric is added by
adding files and an entry."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import check, feed, model_spec, spec  # noqa: E402

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
