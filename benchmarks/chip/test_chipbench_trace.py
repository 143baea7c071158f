"""The trace reduction and the metric readers, on a recorded fixture
whose answers are worked out by hand (fixtures/trace_small.json).

The fixture's window is [1000, 2000) ns. On TPU:0 the ops cover
[1000, 1500) (fusion.1 is cut at the window's start), [1600, 1800) and
[1950, 2000): 750 ns busy. On TPU:1 they cover [1000, 1600) and
[1700, 2000): 900 ns. The all-reduce runs alone on TPU:0 in
[1300, 1500) and on TPU:1 in [1400, 1600): 200 ns each.
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import flops, spec  # noqa: E402
from chipbench import trace as lib  # noqa: E402

NS = 1e-9
KERNEL = "shard_map.1 custom-call tpu_custom_call"   # the masked reduce


@pytest.fixture
def trace():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        return json.load(f)


def test_window_and_busy(trace):
    assert lib.window_s(trace) == pytest.approx(1000 * NS)
    busy = lib.busy_s(trace)
    assert busy["TPU:0"] == pytest.approx(750 * NS)
    assert busy["TPU:1"] == pytest.approx(900 * NS)


def test_kernel_time(trace):
    got = lib.op_s(trace, lambda n: n == KERNEL)
    assert got == pytest.approx({"TPU:0": 200 * NS, "TPU:1": 300 * NS})


def test_exposed_collective(trace):
    assert lib.exposed_s(trace) == pytest.approx({"TPU:0": 200 * NS,
                                                  "TPU:1": 200 * NS})


def test_idle_gaps_named_by_host_span(trace):
    gaps = sorted(lib.idle_gaps(trace))
    assert gaps == [("TPU:0", "bench/trainer_run", pytest.approx(100 * NS)),
                    ("TPU:0", "none", pytest.approx(150 * NS)),
                    ("TPU:1", "bench/trainer_run", pytest.approx(100 * NS))]


def test_breakdown(trace):
    b = lib.breakdown(trace)
    assert [name for name, _ in b["device_ops"]] == [
        "fusion.1", "all-reduce.1", KERNEL, "fusion.2", "fusion.3"]
    assert [s for _, s in b["device_ops"]] == pytest.approx(
        [300 * NS, 275 * NS, 250 * NS, 75 * NS, 25 * NS])
    assert b["idle_gaps"][0] == ["TPU:0 none", pytest.approx(150 * NS)]
    assert len(b["idle_gaps"]) == 3


def test_subtract_and_union():
    assert lib.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert lib.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert lib.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]


def _ctx(trace, **kw):
    sizes = types.SimpleNamespace(param_count=1000)
    traffic = types.SimpleNamespace(total_workers=8, seq_len=64)
    base = dict(trace=trace, trace_lib=lib, flops=flops, sizes=sizes,
                traffic=traffic, chips=2, steps=2, useful_tokens=0,
                mesh_data=4, peaks={"hbm_bytes_per_s": 819e9,
                                    "bf16_flops_per_s": 197e12})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_readers(trace):
    ctx = _ctx(trace)
    idle = spec.metric_reader("device.idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 825 / 1000))
    exposed = spec.metric_reader("collective.exposed_ms")(ctx)
    assert exposed == pytest.approx(1e3 * 200 * NS / 2)
    # 2 local workers: [2, 1000] f32 read, [1000] f32 written = 12000 B;
    # 250 ns of kernel per chip over 2 steps
    roof = spec.metric_reader("backup_reduce_roofline")(ctx)
    assert roof == pytest.approx(100 * (12000 / 819e9) / (125 * NS))
    # no useful tokens, nothing to read
    assert spec.metric_reader("step.mfu")(ctx) is None


def test_roofline_counts_only_the_reduce_kernel(trace):
    """Another Pallas kernel on the line (same target, no kernel name)
    is told apart by its HLO signature and not counted."""
    other = "custom-call.7 custom-call tpu_custom_call"
    devices = dict(trace["devices"])
    devices["TPU:0"] = devices["TPU:0"] + [[other, 1850, 100]]
    calls = dict(trace["custom_calls"], **{other: (
        '%custom-call.7 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} '
        '%q, bf16[8,128]{1,0} %k), custom_call_target="tpu_custom_call"')})
    ctx = _ctx(dict(trace, devices=devices, custom_calls=calls))
    roof = spec.metric_reader("backup_reduce_roofline")(ctx)
    assert roof == pytest.approx(100 * (12000 / 819e9) / (125 * NS))
    # a stack of another width than W_local is not this cell's kernel
    ctx = _ctx(trace, mesh_data=2)
    assert spec.metric_reader("backup_reduce_roofline")(ctx) is None


def test_readers_find_nothing_where_nothing_ran(trace):
    quiet = dict(trace, devices={"TPU:0": [["fusion.1", 1000, 500]]})
    ctx = _ctx(quiet)
    assert spec.metric_reader("backup_reduce_roofline")(ctx) is None
    assert spec.metric_reader("collective.exposed_ms")(ctx) is None


def test_containers_count_only_as_busy():
    """A loop op on the line holds the ops of its body: it counts towards
    busy time, not as an op of its own, and hides no collective."""
    trace = {"window_ns": [0, 100],
             "devices": {"TPU:0": [["while.1 while", 0, 60],
                                   ["fusion.1 fusion", 5, 20],
                                   ["all-reduce.1 all-reduce", 30, 20],
                                   ["fusion.2 fusion", 70, 10]]},
             "host": [["bench/window", 0, 100]]}
    assert lib.busy_s(trace)["TPU:0"] == pytest.approx(70 * NS)
    assert lib.exposed_s(trace)["TPU:0"] == pytest.approx(20 * NS)
    ops = [name for name, _ in lib.breakdown(trace)["device_ops"]]
    assert ops == ["fusion.1 fusion", "all-reduce.1 all-reduce",
                   "fusion.2 fusion"]


def test_op_names_from_hlo_text():
    assert lib.op_name("%fusion.257 = s32[8]{0:T(128)} fusion(s32[8]{0} "
                       "%p), kind=kLoop") == "fusion.257 fusion"
    assert lib.op_name("%while.18 = (s32[]{:T(128)}, bf16[8]{0}) while("
                       "(s32[]) %t), condition=%c") == "while.18 while"
    assert lib.op_name('%custom-call.6 = bf16[8]{0} custom-call(), '
                       'custom_call_target="tpu_custom_call"') == \
        "custom-call.6 custom-call tpu_custom_call"
