"""The reduction from a profiler trace to numbers."""
from pathlib import Path

import pytest

from bench import trace as bt

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def small_trace() -> bt.Trace:
    """Two chips, a 10 ms window: compute, an all-reduce partly hidden
    under compute, and idle gaps inside host spans."""
    return bt.Trace({
        "/host:CPU": {"main": [
            ("bench.window", 0 * MS, 10 * MS),
            ("bench.dispatch", 0 * MS, 1 * MS),
            ("bench.wait", 1 * MS, 9 * MS),
            ("drain", 6 * MS, 2 * MS)]},
        "/device:TPU:0": {
            "XLA Modules": [("jit_step", 1 * MS, 5 * MS),
                            ("jit_add", 8 * MS, MS // 2),
                            ("jit_late", 11 * MS, MS)],
            "XLA Ops": [("fusion.1", 1 * MS, 2 * MS),
                        ("all-reduce.3", 2 * MS, 2 * MS),
                        ("fusion.2", 4 * MS, 2 * MS),
                        ("add.1", 8 * MS, MS // 2),
                        # outside the window: clipped away
                        ("fusion.9", 11 * MS, MS)]},
        "/device:TPU:1": {
            "XLA Ops": [("fusion.1", 1 * MS, 4 * MS)]},
    })


def test_window_is_the_bench_window_span():
    tr = small_trace()
    assert tr.window() == (0, 10 * MS)
    assert tr.window_s() == pytest.approx(0.010)
    assert tr.devices == ["/device:TPU:0", "/device:TPU:1"]


def test_busy_is_the_union_of_operations_inside_the_window():
    tr = small_trace()
    assert tr.busy("/device:TPU:0") == [(1 * MS, 6 * MS),
                                        (8 * MS, 8 * MS + MS // 2)]
    assert tr.busy_s("/device:TPU:0") == pytest.approx(0.0055)
    assert tr.idle_pct("/device:TPU:0") == pytest.approx(45.0)
    assert tr.idle_pct("/device:TPU:1") == pytest.approx(60.0)


def test_breakdown_names_idle_gaps_by_the_enclosing_host_span():
    bd = small_trace().breakdown("/device:TPU:0")
    # own times: the all-reduce's overlap with fusion.1 counts once
    assert dict(bd["device_ops"]) == pytest.approx({
        "fusion.1 (fusion)": 0.001, "all-reduce.3 (all-reduce)": 0.002,
        "fusion.2 (fusion)": 0.002, "add.1 (add)": 0.0005})
    gaps = {round(v * 1e3, 6): n for n, v in bd["idle_gaps"]}
    # 0–1 ms dispatching, 6–8 ms in the drain, 8.5–10 ms waiting
    assert gaps == {1.0: "bench.dispatch", 2.0: "drain", 1.5: "bench.wait"}
    bd = small_trace().breakdown("/device:TPU:0", labels=("drain",))
    assert {n for n, _ in bd["idle_gaps"]} == {"idle", "drain"}


def test_interval_arithmetic():
    assert bt.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert bt.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert bt.clip([(0, 5), (6, 9)], 3, 7) == [(3, 5), (6, 7)]


def test_json_round_trip(tmp_path):
    tr = small_trace()
    tr.to_json(tmp_path / "t.json")
    back = bt.Trace.from_json(tmp_path / "t.json")
    assert back.busy("/device:TPU:0") == tr.busy("/device:TPU:0")


def test_op_names_and_opcodes_of_hlo_text():
    text = ("%while.5 = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)}) "
            "while((s32[]{:T(128)}, bf16[4,8]) %tuple.1), condition=%c")
    assert bt.op_name(text) == "while.5"
    assert bt.opcode(text) == "while"
    assert bt.opcode("%all-reduce-start.3 = f32[8]{0} all-reduce-start("
                     "f32[8]{0} %p), to_apply=%add") == "all-reduce-start"
    assert bt.opcode("all-reduce.3") == "all-reduce"
    # the opcode is the instruction's, not that of an operand it reads
    assert bt.opcode(
        "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3)") == "fusion"


def test_own_time_leaves_out_nested_operations():
    evs = [("%while.1 = () while()", 0, 10), ("%fusion.1 = f() fusion()", 1, 3),
           ("%fusion.2 = f() fusion()", 5, 2), ("%add.1 = f() add()", 6, 1),
           ("%copy.1 = f() copy()", 12, 2)]
    own = dict(bt.self_times(evs, 0, 13))
    assert own == {"%while.1 = () while()": 5, "%fusion.1 = f() fusion()": 3,
                   "%fusion.2 = f() fusion()": 1, "%add.1 = f() add()": 1,
                   "%copy.1 = f() copy()": 1}


@pytest.mark.parametrize("name", sorted(p.name for p in
                                        DATA.glob("trace_*.json")))
def test_recorded_chip_trace(name):
    """Traces recorded on a TPU v5e, cut to a few hundred events (the
    operations' text cut to name and opcode)."""
    tr = bt.Trace.from_json(DATA / name)
    dev = tr.devices[0]
    busy = tr.busy_s(dev)
    assert 0 < busy <= tr.window_s()
    assert 0 <= tr.idle_pct(dev) < 100
    own = bt.self_times(tr.ops(dev), *tr.window())
    assert sum(t for _, t in own) * 1e-9 == pytest.approx(busy, rel=1e-9)
    bd = tr.breakdown(dev)
    assert bd["device_ops"] and len(bd["device_ops"]) <= 10
    assert sum(v for _, v in bd["device_ops"]) <= busy * (1 + 1e-9)
    assert all(n.endswith(")") and " (" in n for n, _ in bd["device_ops"])
