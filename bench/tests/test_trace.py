"""The reduction from a profiler trace to busy time, kernel time and the
breakdown, checked on one recorded step of the m2 train cell and on
made-up events."""
import json

import numpy as np
import pytest

from bench import trace
from bench.tests.conftest import ROOT

KERNELS = ("move_rows", "rowwise_adagrad_apply", "dot_interaction")


@pytest.fixture(scope="module")
def recorded():
    path = ROOT / "bench/tests/data/trace_m2_one_step.json"
    return json.loads(path.read_text())["events"]


def test_busy_is_the_union_of_op_intervals(recorded):
    ops = [e for e in recorded if e["line"] == trace.OPS_LINE]
    t0 = min(e["ts"] for e in ops)
    t1 = max(e["ts"] + e["dur"] for e in ops)
    # an independent count: a 100 ns timeline marked op by op
    tick = 100.0
    line = np.zeros(int((t1 - t0) / tick) + 2, bool)
    for e in ops:
        line[int((e["ts"] - t0) // tick):
             int(np.ceil((e["ts"] + e["dur"] - t0) / tick))] = True
    want = line.sum() * tick * 1e-9
    got = trace.reduce(recorded, KERNELS)["busy_s"]
    assert abs(got - want) <= len(ops) * 2 * tick * 1e-9
    assert 0 < got <= (t1 - t0) * 1e-9


def test_kernel_time_and_calls(recorded):
    got = trace.reduce(recorded, KERNELS)["kernels"]
    for k in KERNELS:
        mine = [e["dur"] for e in recorded if e["line"] == trace.OPS_LINE
                and e["name"].startswith(f"%{k}.")]
        assert got[k] == [pytest.approx(sum(mine) * 1e-9), len(mine)]
        assert got[k][1] == 1            # one call of each in one step
    # the row kernels take most of the step's kernel time; the
    # interaction kernel is short
    assert got["move_rows"][0] > 100 * got["dot_interaction"][0]


def test_breakdown(recorded):
    r = trace.reduce(recorded, KERNELS)
    b = r["breakdown"]
    ops = b["device_ops"]
    assert 0 < len(ops) <= trace.TOP
    # top-level ops only: nothing inside a loop is counted twice
    assert sum(s for _, s in ops) <= r["busy_s"] * (1 + 1e-9)
    assert ops[0][0] == "while"
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = b["idle_gaps"]
    assert 0 < len(gaps) <= trace.TOP
    host = {e["name"] for e in recorded if e["plane"].startswith("/host:")}
    assert all(name in host for name, _ in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)


def test_made_up_events():
    def op(plane, name, ts, dur):
        return {"plane": plane, "line": trace.OPS_LINE, "name": name,
                "ts": ts, "dur": dur}
    a, b = "/device:TPU:0", "/device:TPU:1"
    events = [op(a, "%move_rows.3 = f32[8] custom-call()", 0, 10),
              op(a, "%fusion.1 = f32[8] fusion()", 5, 10),     # overlaps
              op(a, "%while.2 = (s32[]) while()", 40, 10),
              op(b, "%move_rows.1 = f32[8] custom-call()", 0, 30),
              {"plane": "/host:CPU", "line": "python3", "name": "wait",
               "ts": 10, "dur": 40},
              {"plane": "/host:CPU", "line": "python3", "name": "outer",
               "ts": 0, "dur": 100}]
    r = trace.reduce(events, ("move_rows",))
    assert r["busy_s"] == pytest.approx((25 + 30) / 2 * 1e-9)
    assert r["kernels"]["move_rows"] == [pytest.approx(20e-9), 1]
    assert r["breakdown"]["idle_gaps"] == [["wait", pytest.approx(25e-9)]]
    assert trace.op_name("%copy-start.7 = (f32[2]) copy-start()") == \
        "copy-start"
    with pytest.raises(RuntimeError):
        trace.reduce(events[-2:])
