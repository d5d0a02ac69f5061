"""The reduction from a profiler trace to busy time, kernel time and the
breakdown: on hand-made events, and on a small trace recorded on the chip
(``bench/record_testdata.py``)."""
import json

import pytest

from bench import trace
from bench.harness import BENCH
from bench.metrics_common import LM_DECODE, PALLAS_OP

DATA = BENCH / "testdata"


def _hand_made():
    ev = trace.Event
    ops = [ev("fusion.1", 10, 30, 0), ev("%bitserial_matmul_fused.2 = s32[8,128] custom-call(), custom_call_target=\"tpu_custom_call\"", 25, 40, 0),
           ev("fusion.1", 60, 70, 0), ev("fusion.9", 0, 100, 1)]
    modules = [ev("jit__unknown(1)", 10, 40, 0, "_decode_impl"),
               ev("jit__unknown(2)", 60, 70, 0, "_prefill_impl")]
    spans = [ev("bench.traced", 5, 105), ev("bench.step", 6, 45),
             ev("bench.record", 45, 58), ev("bench.step", 58, 72)]
    return trace.Trace(ops=ops, modules=modules, spans=spans, devices=[0],
                       window=(5, 105))


def test_union_and_idle_share_by_hand():
    assert trace.union([(3, 5), (0, 2), (1, 4), (7, 8)]) == [[0, 5], [7, 8]]
    tr = _hand_made()
    # busy on device 0: [10, 40] and [60, 70] = 40 ns of a 100 ns window
    assert tr.busy_s == pytest.approx(40e-9)
    assert tr.idle_share == pytest.approx(0.6)


def test_kernel_time_by_hand():
    tr = _hand_made()
    assert [m.name for m in tr.modules_named(LM_DECODE)] == ["jit__unknown(1)"]
    pallas = tr.ops_matching(PALLAS_OP)
    assert [e.name for e in pallas] == ["bitserial_matmul_fused.2"]
    assert tr.op_seconds(pallas) == pytest.approx(15e-9)


def test_host_spans_placed_on_the_device_clock():
    ev = trace.Event
    modules = [ev("a", 100, 200, 0), ev("b", 300, 420, 0)]
    # the host clock runs 1000 ns ahead; each step ends 3 ns after its
    # program, the traced window spans both
    host = [("bench.traced", 1.05e-6, 1.5e-6), ("bench.step", 1.09e-6,
            1.203e-6), ("bench.step", 1.29e-6, 1.423e-6)]
    spans = trace._aligned(host, modules)
    assert [(s.name, s.start, s.end) for s in spans] == [
        ("bench.traced", 47, 497), ("bench.step", 87, 200),
        ("bench.step", 287, 420)]


def test_breakdown_by_hand():
    b = _hand_made().breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    # gaps: [5,10] in a step, [40,60] mostly in the record span (its
    # midpoint 50), [70,105] past the last span
    assert b["idle_gaps"] == [
        ["host outside the benchmark's spans", pytest.approx(35e-9)],
        ["bench.record", pytest.approx(20e-9)],
        ["bench.step", pytest.approx(5e-9)]]


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "backlog.xplane.pb"
    if not path.exists():
        pytest.fail("bench/testdata/backlog.xplane.pb is missing")
    with open(DATA / "backlog.json") as f:
        rec = json.load(f)
    return trace.load(str(path), None, rec["host_spans"]), rec


def test_recorded_trace(recorded):
    tr, rec = recorded
    assert tr.devices and tr.window_s > 0
    assert 0 < tr.busy_s <= tr.window_s
    assert tr.busy_s == pytest.approx(rec["busy_s"])
    assert tr.window_s == pytest.approx(rec["window_s"])
    # the backlog window runs one program, the forward, per dispatch
    fwd = tr.modules_named("")
    assert len(fwd) == rec["programs"] == rec["traced"]["dispatches"] >= 1
    pallas = tr.ops_matching(PALLAS_OP)
    # 53 convs and the fc, each one Pallas kernel, in every forward
    assert len(pallas) == 54 * len(fwd)
    assert 0 < tr.op_seconds(pallas) <= tr.busy_s
    assert tr.op_seconds(pallas) == pytest.approx(rec["pallas_s"])


def test_recorded_breakdown(recorded):
    tr, rec = recorded
    b = tr.breakdown()
    assert b == json.loads(json.dumps(rec["breakdown"]))
    ops = [s for _, s in b["device_ops"]]
    assert len(ops) <= 10 and ops == sorted(ops, reverse=True)
    gaps = [s for _, s in b["idle_gaps"]]
    assert sum(gaps) <= tr.window_s - tr.busy_s + 1e-9
