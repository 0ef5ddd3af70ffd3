"""bench/trace.py on synthetic device events and host spans (CPU)."""
import pytest

from bench import trace as tr
from bench.trace import Event

DEV = "/device:TPU:0"
HOST = "python3"


def _trace(ops, host):
    return tr.Trace({DEV: [Event(n, s, e, DEV) for n, s, e in ops]},
                    [Event(n, s, e, HOST) for n, s, e in host])


def test_union_covered_and_gaps():
    m = tr.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert m == [(0, 3), (5, 9)]
    assert tr.covered(m, 2, 6) == 2
    assert tr.gaps(m, -1, 10) == [(-1, 0), (3, 5), (9, 10)]


def test_op_label():
    text = ("%fusion.6 = bf16[65537,2048]{1,0:T(8,128)(2,1)} fusion(s32[1] "
            "%a), kind=kCustom, calls=%fused_computation.10")
    assert tr.op_label(text) == "fusion.6 bf16[65537,2048] kCustom"
    assert tr.op_label("%while.5 = (f32[2], s32[]) while(%t)") == "while.5"
    assert tr.op_label("plain") == "plain"


def test_self_time_excludes_nested_ops():
    evs = [Event("%while.1 = (f32[1]) while()", 0, 100, DEV),
           Event("%a = f32[1]{0} add()", 10, 30, DEV),
           Event("%b = f32[1]{0} mul()", 40, 50, DEV)]
    own = {e.name: t for e, t in tr.self_times(evs)}
    assert own[evs[0].name] == 70
    assert own[evs[1].name] == 20


def test_summarize_busy_idle_breakdown_and_span_busy():
    ns = 1e9
    ops = [("%a = f32[1]{0} fusion(), kind=kLoop", 1 * ns, 3 * ns),
           ("%b = f32[1]{0} sort()", 2 * ns, 4 * ns),      # overlaps a
           ("%a = f32[1]{0} fusion(), kind=kLoop", 6 * ns, 7 * ns)]
    host = [("bench.window", 0, 10 * ns),
            ("bench.job", 0.5 * ns, 5 * ns),
            ("bench.launch", 4 * ns, 5 * ns),
            ("$program.py:208 _pack_edges", 4.2 * ns, 4.9 * ns),
            ("bench.job", 5 * ns, 9 * ns),
            ("bench.harvest", 7 * ns, 9.5 * ns)]
    s = tr.summarize(_trace(ops, host), busy_spans=("bench.job",))
    assert s["window_s"] == pytest.approx(10)
    assert s["busy_s"] == pytest.approx(4)       # [1, 4] and [6, 7]
    assert s["idle_share"] == pytest.approx(0.6)
    assert s["span_busy_s"]["bench.job"] == pytest.approx([3.0, 1.0])
    top = dict(s["breakdown"]["device_ops"])
    # ops on one line nest: the overlap of a and b counts once, to b
    assert top == {"a f32[1] kLoop": pytest.approx(2), "b f32[1]":
                   pytest.approx(2)}
    gaps = dict(s["breakdown"]["idle_gaps"])
    # [0,1] in job 1 before any op; [4,6] around the pack (midpoint 5.0 is
    # the start of job 2); [7,10]: midpoint 8.5 inside the harvest
    assert gaps["bench.job"] == pytest.approx(3)
    assert gaps["bench.harvest"] == pytest.approx(3)
    assert sum(gaps.values()) == pytest.approx(6)


def test_gap_labelled_with_inner_host_event():
    ns = 1e9
    ops = [("%a = f32[1]{0} add()", 0, 1 * ns), ("%a = f32[1]{0} add()",
                                                  3 * ns, 4 * ns)]
    host = [("bench.window", 0, 4 * ns), ("bench.launch", 1 * ns, 3 * ns),
            ("$program.py:208 _pack_edges", 1.5 * ns, 2.5 * ns)]
    s = tr.summarize(_trace(ops, host))
    assert s["breakdown"]["idle_gaps"] == [
        ["bench.launch/$program.py:208 _pack_edges", pytest.approx(2)]]


def test_summarize_refuses_a_trace_without_device_work():
    with pytest.raises(ValueError):
        tr.summarize(_trace([], [("bench.window", 0, 10)]))
    with pytest.raises(ValueError):
        tr.summarize(_trace([("%a = f32[1] add()", 0, 1)], []))
