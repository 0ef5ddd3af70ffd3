"""bench/scopes.py on synthetic device events and host spans (CPU)."""
import pytest

from bench import scopes as sc
from bench import trace as tr
from bench.trace import Event

DEV = "/device:TPU:0"
HOST = "python3"
NS = 1e9


def _scoped(ops, host):
    """``ops``: (name, start s, end s, name stack); ``host``: (name, start
    s, end s)."""
    evs = [(Event(n, s * NS, e * NS, DEV), st) for n, s, e, st in ops]
    trace = tr.Trace({DEV: [ev for ev, _ in evs]},
                     [Event(n, s * NS, e * NS, HOST) for n, s, e in host])
    return sc.Scoped(trace, {ev: st for ev, st in evs})


# one graph job: launch (pack, upload, dispatch), two rounds on the device,
# harvest (wait, transfer); one op outside every scope
OPS = [
    ("%p = f32[8]{0} fusion(), kind=kLoop", 3.0, 4.0,
     "jit(f)/while/body/dcra.graph.payload/mul"),
    ("%r = s32[8]{0} custom-call()", 4.0, 4.5,
     "jit(f)/while/body/dcra.graph.route/dcra.route.rank/bucket_rank"),
    ("%s = f32[9]{0} fusion(), kind=kCustom", 4.5, 5.5,
     "jit(f)/while/body/dcra.graph.route/dcra.route.scatter/scatter-add"),
    ("%m = f32[9]{0} fusion(), kind=kCustom", 5.5, 6.0,
     "jit(f)/while/body/dcra.graph.reduce/scatter-min"),
    ("%u = f32[8]{0} add()", 6.0, 6.25,
     "jit(f)/while/body/dcra.graph.update/add"),
    ("%c = pred[] compare()", 6.25, 6.5, "jit(f)/while/cond/lt"),
    ("%p = f32[8]{0} fusion(), kind=kLoop", 6.5, 7.0,
     "jit(f)/while/body/dcra.graph.payload/mul"),
]
HOST_EVENTS = [
    ("bench.window", 0, 10), ("bench.job", 0.5, 9.5),
    ("bench.launch", 0.5, 3.0),
    ("dcra.graph.pack", 0.6, 2.2), ("__unknown__argsort", 0.7, 2.0),
    ("dcra.graph.upload", 2.2, 2.5), ("dcra.graph.dispatch", 2.5, 2.9),
    ("bench.harvest", 3.0, 9.5),
    ("dcra.graph.wait", 3.0, 7.0), ("dcra.graph.transfer", 7.0, 9.4),
    ("_array.py:631 _value", 7.5, 9.0),
]


def test_scope_self_time_counts_nested_scopes_toward_their_parent():
    s = sc.summarize(_scoped(OPS, HOST_EVENTS))
    got = s["scope_self_s"]
    assert got["dcra.route.rank"] == pytest.approx(0.5)
    assert got["dcra.route.scatter"] == pytest.approx(1.0)
    assert got["dcra.graph.route"] == pytest.approx(1.5)
    assert got["dcra.graph.payload"] == pytest.approx(1.5)
    assert got["dcra.graph.reduce"] == pytest.approx(0.5)
    assert got["dcra.graph.update"] == pytest.approx(0.25)
    # the loop condition has no scope: it is in no entry, and in the share
    phases = sum(got[f"dcra.graph.{p}"]
                 for p in ("payload", "route", "reduce", "update"))
    assert phases == pytest.approx(s["busy_s"] - 0.25)
    assert s["scoped_share"] == pytest.approx(3.75 / 4.0)


def test_scope_self_time_excludes_nested_ops_and_the_outside_window():
    ops = [("%while.1 = (f32[1]) while()", 0, 4, "jit(f)/while"),
           ("%a = f32[1]{0} add()", 1, 2, "jit(f)/dcra.moe.router/add"),
           ("%b = f32[1]{0} mul()", 11, 12, "jit(f)/dcra.moe.router/mul")]
    s = sc.summarize(_scoped(ops, [("bench.window", 0, 10)]))
    assert s["scope_self_s"] == {"dcra.moe.router": pytest.approx(1.0)}
    assert s["scoped_share"] == pytest.approx(0.25)


def test_program_spans_in_order_inside_the_window():
    host = HOST_EVENTS + [("dcra.graph.pack", 10.5, 11.0)]
    s = sc.summarize(_scoped(OPS, host))
    spans = s["program_spans_s"]
    assert set(spans) == {"dcra.graph.pack", "dcra.graph.upload",
                          "dcra.graph.dispatch", "dcra.graph.wait",
                          "dcra.graph.transfer"}
    assert spans["dcra.graph.pack"] == pytest.approx([1.6])
    assert spans["dcra.graph.transfer"] == pytest.approx([2.4])


def test_gap_and_op_labels_name_the_program_span_and_scope():
    s = sc.summarize(_scoped(OPS, HOST_EVENTS))
    gaps = dict(s["breakdown"]["idle_gaps"])
    # [0, 3] idle: midpoint 1.5 inside the pack, under the argsort
    assert gaps["bench.launch/dcra.graph.pack/__unknown__argsort"] == \
        pytest.approx(3.0)
    # [7, 10]: midpoint 8.5 inside the transfer, under the host copy
    assert gaps["bench.harvest/dcra.graph.transfer/_array.py:631 _value"] \
        == pytest.approx(3.0)
    top = dict(s["breakdown"]["device_ops"])
    assert top["dcra.graph.payload/p f32[8] kLoop"] == pytest.approx(1.5)
    assert top["dcra.route.rank/r s32[8]"] == pytest.approx(0.5)
    assert top["c pred[]"] == pytest.approx(0.25)


def test_gap_inside_a_program_span_with_nothing_under_it():
    host = [("bench.window", 0, 4), ("bench.harvest", 1, 3),
            ("dcra.graph.wait", 1, 3)]
    ops = [("%a = f32[1]{0} add()", 0, 1, ""), ("%a = f32[1]{0} add()", 3,
                                                 4, "")]
    s = sc.summarize(_scoped(ops, host))
    assert s["breakdown"]["idle_gaps"] == [
        ["bench.harvest/dcra.graph.wait", pytest.approx(2)]]


def test_unscoped_trace_summarizes_as_trace_py_does():
    """Without program spans or scopes, every key trace.summarize returns
    comes out unchanged, labels included."""
    ops = [("%a = f32[1]{0} fusion(), kind=kLoop", 1, 3, ""),
           ("%b = f32[1]{0} sort()", 2, 4, ""),
           ("%a = f32[1]{0} fusion(), kind=kLoop", 6, 7, "")]
    host = [("bench.window", 0, 10), ("bench.job", 0.5, 5),
            ("bench.launch", 4, 5), ("$program.py:208 _pack_edges", 4.2, 4.9),
            ("bench.job", 5, 9), ("bench.harvest", 7, 9.5)]
    scoped = _scoped(ops, host)
    want = tr.summarize(scoped.trace, busy_spans=("bench.job",))
    got = sc.summarize(scoped, busy_spans=("bench.job",))
    for key in ("busy_s", "window_s", "idle_share", "span_busy_s",
                "breakdown"):
        assert got[key] == want[key]
    assert got["scope_self_s"] == {}
    assert got["program_spans_s"] == {}
    assert got["scoped_share"] == 0


HLO = """
HloModule jit_kernel

%fused_computation.38 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(kernel)/dcra.graph.payload/mul"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %fusion.38 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.38, metadata={op_name="jit(kernel)/dcra.graph.payload/mul"}
  %sort.4 = s32[8]{0} sort(%x), metadata={op_name="jit(kernel)/dcra.graph.route/dcra.route.scatter/scatter-add"}
  %copy.4 = u32[8,2]{1,0:T(8,128)(2,1)} copy(u32[8,2]{0,1:T(8,128)} %reshape.2)
  %reshape.2 = u32[8,2]{0,1:T(8,128)} reshape(%x, %sort.4)
}
"""
OTHER = """
ENTRY %main (x: f32[8]) -> f32[8] {
  %sort.4 = s32[8]{0} sort(%x), metadata={op_name="jit(other)/sort"}
  %add.2 = f32[8]{0} add(%x, %x), metadata={op_name="jit(other)/add"}
}
"""


def test_hlo_stacks_by_op_label():
    got = sc.hlo_stacks([HLO, OTHER])
    assert got["fusion.38 f32[8] kLoop"] == \
        "jit(kernel)/dcra.graph.payload/mul"
    assert got["add.2 f32[8]"] == "jit(other)/add"
    # sort.4 s32[8] names two instructions with different stacks
    assert "sort.4 s32[8]" not in got
    # the compiler's copy and reshape carry no op_name: they take that of
    # their first operand that has one, through the chain
    assert got["reshape.2 u32[8,2]"] == got["copy.4 u32[8,2]"] == \
        "jit(kernel)/dcra.graph.route/dcra.route.scatter/scatter-add"


def test_device_op_events_take_the_stack_of_their_instruction():
    """A TPU op event is named by its instruction's text with operand
    shapes and layouts, and without metadata."""
    event = ("%fusion.38 = f32[8]{0:T(1024)} fusion(f32[8]{0:T(1024)} %x), "
             "kind=kLoop, calls=%fused_computation.38")
    stacks = sc.hlo_stacks([HLO])
    assert stacks[tr.op_label(event)] == "jit(kernel)/dcra.graph.payload/mul"
