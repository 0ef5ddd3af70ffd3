"""The program's own spans and scopes in a profiler trace.

The program names its work in the trace that ``bench/trace.py`` reduces:

* host spans ``dcra.*`` (``jax.profiler.TraceAnnotation``), such as the
  graph launch's ``dcra.graph.pack`` / ``upload`` / ``dispatch`` /
  ``wait`` / ``transfer``, on the host plane and the device trace's clock;
* device scopes ``dcra.*`` (``jax.named_scope``), such as
  ``dcra.graph.route`` or ``dcra.moe.expert_pad``, which reach each
  device op's name stack (its HLO ``op_name``, ``jit(f)/dcra.graph.route/
  dcra.route.rank/...``; a fusion takes the stack of its root).

A TPU op event carries the op's HLO text without its metadata, so the
name stack comes from the compiled modules: ``capture_hlo`` collects
their text while the programs compile or load, and each event takes the
``op_name`` of the instruction with its name, shape and fusion kind.

On top of ``trace.summarize``, whose keys and values it leaves as they
are, this module adds:

* ``scope_self_s``: for each device scope, the device self time (as in
  ``trace.self_times``) of the ops whose name stack holds it, inside the
  window, in seconds per chip. A nested scope counts toward its parent
  too: ``dcra.graph.route`` includes ``dcra.route.rank``;
* ``program_spans_s``: for each host span name, the durations of those
  spans that start inside the window, in order, in seconds;
* ``scoped_share``: the device time inside some scope over the time of
  every op, inside the window;
* a breakdown whose idle-gap labels put the innermost open ``dcra.`` span
  between the ``bench.`` span and the innermost other event
  (``bench.launch/dcra.graph.pack/__unknown__argsort``), and whose
  device-op labels lead with the op's innermost scope
  (``dcra.graph.route/fusion.37 s32[7590001] kCustom``).

Run as a script, it runs one cell once with the window traced, like
``bench/run.py --trace 1``, and prints that summary with the driver's
record::

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import contextlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, NamedTuple, Optional  # noqa: E402

if __name__ == "__main__":
    # as bench/run.py: the program under test, and this directory as the
    # package ``bench``
    sys.path[:1] = [str(Path(__file__).resolve().parents[1] / "src"),
                    str(Path(__file__).resolve().parents[1])]

from bench import trace as tr  # noqa: E402

PREFIX = "dcra."
OP_NAME = re.compile(r'op_name="([^"]*)"')
# ``%name = <shape> opcode(<operands and attributes>``: the shape may hold
# layout parentheses, the opcode is lower case
INSTRUCTION = re.compile(r"%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$")
OPERAND = re.compile(r"%([\w.\-]+)")


class Scoped(NamedTuple):
    trace: tr.Trace
    stacks: Dict[tr.Event, str]     # device op -> its HLO name stack


@contextlib.contextmanager
def capture_hlo():
    """Collect the HLO text of every executable compiled or loaded from
    the compile cache inside the block (a list, filled as they come)."""
    from jax._src import compiler
    texts: List[str] = []
    compile_or_get_cached = compiler.compile_or_get_cached

    def capture(*args, **kwargs):
        exe = compile_or_get_cached(*args, **kwargs)
        try:
            texts.extend(m.to_string() for m in exe.hlo_modules())
        except Exception as err:           # the run goes on, unscoped
            print(f"bench: no HLO text from an executable: {err}",
                  file=sys.stderr)
        return exe

    compiler.compile_or_get_cached = capture
    try:
        yield texts
    finally:
        compiler.compile_or_get_cached = compile_or_get_cached


def _instructions(text: str):
    """(name, op_label, op_name or None, operand names) of each HLO
    instruction line in ``text``."""
    for line in text.splitlines():
        m = INSTRUCTION.match(line.strip().removeprefix("ROOT "))
        if not m:
            continue
        depth, end = 1, len(m.group(4))
        for i, ch in enumerate(m.group(4)):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                end = i
                break
        stack = OP_NAME.search(line)
        yield (m.group(1), tr.op_label(m.group(0)),
               stack.group(1) if stack else None,
               OPERAND.findall(m.group(4)[:end]))


def hlo_stacks(texts) -> Dict[str, str]:
    """``trace.op_label`` of each instruction in the HLO ``texts`` -> its
    ``op_name``. An instruction the compiler added without one (a layout
    copy, an expanded bitcast) takes that of its first operand that has
    one. A label that two instructions share with different names is
    left out."""
    out: Dict[str, str] = {}
    seen = set()
    for text in texts:
        insts = {name: (label, stack, operands)
                 for name, label, stack, operands in _instructions(text)}
        resolved: Dict[str, str] = {}

        def stack_of(name, depth=0):
            if name not in resolved:
                resolved[name] = ""          # cycle guard
                _, stack, operands = insts.get(name, (None, None, ()))
                if stack is None and depth < 64:
                    stack = next((st for st in (stack_of(o, depth + 1)
                                                for o in operands) if st), "")
                resolved[name] = stack or ""
            return resolved[name]

        for name, (label, _, _) in insts.items():
            stack = stack_of(name)
            if not stack:
                continue
            if label in seen and out.get(label) != stack:
                out.pop(label, None)
                continue
            seen.add(label)
            out[label] = stack
    return out


def scopes_of(stack: str) -> List[str]:
    """The ``dcra.`` scopes of a name stack, outermost first."""
    return [p for p in stack.split("/") if p.startswith(PREFIX)]


def read(trace_dir: str, hlo_texts) -> Scoped:
    """``trace.read``, with each device op's name stack from the compiled
    modules' text (``capture_hlo``)."""
    trace = tr.read(trace_dir)
    by_label = hlo_stacks(hlo_texts)
    return Scoped(trace, {e: by_label.get(tr.op_label(e.name), "")
                          for evs in trace.ops.values() for e in evs})


def scope_self_s(scoped: Scoped, lo: float, hi: float) -> Dict[str, float]:
    """Device self seconds per scope inside ``[lo, hi)``, per chip."""
    out: Dict[str, float] = defaultdict(float)
    for evs in scoped.trace.ops.values():
        for e, own in tr.self_times(evs):
            if lo <= e.start < hi:
                for scope in set(scopes_of(scoped.stacks.get(e, ""))):
                    out[scope] += own
    n = max(1, len(scoped.trace.ops))
    return {k: v / n / 1e9 for k, v in sorted(out.items())}


def program_spans_s(trace: tr.Trace, lo: float,
                    hi: float) -> Dict[str, List[float]]:
    """Durations of the program's host spans that start in ``[lo, hi)``."""
    out: Dict[str, List[float]] = defaultdict(list)
    for e in sorted(trace.host, key=lambda e: e.start):
        if e.name.startswith(PREFIX) and lo <= e.start < hi:
            out[e.name].append((e.end - e.start) / 1e9)
    return dict(out)


def _labels(host: List[tr.Event], thread: str,
            times: List[float]) -> List[str]:
    """``trace._labels`` with the innermost open program span between the
    bench span and the innermost other event."""
    evs = sorted((e for e in host if e.where == thread),
                 key=lambda e: (e.start, -e.end))
    order = sorted(range(len(times)), key=times.__getitem__)
    out = [""] * len(times)
    stack: List[tr.Event] = []
    i = 0
    for k in order:
        t = times[k]
        while i < len(evs) and evs[i].start <= t:
            while stack and stack[-1].end <= evs[i].start:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        bench = [j for j, e in enumerate(stack)
                 if e.name.startswith(tr.SPAN_PREFIX)]
        if not bench:
            out[k] = "outside bench spans"
            continue
        parts = [stack[bench[-1]].name]
        inner = stack[bench[-1] + 1:]
        ours = [e for e in inner if e.name.startswith(PREFIX)]
        if ours:
            parts.append(ours[-1].name)
        if inner and not inner[-1].name.startswith(PREFIX):
            parts.append(inner[-1].name)
        out[k] = "/".join(parts)
    return out


def op_label(e: tr.Event, stack: str) -> str:
    """``trace.op_label``, led by the op's innermost scope."""
    scopes = scopes_of(stack)
    label = tr.op_label(e.name)
    return f"{scopes[-1]}/{label}" if scopes else label


def summarize(scoped: Scoped, busy_spans=(), top: int = 10) -> dict:
    """``trace.summarize`` with ``scope_self_s``, ``program_spans_s`` and
    ``scoped_share`` added and the breakdown labelled by program span and
    scope."""
    trace = scoped.trace
    out = tr.summarize(trace, busy_spans=busy_spans, top=top)
    (win,) = tr.spans(trace, tr.WINDOW_SPAN)
    lo, hi = win.start, win.end

    per_op: Dict[str, float] = defaultdict(float)
    total = 0.0
    for evs in trace.ops.values():
        for e, own in tr.self_times(evs):
            if lo <= e.start < hi:
                per_op[op_label(e, scoped.stacks.get(e, ""))] += own
                total += own
    first = trace.ops[sorted(trace.ops)[0]]
    idle = tr.gaps(tr.union((e.start, e.end) for e in first), lo, hi)
    per_gap: Dict[str, float] = defaultdict(float)
    for (s, e), label in zip(idle, _labels(trace.host, win.where,
                                           [(s + e) / 2 for s, e in idle])):
        per_gap[label] += e - s

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top] if v > 0]

    scope_s = scope_self_s(scoped, lo, hi)
    outer = sum(v for k, v in per_op.items() if k.startswith(PREFIX))
    out.update(scope_self_s=scope_s,
               program_spans_s=program_spans_s(trace, lo, hi),
               scoped_share=outer / total if total else None,
               breakdown={"device_ops": ranked(per_op),
                          "idle_gaps": ranked(per_gap)})
    return out


def main(argv: Optional[list] = None) -> None:
    import argparse
    import json
    import shutil
    from bench import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)
    spec = bench_run.load_spec(args.workload)
    devices = bench_run.require_chips(int(spec["cell"]["chips"]))
    import jax
    from repro.core.compat import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = bench_run.Run(spec, args.seed, args.seconds, True, devices,
                        T_START)
    driver = bench_run.load_module(bench_run.BENCH / "drivers"
                                   / f"{run.traffic['driver']}.py")
    with capture_hlo() as texts:
        out = driver.run(run)
    try:
        summary = summarize(read(run.trace_dir, texts),
                            busy_spans=driver.BUSY_SPANS, top=args.top)
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "attempted": out["attempted"],
                      "checks": out["checks"], "record": out["record"],
                      "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
