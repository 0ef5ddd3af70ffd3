"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
that belongs to it is found by name: its configuration in the file that
the ``configs`` entry names, its traffic in ``bench/traffic/<traffic>.json``
(which names its driver module, ``bench/drivers/<driver>.py``), and each per-layer
metric in ``bench/metrics/<metric>.py``. A later cell, configuration or
metric is added as files and entries; no file here changes.

A run: set-up (imports, data and weights from the seed, one warm-up job or
step of the cell's own shapes), then a measured window of ``--seconds``,
then the check of what the window produced against the plain reference in
``bench/ref``. With ``--trace 0`` the result reports the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
result reports the per-layer metrics, ``busy_s``, ``window_s`` and a
breakdown. The last line of standard output is one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    # the program under test, and this directory as the package ``bench``
    # (not as a top-level path, where its trace.py would shadow the
    # standard library's)
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_spec(workload: str, root: Path = ROOT) -> dict:
    """The cell ``workload`` with its configuration, traffic and metrics."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {"cell": cell,
            "config": json.loads((root / conf["file"]).read_text()),
            "traffic": json.loads((root / "bench" / "traffic"
                                   / f"{cell['traffic']}.json").read_text()),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def load_module(path: Path):
    """Import one driver or metric file by its path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(n: int):
    """JAX's devices, or exit non-zero where they are not ``n`` TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devices)}")
    return devices


class Run:
    """What a driver is handed: the cell's data, the seed, the clock, and
    the measured window (profiled when ``trace``)."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 devices, t_start: float):
        self.cfg, self.traffic = spec["config"], spec["traffic"]
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.devices = devices
        self.t_start = t_start
        self.setup_s = None
        self.trace_dir = None
        self.compiles = [0]
        self.compiles_in_window = None

        import jax
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _s, **_kw: self.compiles.__setitem__(
                0, self.compiles[0] + (event == COMPILE_EVENT)))

    @property
    def window_seconds(self) -> float:
        """How long the window runs: ``--seconds``, or in a traced run the
        traffic's ``trace_seconds`` where that is shorter."""
        if self.trace and "trace_seconds" in self.traffic:
            return min(self.seconds, float(self.traffic["trace_seconds"]))
        return self.seconds

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """Ends set-up; wraps the measured window."""
        import jax
        from repro.sparse.program import cache_stats
        self.setup_s = time.perf_counter() - self.t_start
        c0, k0 = self.compiles[0], cache_stats()["kernel_traces"]
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        try:
            with self.span("bench.window"):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()
        self.compiles_in_window = (self.compiles[0] - c0,
                                   cache_stats()["kernel_traces"] - k0)

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip (0 where JAX reports none)."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float = None) -> dict:
    """One run of the cell in ``spec``; returns the result object."""
    from bench import trace as tr
    run = Run(spec, seed, seconds, trace, devices,
              time.perf_counter() if t_start is None else t_start)
    driver = load_module(BENCH / "drivers" / f"{run.traffic['driver']}.py")
    out = driver.run(run)
    compiles, traces = run.compiles_in_window
    print(f"bench: compilations in window {compiles}, kernel traces in "
          f"window {traces}", file=sys.stderr)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": all(v <= lim for v, lim in out["checks"].values())
              and out["failed"] == 0,
              "attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        try:
            summary = tr.summarize(tr.read(run.trace_dir),
                                   busy_spans=driver.BUSY_SPANS)
        finally:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        metrics = {}
        for m in spec["per_layer"]:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(
                out["record"], summary, devices[0].device_kind)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out["end_to_end"], setup_s=run.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    result.update(metrics=metrics, device=device)
    if trace:
        result["breakdown"] = summary["breakdown"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out["checks"].items()}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    devices = require_chips(int(spec["cell"]["chips"]))
    import jax
    from repro.core.compat import use_compile_cache
    use_compile_cache()
    # every program, however quick to compile, is kept: set-up is then the
    # same in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices, T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
