from . import apps, csr, datasets, ref                    # noqa: F401

# The distributed executables import jax; keep the numpy-only analytic path
# (datasets/oracles/task-engine apps) jax-free by resolving them lazily.
_JAX_APPS = ("AppStats", "PROGRAMS", "TaskProgram", "dcra_bfs",
             "dcra_histogram", "dcra_kcore", "dcra_pagerank",
             "dcra_scatter", "dcra_spmv", "dcra_sssp", "dcra_wcc",
             "histogram_task_stream", "launch_program", "run_program",
             "ProgramLaunch", "spmv_task_stream")

# launch configuration (numpy-only module — no jax import)
_OPTIONS = ("LaunchOptions",)


def __getattr__(name):
    if name in _JAX_APPS:
        from . import jax_apps
        return getattr(jax_apps, name)
    if name in _OPTIONS:
        from . import options
        return getattr(options, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_JAX_APPS) + list(_OPTIONS))
