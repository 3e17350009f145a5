"""Job: median device duration of one execution of ``bench_job_step``, from
the profiler trace. Beside ``job_step_p95_ms`` it says how much of a slow
step was the device and how much the host."""

from benchmarks import trace_reduce
from benchmarks.harness import median


def read(obs):
    if obs.trace is None:
        return None
    runs = trace_reduce.module_durations(obs.trace, "bench_job_step")
    m = median(runs)
    return None if m is None else m * 1e3
