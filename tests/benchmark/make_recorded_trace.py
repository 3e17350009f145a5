"""Makes ``recorded_cpu_trace.xplane.pb``, the small trace the reduction is
checked on. Made on the CPU backend: right for the structure (planes, lines,
events, stats, the benchmark's host spans), and says nothing about a TPU's
layout, which ``trace_reduce.extract`` reads from device planes instead.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        python tests/benchmark/make_recorded_trace.py

What it records, inside the span ``bench:window``: three executions of a
jitted program named ``bench_job_step`` on device 0 under the host span
``bench:phase one``, then a sleep of 50 ms under ``bench:known idle gap`` in
which no device runs anything, then the same program on devices 0 and 1
dispatched together (their operations overlap in time) under
``bench:phase two``.
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    @jax.jit
    def bench_job_step(x):
        with jax.named_scope("bench_job_step"):
            return jnp.tanh(x @ x) @ x

    d0, d1 = jax.devices()[:2]
    x0 = jax.device_put(jnp.ones((384, 384)), d0)
    x1 = jax.device_put(jnp.ones((384, 384)), d1)
    jax.block_until_ready([bench_job_step(x0), bench_job_step(x1)])
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:phase one"):
            for _ in range(3):
                bench_job_step(x0).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:known idle gap"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench:phase two"):
            for _ in range(3):
                jax.block_until_ready([bench_job_step(x0),
                                       bench_job_step(x1)])
    jax.profiler.stop_trace()
    (found,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                      "*.xplane.pb"))
    shutil.copy(found, os.path.join(HERE, "recorded_cpu_trace.xplane.pb"))
    shutil.rmtree(logdir)


if __name__ == "__main__":
    main()
