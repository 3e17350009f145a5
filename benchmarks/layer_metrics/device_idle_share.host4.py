"""Device: the same reduction as ``device_idle_share``, the mean over the
host's four chips (``trace_reduce.reduce(trace, 4)`` counts a chip that ran
nothing, and so has no plane in the trace, as idle), under a name of its own
for the four-chip cell."""

from benchmarks.harness import load_module

read = load_module("layer_metrics", "device_idle_share").read
