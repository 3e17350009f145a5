"""Device: the same reduction as ``device_idle_share``, under a name of its
own because beside a job it moves another end-to-end metric
(``job_steps_per_s``)."""

from benchmarks.harness import load_module

read = load_module("layer_metrics", "device_idle_share").read
