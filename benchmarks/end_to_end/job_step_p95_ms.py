"""95th percentile of the wall time of every job step inside the window."""

from benchmarks.harness import percentile


def read(obs):
    steps = obs.steps_in_window()
    if not steps:
        return None
    return percentile([(e - s) * 1e3 for s, e in steps], 0.95)
