"""Host->HBM: the share of the window in which a transfer of the sink was in
flight (union of ``transfer_spans`` over the window's seconds)."""

from benchmarks.harness import union_seconds


def read(obs):
    spans = obs.transfer_spans()
    if not spans:
        return None
    return union_seconds(spans, obs.window.t0, obs.window.t1) / obs.window_s
