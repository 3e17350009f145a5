"""Host to HBM: for each chip its ready bytes over the union of its own
``hbm_shard`` spans inside the window; the slowest chip's. Nothing where
``hbm_shard`` names no chip."""

from benchmarks.harness import union_seconds
from benchmarks.layer_metrics.hbm_chips_overlap import by_chip


def read(obs):
    w = obs.window
    rates = []
    for spans, nbytes in by_chip(obs).values():
        busy = union_seconds(spans, w.t0, w.t1)
        if busy > 0:
            rates.append(nbytes / busy / 1e9)
    return min(rates) if rates else None
