"""Host to HBM: the fullest chip's ready bytes over the mean of the cell's
chips, from the ``hbm_shard`` events of the window: 1.0 when the sink obeys a
balanced manifest, the number of chips when everything lands on one. Nothing
where ``hbm_shard`` names no chip."""

from benchmarks.layer_metrics.hbm_chips_overlap import by_chip


def read(obs):
    ready = [nbytes for _spans, nbytes in by_chip(obs).values()]
    if not ready or sum(ready) <= 0:
        return None
    return max(ready) / (sum(ready) / max(obs.cell.chips, len(ready)))
