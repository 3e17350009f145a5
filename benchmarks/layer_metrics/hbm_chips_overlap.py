"""Host to HBM: how far the chips' transfers ran at once. The sum over chips
of the union of that chip's ``hbm_shard`` spans, over the union of all chips'
spans, inside the window: 1.0 is the chips taking turns, the number of chips
is all of them busy together. Nothing where ``hbm_shard`` names no chip (a
program before placement)."""

from benchmarks import journal
from benchmarks.harness import union_seconds


def by_chip(obs) -> dict[str, tuple[list[tuple[float, float]], int]]:
    """chip's ordinal -> (its transfer spans, its ready bytes), from the
    ``hbm_shard`` events of the window that name a chip."""
    spans: dict[str, list] = {}
    ready: dict[str, int] = {}
    for at, chip, nbytes, dur in journal.sections(obs, "hbm_shard"):
        if chip != "":
            spans.setdefault(chip, []).append((at, at + dur))
            ready[chip] = ready.get(chip, 0) + nbytes
    return {chip: (spans[chip], ready[chip]) for chip in spans}


def read(obs):
    chips = by_chip(obs)
    w = obs.window
    whole = union_seconds([s for spans, _n in chips.values() for s in spans],
                          w.t0, w.t1)
    if whole <= 0:
        return None
    return sum(union_seconds(spans, w.t0, w.t1)
               for spans, _n in chips.values()) / whole
