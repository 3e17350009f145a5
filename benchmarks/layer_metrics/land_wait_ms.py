"""Daemon loop: what a landing waited beside its own run (``land_wait``: for
a storage thread to take it, then for the loop to resume the coroutine once
the thread had finished), median over the window's landings."""

from benchmarks import journal
from benchmarks.harness import median


def read(obs):
    waits = [dur * 1e3 for _at, _parent, _n, dur
             in journal.sections(obs, "land_wait")]
    return median(waits)
