"""Wire: the flight recorder's per-piece ``ttfb_ms`` (GET fired to first body
chunk), median over every piece of the window's requests."""

from benchmarks.harness import median


def read(obs):
    return median([row["ttfb_ms"] for row in obs.piece_rows()])
