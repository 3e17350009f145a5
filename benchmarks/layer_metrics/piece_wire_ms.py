"""Wire: the flight recorder's per-piece ``wire_ms`` (first byte to verified
bytes), median over every piece of the window's requests."""

from benchmarks.harness import median


def read(obs):
    return median([row["wire_ms"] for row in obs.piece_rows()])
