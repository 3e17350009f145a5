"""Landing + verify: bytes landed over the seconds the storage thread ran
for them (``landed``: the fused write and digest pass of ``write_span``,
off the loop and off the GIL)."""

from benchmarks import journal


def read(obs):
    return journal.rate_GB_per_s(obs, "landed")
