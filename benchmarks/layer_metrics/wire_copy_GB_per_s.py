"""Wire: bytes read off the wire over the seconds ``_read_body`` itself ran
on the daemon loop for them (``wire_copy``: the per-chunk slice copy and
watermark store, not the awaits)."""

from benchmarks import journal


def read(obs):
    return journal.rate_GB_per_s(obs, "wire_copy")
