"""Host to HBM: bytes copied into the sink's host buffer over the seconds
the copies took on the storage threads (``staged``: made in the landing's
own hop, once a piece has verified; off the loop, and in the native path
off the GIL). A program that stages on the loop journals no ``staged`` and
this says nothing."""

from benchmarks import journal


def read(obs):
    return journal.rate_GB_per_s(obs, "staged")
