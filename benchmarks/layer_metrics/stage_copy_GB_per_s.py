"""Host to HBM: bytes staged into the sink's host buffer over the seconds
``DeviceIngest.write`` took for them (``hbm_done``'s duration: the copy,
the coverage map and the spec scan), on the daemon loop by design."""

from benchmarks import journal


def read(obs):
    return journal.rate_GB_per_s(obs, "hbm_done")
