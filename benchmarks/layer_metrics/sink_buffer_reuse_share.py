"""Host to HBM: of the sinks opened in the window, the share whose host
buffer was a released one from the sink buffer pool (``sink_open`` names
``hit`` or ``miss``), so that the staging copy wrote pages already there.
Nothing where ``sink_open`` names neither."""

from benchmarks import journal


def read(obs):
    leases = [parent for _at, parent, _n, _dur
              in journal.sections(obs, "sink_open")
              if parent in ("hit", "miss")]
    if not leases:
        return None
    return leases.count("hit") / len(leases)
