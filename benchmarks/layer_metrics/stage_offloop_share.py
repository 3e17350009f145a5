"""Host to HBM: of the bytes staged into the sink's host buffer in the
window (``hbm_done``: every piece the sink accounted), the share whose copy
a storage thread made (``staged``) and not the daemon loop. Nothing where
the program journals no ``staged`` at all."""

from benchmarks import journal


def read(obs):
    off_loop = sum(n for _at, _parent, n, _dur
                   in journal.sections(obs, "staged"))
    staged = sum(n for _at, _parent, n, _dur
                 in journal.sections(obs, "hbm_done"))
    if off_loop <= 0 or staged <= 0:
        return None
    return off_loop / staged
