"""Dispatcher: of the seconds the holder's piece workers lived through, the
share they were parked with nothing to fetch (``worker_wait``: the
dispatcher's wait buckets, journaled at teardown) against the seconds inside
a download (``worker_busy``). High: the protocol starves its workers; low on
a slow task: the loop is the wall."""

from benchmarks import journal


def read(obs):
    wait = journal.seconds(obs, "worker_wait")
    total = wait + journal.seconds(obs, "worker_busy")
    return wait / total if total > 0 else None
