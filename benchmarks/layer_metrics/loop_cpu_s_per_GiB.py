"""Daemon loop: CPU seconds (user + system) of the chip holder's loop thread
over the window, sampled by the program on that thread, per GiB that became
ready in it."""

from benchmarks import journal


def read(obs):
    cpu = journal.loop_cpu(obs)
    if cpu is None or obs.window.bytes_ready <= 0:
        return None
    return sum(cpu) / (obs.window.bytes_ready / journal.GiB)
