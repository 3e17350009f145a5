"""Daemon loop: the kernel's share (system over user + system) of the loop
thread's CPU seconds in the window: socket reads and page faults against
Python and copies."""

from benchmarks import journal


def read(obs):
    cpu = journal.loop_cpu(obs)
    if cpu is None or sum(cpu) <= 0:
        return None
    return cpu[1] / sum(cpu)
