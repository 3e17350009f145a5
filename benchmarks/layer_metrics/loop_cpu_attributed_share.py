"""Daemon loop: how much of the loop thread's CPU seconds the program's own
sections on that thread explain (``wire_copy`` + ``hbm_done`` +
``sink_open`` seconds over the thread's user + system seconds). The rest is
aiohttp, asyncio, the kernel's receive path or waiting for the GIL."""

from benchmarks import journal


def read(obs):
    cpu = journal.loop_cpu(obs)
    if cpu is None or sum(cpu) <= 0:
        return None
    own = journal.seconds(obs, "wire_copy", "hbm_done", "sink_open")
    return own / sum(cpu) if own > 0 else None
