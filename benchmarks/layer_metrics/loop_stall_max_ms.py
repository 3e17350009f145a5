"""Daemon loop: the longest stall of the chip holder's event loop in the
window, by a 10 ms ticker on that loop (``EmbeddedDaemon.take_stall_ms``).
The staging memcpy rides this loop by design."""


def read(obs):
    return obs.stall_ms
