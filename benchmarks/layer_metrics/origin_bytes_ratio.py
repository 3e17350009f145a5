"""Scheduler: bytes the origin served in the window per byte that became
ready in it. Ideal 1.0 for one cold consumer; the seed may run ahead of what
was delivered when the window closes. Silent where nothing came from the
origin (a warm cell): a ratio of 0 is not a reading."""


def read(obs):
    if not obs.origin_bytes or not obs.window.bytes_ready:
        return None
    return obs.origin_bytes / obs.window.bytes_ready
