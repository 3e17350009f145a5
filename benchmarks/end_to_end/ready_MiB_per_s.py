"""Bytes that became verified, ready arrays on the device in the window, over
the whole window: first request issued to last counted request ready. All the
work over all the time, never a median of per-file rates."""


def read(obs):
    if obs.window_s <= 0:
        return None
    return obs.window.bytes_ready / (1 << 20) / obs.window_s
