"""Wire: bytes read off the wire (the ``wire_copy`` events' bytes) per body
chunk the wire handed the loop (``TaskFlight.wire_chunks``), over the
window's requests: each chunk is one wake-up of the loop and one slice copy,
so this is how many of them a GiB costs."""

KiB = 1 << 10


def read(obs):
    read_bytes = chunks = 0
    for r in obs.window.requests:
        if r.ok and r.flight is not None:
            chunks += getattr(r.flight, "wire_chunks", 0)
            read_bytes += sum(n for _t, stage, _p, _par, n, _d
                              in list(r.flight.events)
                              if stage == "wire_copy")
    return read_bytes / chunks / KiB if chunks > 0 else None
