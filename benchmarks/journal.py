"""What the per-layer readers share when they read what the program records
of itself: the sections of the chip holder's flight journals
(``daemon/flight_recorder.py``: ``wire_copy``, ``landed``, ``land_wait``,
``hbm_done``, ``sink_open``, ``worker_wait``, ``worker_busy``) and the CPU
seconds of its loop thread (``common/health.py``: ``PLANE.loop_samples``),
each cut to the window. A program that records neither (the commit before
these existed) gives every reader nothing to read, and it says nothing.
"""

from __future__ import annotations

GiB = 1 << 30


def sections(obs, stage: str) -> list[tuple[float, str, int, float]]:
    """(absolute ``time.monotonic()`` of the event, parent, bytes, seconds
    it ran) of every ``stage`` event that the journals of the window's
    counted requests hold inside the window."""
    w = obs.window
    out = []
    for r in w.requests:
        if not r.ok or r.flight is None:
            continue
        for t_ms, kind, _piece, parent, nbytes, dur_ms in list(
                r.flight.events):
            if kind == stage:
                at = r.flight._m0 + t_ms / 1e3
                if w.t0 <= at <= w.t1:
                    out.append((at, parent, nbytes, dur_ms / 1e3))
    return out


def seconds(obs, *stages: str) -> float:
    return sum(dur for stage in stages
               for _at, _parent, _n, dur in sections(obs, stage))


def rate_GB_per_s(obs, stage: str) -> float | None:
    """The bytes of a stage's events over the seconds they ran."""
    rows = sections(obs, stage)
    busy = sum(dur for _at, _parent, _n, dur in rows)
    if busy <= 0:
        return None
    return sum(n for _at, _parent, n, _dur in rows) / busy / 1e9


def loop_cpu(obs) -> tuple[float, float] | None:
    """(user, system) CPU seconds the daemon loop's thread spent in the
    window: the health plane's samples of that thread's ``getrusage``, one
    a 0.1 s tick, taken on it; the two that bracket each end of the window
    are interpolated. None where the program keeps no such samples or they
    do not span the window."""
    from dragonfly2_tpu.common import health

    samples = list(getattr(health.PLANE, "loop_samples", ()))

    def at(t: float) -> tuple[float, float] | None:
        for (ta, _la, ua, sa), (tb, _lb, ub, sb) in zip(samples,
                                                        samples[1:]):
            if ta <= t <= tb:
                f = (t - ta) / (tb - ta) if tb > ta else 0.0
                return ua + f * (ub - ua), sa + f * (sb - sa)
        return None

    lo, hi = at(obs.window.t0), at(obs.window.t1)
    if lo is None or hi is None:
        return None
    return hi[0] - lo[0], hi[1] - lo[1]
