"""Scheduler: request to the ``registered`` event in the chip holder's flight
journal (the register call's round trip to the scheduler), median over the
window's requests. The part of ``first_piece_ms`` that is not the first
ruling and the first offer."""

from benchmarks.harness import median


def read(obs):
    firsts = []
    for r in obs.window.requests:
        if r.ok and r.flight is not None:
            ts = [t for t, stage, *_ in list(r.flight.events)
                  if stage == "registered"]
            if ts:
                firsts.append(min(ts))
    return median(firsts)
