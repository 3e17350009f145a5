"""Scheduler: request to the first ``dispatched`` event in the chip holder's
flight journal (register, the scheduler's first ruling, the first parent
offered), median over the window's requests."""

from benchmarks.harness import median


def read(obs):
    firsts = []
    for r in obs.window.requests:
        if r.ok and r.flight is not None:
            ts = [t for t, stage, *_ in list(r.flight.events)
                  if stage == "dispatched"]
            if ts:
                firsts.append(min(ts))
    return median(firsts)
