"""Host->HBM: the window's ready bytes over the union of the sink's transfer
spans (each closes when the transfer is done, not when it is dispatched). A
rate beside the plain ``device_put`` yardstick that set-up prints; never a
share of it: the sink dispatching views back to back has measured faster."""

from benchmarks.harness import union_seconds


def read(obs):
    busy = union_seconds(obs.transfer_spans(), obs.window.t0, obs.window.t1)
    if busy <= 0:
        return None
    return obs.window.bytes_ready / busy / 1e9
