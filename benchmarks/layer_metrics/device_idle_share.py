"""Device: 1 - the union of device-operation intervals over the traced
window, from the profiler trace."""


def read(obs):
    return None if obs.reduced is None else obs.reduced["idle_share"]
