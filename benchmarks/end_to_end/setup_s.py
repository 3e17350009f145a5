"""Process start to window start: imports, the native build, data, swarm,
backend init, pre-seeding, warm-up and, in a first run, compilation."""


def read(obs):
    return obs.setup_s
