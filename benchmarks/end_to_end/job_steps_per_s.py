"""Steps the co-located job completed (each waited for) inside the window,
over the whole window: the accelerator time the fabric takes from its
neighbour."""


def read(obs):
    if not obs.window.job_steps or obs.window_s <= 0:
        return None
    return len(obs.steps_in_window()) / obs.window_s
