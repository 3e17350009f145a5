"""Bringing JAX up: the one place a process initialises its accelerator.

A chip belongs to ONE process, so nothing in this package touches JAX
until the process needs a device: the daemon that opens a device sink, a
trainer that fits, the bench's TPU leg. Each of them comes through
``bring_up()`` first, off any event loop (a cold TPU backend init takes
seconds), and gets the persistent compile cache placed before its first
compile.
"""

from __future__ import annotations

import logging
import os
import time

log = logging.getLogger("df.tpu.runtime")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout, git-ignored: the directory is part of the
# cache key, so one that moved between runs (tempfile, pid, time) never hits
IN_TREE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str | None:
    """Decide where compiled programs persist; returns the directory, or
    None when they do not. ``JAX_COMPILATION_CACHE_DIR`` wins and is left
    to JAX, which reads it itself — no other path is set in code then.
    Otherwise accelerator backends cache under the checkout; the CPU
    backend does not (its loader logs a multi-KB machine-feature warning
    per hit, and its compiles are what the test suite runs thousands of).
    Must run before the process's first compile; idempotent."""
    import jax

    # the trainer's jitted steps compile in well under JAX's 1 s floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get(CACHE_ENV)
    if from_env:
        return from_env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", IN_TREE_CACHE_DIR)
    return IN_TREE_CACHE_DIR


def bring_up() -> list:
    """Initialise the backend (blocking — call off the event loop) and
    return this host's devices. Safe to call again: a warm call is a
    lookup."""
    import jax

    t0 = time.monotonic()
    devices = jax.local_devices()
    init_s = time.monotonic() - t0
    cache = place_compile_cache()
    log.info("jax up in %.2fs: platform=%s kind=%s local_devices=%d "
             "compile_cache=%s", init_s, devices[0].platform,
             devices[0].device_kind, len(devices), cache or "off")
    return devices
