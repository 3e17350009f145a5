"""Faults planted in the program's timed path, to see ``correct`` come out
false. Used by ``benchmarks/control.py`` on the chip and by the tests under
``tests/benchmark``; ``run.py`` never plants one.

  alter_answer   one seeded bit of every verified piece is flipped on its way
                 into the sink's host buffer: an answer altered where it is
                 produced (the transfer of that range carries it)
  drop_half      the sink hands over half of what it was asked for: every
                 second named array of a manifest, or every second unit of
                 a whole buffer (half of the batch left out)
"""

from __future__ import annotations


def plant(name: str, seed: int) -> None:
    import numpy as np

    from dragonfly2_tpu.tpu.hbm_sink import DeviceIngest

    if name == "alter_answer":
        write = DeviceIngest.write
        rng = np.random.default_rng([seed, 99])

        def altered_write(self, offset, data):
            flipped = bytearray(data)
            flipped[int(rng.integers(len(flipped)))] ^= 1 << int(
                rng.integers(8))
            write(self, offset, bytes(flipped))

        DeviceIngest.write = altered_write
    elif name == "drop_half":
        result = DeviceIngest.result

        def half_result(self, timeout=None):
            out = result(self, timeout)
            if isinstance(out, dict):
                return dict(list(out.items())[::2])
            return out[::2] if isinstance(out, list) else out

        DeviceIngest.result = half_result
    else:
        raise ValueError(f"no fault named {name!r}")
