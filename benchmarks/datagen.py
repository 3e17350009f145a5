"""Seeded content: every byte a run moves is made here from ``--seed``.

Random bit patterns are what bf16 tensors must survive: NaNs, infs and
denormals included. The origin's bytes live in memory and on no disk:
anonymous memory files (``memfd``), filled once by a few threads, each file
from a generator of its own (seed, file index), so the bytes do not depend
on the order the threads run in. The origin's process maps the same pages
and serves them; the comparison reads them here, independent of every store
in between. Nothing is hashed. The memory goes when the processes end,
however they end.
"""

from __future__ import annotations

import concurrent.futures
import mmap
import os

MiB = 1 << 20
WRITERS = 4
CHUNK = 64 * MiB


def fill_seeded(fd: int, size: int, seed: int, index: int) -> None:
    """The memory file ``fd`` filled with ``size`` bytes from the generator
    of (seed, index). Written, not stored through a mapping: a write
    allocates its pages in one go, a mapping faults them in one by one."""
    import numpy as np

    rng = np.random.default_rng([seed, index])
    for lo in range(0, size, CHUNK):
        n = min(CHUNK, size - lo)
        # (Generator.bytes is an order of magnitude slower)
        os.pwrite(fd, memoryview(rng.integers(
            0, 1 << 64, -(-n // 8), dtype="uint64")).cast("B")[:n], lo)


class OriginBytes:
    """The origin's files, each in an anonymous memory file of its own (a
    machine that bounds the size of a file bounds these too, and the files
    come cut under that bound)."""

    def __init__(self, files: list[dict], seed: int):
        import numpy as np

        self.fds: dict[str, tuple[int, int]] = {}
        self._views = {}
        for f in files:
            self.fds[f["name"]] = (
                os.memfd_create(f"df-bench-origin-{f['name']}"), f["size"])
        with concurrent.futures.ThreadPoolExecutor(WRITERS) as pool:
            jobs = [pool.submit(fill_seeded, *self.fds[f["name"]], seed, i)
                    for i, f in enumerate(files)]
            for j in jobs:
                j.result()
        for name, (fd, size) in self.fds.items():
            self._views[name] = np.frombuffer(
                mmap.mmap(fd, size, prot=mmap.PROT_READ), dtype=np.uint8) \
                if size else np.zeros(0, np.uint8)

    def bytes_of(self, f: dict):
        """The file's bytes as the origin has them: a view, not a copy."""
        return self._views[f["name"]]

    def spec(self) -> dict:
        """What the origin's process needs to serve the same pages: each
        file's descriptor, which it inherits, and size."""
        return self.fds
