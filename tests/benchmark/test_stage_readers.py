"""The three per-layer readers of PR 28 (``stage_thread_GB_per_s``,
``stage_offloop_share``, ``sink_buffer_reuse_share``) on journals made by
hand: what each reads, that only the window counts, and that a program
which journals no ``staged`` and names no lease in ``sink_open`` (the commit
before) gives each nothing to read and makes none raise."""

import types
from collections import deque

import pytest

from benchmarks import harness

MiB = 1 << 20
NEW = ("stage_thread_GB_per_s", "stage_offloop_share",
       "sink_buffer_reuse_share")


def _obs(flights, t0=100.0, t1=110.0):
    requests = [types.SimpleNamespace(
        ok=True, bytes_p2p=0,
        flight=types.SimpleNamespace(_m0=m0, events=deque(events)))
        for m0, events in flights]
    requests.append(types.SimpleNamespace(ok=False, flight=None,
                                          bytes_p2p=0))
    return types.SimpleNamespace(window=types.SimpleNamespace(
        t0=t0, t1=t1, requests=requests, bytes_ready=1 << 30))


def _task(lease: str, on_loop: int = 0, late: bool = False) -> list:
    """One task of two landings of 256 MiB, each staged by its storage
    thread in 50 ms; ``on_loop`` more bytes were staged with no ``staged``
    event (a copy the loop made)."""
    half = 256 * MiB
    ev = [(20.0, "sink_open", -1, lease, 2 * half, 10.0)]
    for piece, t in ((0, 1000.0), (1, 2000.0)):
        ev += [(t, "landed", piece, "native", half, 250.0),
               (t + 250, "staged", piece, "native", half, 50.0),
               (t + 300, "land_wait", piece, "native", 0, 2.0),
               (t + 301, "hbm_done", piece, "", half, 0.01)]
    if on_loop:
        ev.append((2500.0, "hbm_done", 2, "", on_loop, 120.0))
    if late:                                 # 12 s after m0: past the window
        ev += [(12000.0, "staged", 9, "native", half, 1.0),
               (12000.0, "sink_open", -1, "hit", half, 1.0)]
    return ev


def recorded(on_loop: int = 0):
    return _obs([(100.5, _task("miss")), (103.0, _task("hit", on_loop)),
                 (104.0, _task("hit", late=True))])


EXPECTED = {
    "stage_thread_GB_per_s": 6 * 256 * MiB / 0.3 / 1e9,
    "stage_offloop_share": 1.0,
    "sink_buffer_reuse_share": 2 / 3,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_its_number_from_the_journal(name):
    assert set(EXPECTED) == set(NEW)
    read = harness.load_module("layer_metrics", name).read
    assert read(recorded()) == pytest.approx(EXPECTED[name])


def test_bytes_the_loop_staged_lower_the_offloop_share():
    read = harness.load_module("layer_metrics", "stage_offloop_share").read
    assert read(recorded(on_loop=512 * MiB)) == pytest.approx(0.75)


@pytest.mark.parametrize("name", NEW)
def test_reader_says_nothing_of_a_program_that_records_nothing(name):
    """The parent commit: ``hbm_done`` carries the copy's own seconds,
    ``sink_open`` names no lease, there is no ``staged``."""
    old = [(20.0, "sink_open", -1, "", 512 * MiB, 10.0),
           (900.0, "landed", 0, "native", 256 * MiB, 250.0),
           (950.0, "hbm_done", 0, "", 256 * MiB, 270.0)]
    read = harness.load_module("layer_metrics", name).read
    assert read(_obs([(100.5, old)])) is None
    assert read(_obs([])) is None


def test_the_benchmark_declares_the_three_in_both_cells():
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(mine) == set(NEW)
    for m in mine.values():
        assert m["layer"] == "host to HBM" and m["better"] == "higher"
        assert m["moves"] == "ready_MiB_per_s" and m["workloads"] == cells
