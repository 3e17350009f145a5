"""The readers of what the program records of itself (``benchmarks/journal.py``
and the ten per-layer metrics of PR 26), on a journal made by hand: what each
reads, that only the window counts, how the loop's CPU samples are
interpolated, and that a program which records none of it (the commit before)
gives every reader nothing to read and makes none raise."""

import types
from collections import deque

import pytest

from benchmarks import harness, journal

MiB = 1 << 20
NEW = ("register_ms", "dispatch_wait_share", "wire_copy_GB_per_s",
       "wire_chunk_KiB", "land_GB_per_s", "land_wait_ms",
       "stage_copy_GB_per_s", "loop_cpu_s_per_GiB", "loop_sys_share",
       "loop_cpu_attributed_share")


def _flight(m0: float, events: list, chunks: int | None):
    flight = types.SimpleNamespace(_m0=m0, events=deque(events))
    if chunks is not None:
        flight.wire_chunks = chunks
    return flight


def _obs(flights, t0=100.0, t1=110.0, bytes_ready=1 << 30):
    requests = [types.SimpleNamespace(ok=True, flight=f, bytes_p2p=0)
                for f in flights]
    requests.append(types.SimpleNamespace(ok=False, flight=None,
                                          bytes_p2p=0))
    window = types.SimpleNamespace(t0=t0, t1=t1, requests=requests,
                                   bytes_ready=bytes_ready)
    return types.SimpleNamespace(window=window)


def recorded():
    """Two requests inside a window of [100, 110] s: 512 MiB each in two
    pieces; the second's journal also holds events from after the window."""
    def one(m0, late=False):
        half = 256 * MiB
        ev = [(5.0, "registered", -1, "", 0, 0.0),
              (20.0, "sink_open", -1, "", 2 * half, 10.0)]
        for piece, t in ((0, 1000.0), (1, 2000.0)):
            ev += [(t, "wire_copy", piece, "peer", half, 50.0),
                   (t + 10, "landed", piece, "native", half, 250.0),
                   (t + 300, "land_wait", piece, "native", 0, 4.0 + piece),
                   (t + 600, "hbm_done", piece, "", half, 300.0)]
        ev += [(2900.0, "worker_wait", -1, "no_piece_s", 0, 1000.0),
               (2900.0, "worker_wait", -1, "busy_s", 0, 500.0),
               (2900.0, "worker_busy", -1, "", 0, 4500.0)]
        if late:                             # 12 s after m0: past the window
            ev.append((12000.0, "hbm_done", 9, "", half, 300.0))
        return _flight(m0, ev, chunks=2048)

    return _obs([one(100.5), one(104.0, late=True)])


@pytest.fixture
def loop_samples(monkeypatch):
    """The health plane's ring as a loop that burns 0.6 user and 0.2 system
    CPU seconds a second would fill it, a sample a second from t = 95."""
    from dragonfly2_tpu.common import health
    samples = deque(((95.0 + i, 0.0, 0.6 * i, 0.2 * i) for i in range(30)),
                    maxlen=4096)
    monkeypatch.setattr(health.PLANE, "loop_samples", samples,
                        raising=False)
    return samples


def test_sections_count_only_what_falls_inside_the_window():
    obs = recorded()
    staged = journal.sections(obs, "hbm_done")
    assert len(staged) == 4                  # the late one is left out
    assert all(100.0 <= at <= 110.0 for at, *_ in staged)
    assert journal.seconds(obs, "hbm_done") == pytest.approx(1.2)
    assert journal.seconds(obs, "wire_copy", "sink_open") == \
        pytest.approx(0.2 + 0.02)
    assert journal.rate_GB_per_s(obs, "landed") == pytest.approx(
        (1 << 30) / 1.0 / 1e9)
    assert journal.rate_GB_per_s(obs, "no_such_stage") is None


def test_loop_cpu_is_interpolated_between_the_samples_around_each_end(
        loop_samples):
    obs = _obs([], t0=100.25, t1=110.75)
    user, system = journal.loop_cpu(obs)
    assert user == pytest.approx(0.6 * 10.5)
    assert system == pytest.approx(0.2 * 10.5)
    # a window the samples do not span says nothing, and nor does no ring
    assert journal.loop_cpu(_obs([], t0=90.0, t1=100.0)) is None
    assert journal.loop_cpu(_obs([], t0=100.0, t1=200.0)) is None
    loop_samples.clear()
    assert journal.loop_cpu(obs) is None


EXPECTED = {
    "register_ms": 5.0,
    "dispatch_wait_share": 3.0 / 12.0,
    "wire_copy_GB_per_s": (1 << 30) / 0.2 / 1e9,
    "wire_chunk_KiB": (1 << 30) / 4096 / 1024,
    "land_GB_per_s": (1 << 30) / 1.0 / 1e9,
    "land_wait_ms": 4.5,
    "stage_copy_GB_per_s": (1 << 30) / 1.2 / 1e9,
    "loop_cpu_s_per_GiB": 8.0,
    "loop_sys_share": 0.25,
    "loop_cpu_attributed_share": (0.2 + 1.2 + 0.02) / 8.0,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_its_number_from_the_journal(name, loop_samples):
    assert set(EXPECTED) == set(NEW)
    read = harness.load_module("layer_metrics", name).read
    assert read(recorded()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_says_nothing_of_a_program_that_records_nothing(
        name, monkeypatch):
    """The commit before PR 26: flights with the old stages only (an
    ``hbm_done`` without a duration), no ``wire_chunks`` slot, no
    ``loop_samples`` on the plane. ``register_ms`` reads an event that was
    always there."""
    from dragonfly2_tpu.common import health
    monkeypatch.delattr(health.PLANE, "loop_samples", raising=False)
    old = _flight(100.5, [(5.0, "registered", -1, "", 0, 0.0),
                          (900.0, "wire_done", 0, "peer", MiB, 40.0),
                          (950.0, "hbm_done", 0, "", MiB, 0.0)], chunks=None)
    value = harness.load_module("layer_metrics", name).read(_obs([old]))
    assert value == (5.0 if name == "register_ms" else None)
