"""The four per-layer readers of the four-chip cell, on a journal made by
hand: what each reads of the ``hbm_shard`` events that name a chip, that only
the window counts, and that a program whose ``hbm_shard`` names no chip (the
commit before placement) gives each nothing to read and makes none raise."""

import types
from collections import deque

import pytest

from benchmarks import harness

GB = 10 ** 9


def _obs(events, chips=4, t0=100.0, t1=110.0, reduced=None):
    flight = types.SimpleNamespace(_m0=100.0, events=deque(events))
    requests = [types.SimpleNamespace(ok=True, flight=flight),
                types.SimpleNamespace(ok=False, flight=None)]
    window = types.SimpleNamespace(t0=t0, t1=t1, requests=requests,
                                   bytes_ready=8 * GB)
    return types.SimpleNamespace(window=window, reduced=reduced,
                                 cell=types.SimpleNamespace(chips=chips))


def _shard(t_ms, chip, nbytes, dur_ms):
    return (t_ms, "hbm_shard", 0, chip, nbytes, dur_ms)


def _read(name, obs):
    return harness.load_module("layer_metrics", name).read(obs)


def recorded():
    """Chips 0 and 1 transfer together for 2 s, chip 2 alone for 1 s, chip 3
    alone for 1 s in two halves; one more transfer starts after the
    window."""
    return _obs([
        (5.0, "registered", -1, "", 0, 0.0),
        _shard(1000.0, "0", 2 * GB, 2000.0),
        _shard(1000.0, "1", 2 * GB, 1000.0),
        _shard(2000.0, "1", 1 * GB, 1000.0),
        _shard(4000.0, "2", 2 * GB, 1000.0),
        _shard(6000.0, "3", 1 * GB, 500.0),
        _shard(6500.0, "3", 1 * GB, 500.0),
        _shard(12000.0, "3", 5 * GB, 1000.0)])


def test_overlap_is_the_chips_busy_seconds_over_their_union():
    # chips: 2 + 2 + 1 + 1 = 6 s; union: [1,3] + [4,5] + [6,7] = 4 s
    assert _read("hbm_chips_overlap", recorded()) == pytest.approx(1.5)
    turns = _obs([_shard(1000.0 * c, str(c), GB, 1000.0) for c in range(4)])
    assert _read("hbm_chips_overlap", turns) == pytest.approx(1.0)
    together = _obs([_shard(1000.0, str(c), GB, 1000.0) for c in range(4)])
    assert _read("hbm_chips_overlap", together) == pytest.approx(4.0)


def test_the_slowest_chips_rate_is_its_bytes_over_its_own_union():
    # chip 0: 2 GB in 2 s; chip 1: 3 GB in 2 s; chip 2: 2 in 1; chip 3: 2 in 1
    assert _read("hbm_chip_GB_per_s_min", recorded()) == pytest.approx(1.0)


def test_skew_is_the_fullest_chip_over_the_mean_of_the_cells_chips():
    # 2, 3, 2, 2 GB: 3 over 2.25
    assert _read("placement_skew", recorded()) == pytest.approx(3 / 2.25)
    even = _obs([_shard(1000.0, str(c), GB, 100.0) for c in range(4)])
    assert _read("placement_skew", even) == pytest.approx(1.0)
    # everything on one chip of four reads 4, not 1
    one = _obs([_shard(1000.0, "2", GB, 100.0)])
    assert _read("placement_skew", one) == pytest.approx(4.0)


def test_a_span_that_leaves_the_window_is_cut_to_it():
    obs = _obs([_shard(9000.0, "0", GB, 3000.0),
                _shard(9500.0, "1", GB, 500.0)])
    # chip 0: [109, 110] of [109, 112]; chip 1: [109.5, 110]
    assert _read("hbm_chips_overlap", obs) == pytest.approx(1.5)


def test_the_idle_share_is_the_traces_reduction():
    assert _read("device_idle_share.host4",
                 _obs([], reduced={"idle_share": 0.9991})) == 0.9991
    assert _read("device_idle_share.host4", _obs([])) is None


@pytest.mark.parametrize("name", ["hbm_chips_overlap",
                                  "hbm_chip_GB_per_s_min", "placement_skew"])
def test_a_program_that_names_no_chip_gives_nothing_to_read(name):
    before = _obs([(1000.0, "hbm_shard", i, "", 0, 500.0) for i in range(4)])
    assert _read(name, before) is None
    assert _read(name, _obs([])) is None
