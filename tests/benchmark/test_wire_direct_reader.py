"""The per-layer reader of PR 31 (``wire_direct_share``) on journals made by
hand: what it reads, that a request that failed is left out, and that a
program whose flights have no ``wire_direct_bytes`` slot (the commit before)
gives it nothing to read and does not make it raise."""

import types
from collections import deque

import pytest

from benchmarks import harness

MiB = 1 << 20


def _request(direct: int | None, pieces: int = 4, ok: bool = True):
    events = [(5.0, "registered", -1, "", 0, 0.0)]
    events += [(100.0 * k, "wire_copy", k, "peer", 12 * MiB, 0.05)
               for k in range(pieces)]
    flight = types.SimpleNamespace(_m0=100.5, events=deque(events),
                                   wire_chunks=7 * pieces)
    if direct is not None:
        flight.wire_direct_bytes = direct
    return types.SimpleNamespace(ok=ok, flight=flight, bytes_p2p=0)


def _obs(requests):
    requests.append(types.SimpleNamespace(ok=False, flight=None,
                                          bytes_p2p=0))
    return types.SimpleNamespace(window=types.SimpleNamespace(
        t0=100.0, t1=110.0, requests=requests, bytes_ready=1 << 30))


def read(obs):
    return harness.load_module("layer_metrics", "wire_direct_share").read(obs)


def test_direct_bytes_over_the_bytes_the_wire_read():
    behind_heads = 4 * 3000              # rode in with the four heads
    obs = _obs([_request(48 * MiB - behind_heads), _request(48 * MiB),
                _request(1, ok=False)])  # a failed request counts nothing
    assert read(obs) == pytest.approx(1.0 - behind_heads / (96 * MiB))


@pytest.mark.parametrize("requests", [[], [_request(None)],
                                      [_request(0, pieces=0)]])
def test_nothing_to_read_says_nothing(requests):
    assert read(_obs(list(requests))) is None


def test_the_cell_lists_it_for_every_cell_and_edits_no_other_metric():
    for name in ("ckpt-warm-pull", "dataset-stream-beside-job",
                 "ckpt-warm-pull-4chip"):
        mine = [m for m in harness.load_cell(name).per_layer
                if m["name"] == "wire_direct_share"]
        assert len(mine) == 1 and mine[0]["layer"] == "wire"
        assert mine[0]["moves"] == "ready_MiB_per_s"
