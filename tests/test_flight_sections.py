"""The data path's sections as the program records them of itself: one pull
of a few MiB through a real scheduler, seed and leecher into a device sink
on the CPU backend, under an open ``jax.profiler`` trace, and what the
leecher's flight journal, the health plane's loop samples and the trace
then hold of it (``daemon/flight_recorder.py``: ``wire_copy``, ``landed``,
``staged``, ``land_wait``, ``hbm_done``'s duration, ``sink_open``, ``worker_wait``,
``worker_busy``; ``common/health.py``: ``PLANE.loop_samples``;
``common/tracing.py``: ``annotate``)."""

import asyncio
import glob
import os
import subprocess
import sys
import time
import types

import pytest

from dragonfly2_tpu.common import health, tracing
from dragonfly2_tpu.daemon import flight_recorder as fr
from dragonfly2_tpu.daemon.conductor import _span_lands
from dragonfly2_tpu.daemon.config import SchedulerConfig as DaemonSchedCfg
from dragonfly2_tpu.daemon.daemon import Daemon
from dragonfly2_tpu.daemon.piece_dispatcher import PieceDispatcher
from dragonfly2_tpu.daemon.piece_downloader import PieceDownloader
from dragonfly2_tpu.idl.messages import DeviceSink, DownloadRequest
from dragonfly2_tpu.scheduler import Scheduler, SchedulerConfig
from dragonfly2_tpu.scheduler.config import SeedPeerAddr

sys.path.insert(0, os.path.dirname(__file__))
from test_daemon_e2e import daemon_config, start_origin  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = (18 << 20) + 777                      # five pieces of 4 MiB
WINDOW = "test:pull"


def _lands() -> dict[str, float]:
    return {k[0]: v for k, _suffix, v in _span_lands._samples()}


def _loop_cpu_total() -> float:
    return sum(v for _k, _suffix, v in health._loop_cpu._samples())


def pull(tmp_path, *, flight_enabled: bool = True, size: int = SIZE):
    """Origin -> seed -> leecher (P2P only, device sink): what the leecher
    holds of it afterwards."""
    out = types.SimpleNamespace()

    async def go():
        data = os.urandom(size)
        origin, base = await start_origin({"w.bin": data})
        seed_cfg = daemon_config(tmp_path, "seed")
        seed_cfg.is_seed = True
        seed = Daemon(seed_cfg)
        await seed.start()
        sched = Scheduler(SchedulerConfig(seed_peers=[SeedPeerAddr(
            ip="127.0.0.1", rpc_port=seed.rpc.port,
            download_port=seed.upload_server.port)]))
        await sched.start()
        leech_cfg = daemon_config(tmp_path, "leech")
        leech_cfg.scheduler = DaemonSchedCfg(
            addresses=[sched.address], schedule_timeout_s=20.0)
        leech_cfg.flight.enabled = flight_enabled
        leech = Daemon(leech_cfg)
        await leech.start()
        try:
            lands0, cpu0 = _lands(), _loop_cpu_total()
            out.samples_before = len(health.PLANE.loop_samples)
            out.t0 = time.monotonic()
            async for _ in leech.ptm.start_file_task(DownloadRequest(
                    url=f"{base}/w.bin", output=str(tmp_path / "out.bin"),
                    disable_back_source=True, timeout_s=60.0,
                    device_sink=DeviceSink(enabled=True))):
                pass
            out.t1 = time.monotonic()
            await asyncio.sleep(0.25)        # two more ticks of the plane
            out.ok = (tmp_path / "out.bin").read_bytes() == data
            conductor = leech.ptm.conductor(
                next(iter(leech.ptm._conductors)))
            out.conductor = conductor
            out.flight = conductor.flight
            out.workers = leech.cfg.download.piece_parallelism
            out.lands = {k: v - lands0.get(k, 0.0)
                         for k, v in _lands().items()
                         if v - lands0.get(k, 0.0)}
            out.samples = list(health.PLANE.loop_samples)
            out.loop_cpu_counted = _loop_cpu_total() - cpu0
        finally:
            await leech.stop()
            await sched.stop()
            await seed.stop()
            await origin.cleanup()

    asyncio.run(go())
    return out


@pytest.fixture(scope="module")
def pulled(tmp_path_factory):
    """One traced pull, shared by the tests that read what it left."""
    import jax

    jax.devices()                            # the backend, before the trace
    tmp = tmp_path_factory.mktemp("sections")
    tracedir = str(tmp / "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tracedir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            out = pull(tmp)
    finally:
        jax.profiler.stop_trace()
    out.xplane = sorted(glob.glob(os.path.join(
        tracedir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    assert out.ok and out.flight is not None
    out.events = list(out.flight.events)
    out.done_ms = next(t for t, stage, *_ in out.events if stage == fr.DONE)
    return out


def _of(pulled, stage):
    return [e for e in pulled.events if e[1] == stage]


def test_every_dispatch_journals_its_wire_copy(pulled):
    copies = _of(pulled, fr.WIRE_COPY)
    # one first_byte a request, one wire_copy a request that came back
    assert len(copies) == len(_of(pulled, fr.FIRST_BYTE)) >= 1
    assert sum(e[4] for e in copies) == SIZE
    for t, _s, piece, parent, nbytes, dur in copies:
        assert piece >= 0 and parent and nbytes > 0
        assert 0 <= dur and 0 <= t <= pulled.done_ms
    # the copy cannot have run longer than the wire took
    wire = sum(r["wire_ms"] + r["ttfb_ms"]
               for r in pulled.flight.summarize()["piece_rows"])
    assert 0 < sum(e[5] for e in copies) <= wire
    assert 1 <= pulled.flight.wire_chunks <= SIZE
    assert pulled.flight.summarize()["wire_chunks"] == \
        pulled.flight.wire_chunks


def test_every_landing_journals_landed_and_its_wait(pulled):
    landed, waits = _of(pulled, fr.LANDED), _of(pulled, fr.LAND_WAIT)
    assert len(landed) == len(waits) == sum(pulled.lands.values()) >= 1
    # the path the counter counted is the path the journal names
    by_path: dict[str, int] = {}
    for e in landed:
        by_path[e[3]] = by_path.get(e[3], 0) + 1
    assert by_path == {k: int(v) for k, v in pulled.lands.items()}
    assert set(by_path) <= {"native", "python", "per_piece"}
    assert sum(e[4] for e in landed) == SIZE
    for (t, _s, piece, path, _n, dur), wait in zip(landed, waits):
        assert 0 <= dur and 0 <= t <= pulled.done_ms
        assert wait[2] == piece and wait[3] == path and wait[5] >= 0
        # the thread began before the loop resumed the coroutine
        assert t <= wait[0] <= pulled.done_ms


def test_staging_and_sink_open_carry_their_seconds(pulled):
    staged = _of(pulled, fr.HBM_DONE)
    assert sum(e[4] for e in staged) == SIZE
    assert all(0 <= e[5] and 0 <= e[0] <= pulled.done_ms for e in staged)
    assert sum(e[5] for e in staged) > 0
    (opened,) = _of(pulled, fr.SINK_OPEN)
    assert opened[4] == SIZE and opened[5] > 0
    assert 0 <= opened[0] <= min(e[0] for e in staged)


def test_summary_rows_split_hbm_ms_into_landing_and_staging(pulled):
    s = pulled.flight.summarize()
    assert s["piece_rows"]
    for row in s["piece_rows"]:
        assert row["land_ms"] >= 0 and row["stage_ms"] >= 0
        assert row["land_ms"] + row["stage_ms"] == pytest.approx(
            row["hbm_ms"], abs=0.002)
    sec = s["sections_ms"]
    assert set(sec) == set(fr.SECTIONS) | {"stage_copy"}
    assert sec["stage_copy"] == pytest.approx(
        sum(r["stage_ms"] for r in s["piece_rows"]), abs=0.01)
    assert sec[fr.LANDED] > 0 and sec[fr.WIRE_COPY] > 0
    # the compact form that rides the PeerResult keeps the totals
    assert pulled.flight.compact_summary()["sections_ms"] == sec


def test_new_stages_account_for_a_single_piece_dispatchs_hbm_ms(pulled):
    """``landed`` + ``land_wait`` + the staging copy are what lies between
    a piece's last byte and its ``hbm_done``; what is left is Python
    between them, and never negative."""
    rows = {r["piece"]: r for r in pulled.flight.summarize()["piece_rows"]}
    landed = {e[2]: e for e in _of(pulled, fr.LANDED)}
    waits = {e[2]: e for e in _of(pulled, fr.LAND_WAIT)}
    staged = {e[2]: e for e in _of(pulled, fr.HBM_DONE)}
    singles = [n for n, e in landed.items()
               if e[4] == rows[n]["bytes"]]   # a span of one piece
    assert singles
    for n in singles:
        parts = landed[n][5] + waits[n][5] + staged[n][5]
        assert parts <= rows[n]["hbm_ms"] + 0.01
        assert rows[n]["hbm_ms"] - parts < 250.0


def test_staged_is_the_storage_threads_copy_beside_landed(pulled):
    """Every byte reaches the sink's host buffer in a landing's own hop
    (``staged``: its seconds, its bytes, the landing's piece and path),
    and for a dispatch of one piece ``landed`` + ``staged`` + ``land_wait``
    + the sink's accounting still lie inside its ``hbm_ms``."""
    staged = _of(pulled, fr.STAGED)
    landed = {e[2]: e for e in _of(pulled, fr.LANDED)}
    assert sum(e[4] for e in staged) == SIZE
    assert sum(e[4] for e in staged) == \
        sum(e[4] for e in _of(pulled, fr.HBM_DONE))
    for t, _s, piece, path, nbytes, dur in staged:
        assert dur > 0 and 0 <= t <= pulled.done_ms
        assert landed[piece][3] == path and landed[piece][4] == nbytes
        assert landed[piece][5] >= 0
    (opened,) = _of(pulled, fr.SINK_OPEN)
    assert opened[3] in ("hit", "miss")
    rows = {r["piece"]: r for r in pulled.flight.summarize()["piece_rows"]}
    waits = {e[2]: e for e in _of(pulled, fr.LAND_WAIT)}
    done = {e[2]: e for e in _of(pulled, fr.HBM_DONE)}
    for t, _s, n, _path, nbytes, dur in staged:
        if nbytes == rows[n]["bytes"]:       # a span of one piece
            parts = landed[n][5] + dur + waits[n][5] + done[n][5]
            assert parts <= rows[n]["hbm_ms"] + 0.01


def test_worker_seconds_are_journaled_before_done(pulled):
    (busy,) = _of(pulled, fr.WORKER_BUSY)
    waits = _of(pulled, fr.WORKER_WAIT)
    assert busy[5] > 0 and busy[0] <= pulled.done_ms
    buckets = set(PieceDispatcher().wait_stats)
    assert all(e[5] > 0 and e[3] in buckets and e[0] <= busy[0]
               for e in waits)
    assert len({e[3] for e in waits}) == len(waits)
    assert busy[5] + sum(e[5] for e in waits) \
        <= pulled.workers * pulled.done_ms
    assert pulled.events[-1][1] == fr.DONE


def test_loop_samples_grow_in_time_and_cpu_and_stay_bounded(pulled):
    samples = pulled.samples
    assert len(samples) > pulled.samples_before or \
        len(samples) == health.PLANE.MAX_LOOP_SAMPLES
    assert health.PLANE.loop_samples.maxlen == health.PLANE.MAX_LOOP_SAMPLES
    inside = [s for s in samples if pulled.t0 <= s[0] <= pulled.t1 + 0.3]
    assert len(inside) >= 2
    for (ta, la, ua, sa), (tb, lb, ub, sb) in zip(samples, samples[1:]):
        assert ta < tb and ua <= ub and sa <= sb and la >= 0 and lb >= 0
    # the loop did the pull: its thread burned CPU in it, and the operator's
    # counter moved by what the samples say
    spent = (inside[-1][2] + inside[-1][3]) - (inside[0][2] + inside[0][3])
    assert 0 < spent <= (inside[-1][0] - inside[0][0]) * 1.05 + 0.02
    assert pulled.loop_cpu_counted >= spent - 1e-6


def test_loop_samples_are_bounded_by_the_ring():
    plane = health.HealthPlane()
    assert plane.loop_samples.maxlen == plane.MAX_LOOP_SAMPLES == 4096

    async def go():
        plane.acquire(health.HealthConfig(sample_interval_s=0.01))
        try:
            await asyncio.sleep(0.2)
        finally:
            plane.release()

    asyncio.run(go())
    assert 3 <= len(plane.loop_samples) <= 30


def test_loop_samples_are_empty_with_the_plane_disabled():
    plane = health.HealthPlane()

    async def go():
        plane.acquire(health.HealthConfig(sample_interval_s=0.01))
        await asyncio.sleep(0.05)
        assert plane.loop_samples
        # last caller wins, OFF included: the series goes with the monitor
        plane.acquire(health.HealthConfig(enabled=False))
        await asyncio.sleep(0.05)
        plane.release()
        plane.release()

    asyncio.run(go())
    assert not plane.active and len(plane.loop_samples) == 0


def test_a_pull_with_the_flight_recorder_disabled_journals_nothing(tmp_path):
    out = pull(tmp_path, flight_enabled=False, size=(5 << 20) + 3)
    assert out.ok and out.flight is None
    assert out.conductor.state == out.conductor.SUCCESS
    assert sum(out.lands.values()) >= 1      # it landed all the same


def test_the_profiler_trace_holds_the_programs_spans(pulled):
    import jax

    data = jax.profiler.ProfileData.from_file(pulled.xplane)
    spans: dict[str, list[tuple[int, int]]] = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("df:") or e.name == WINDOW:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    ((lo, hi),) = spans[WINDOW]
    for name in ("df:land", "df:hbm_transfer", "df:sink_open"):
        assert name in spans, sorted(spans)
        assert all(lo <= s <= e <= hi for s, e in spans[name]), name
    # the staging copy rides the landing: the native call makes it inside
    # df:land; only a copy made in Python has a span of its own
    in_python = [e for e in _of(pulled, fr.STAGED) if e[3] != "native"]
    assert (len(spans.get("df:stage_copy", ())) >= len(in_python)
            and bool(in_python) == ("df:stage_copy" in spans))
    assert len(spans["df:sink_open"]) == 1
    # the seed shares the process, and its landings off the origin the span
    assert len(spans["df:land"]) >= len(_of(pulled, fr.LANDED))


def test_annotate_is_the_profilers_span_only_where_jax_already_is():
    import jax

    with tracing.annotate("stage_copy") as section:
        assert isinstance(section, jax.profiler.TraceAnnotation)
    # a process that never imported jax gets a no-op and stays off it,
    # whatever of the data path it imports
    code = (
        "import sys\n"
        "from dragonfly2_tpu.common import health, tracing\n"
        "from dragonfly2_tpu.daemon import conductor, piece_engine\n"
        "with tracing.annotate('land') as section:\n"
        "    assert section is None, section\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print('off jax')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env={**os.environ, "PYTHONPATH": ROOT},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "off jax"


def test_read_body_counts_chunks_and_times_only_its_own_work():
    from test_piece_wire import CONTENT, StubParent, head

    from dragonfly2_tpu.common.bufpool import POOL
    from dragonfly2_tpu.idl.messages import PieceInfo

    parts = [1000, 3000, 96]

    async def three_parts(req, writer):
        body, off = req.body(), 0
        writer.write(head(206, req.size))
        for n in parts:
            await writer.drain()
            await asyncio.sleep(0.02)        # the wire: not this module
            writer.write(body[off:off + n])
            off += n

    async def main():
        async with StubParent(three_parts) as stub:
            dl = PieceDownloader(timeout_s=10)
            meta: dict = {}
            buf, _cost = await dl.download_piece(
                dst_addr=stub.addr, task_id="t" * 64, src_peer_id="me",
                piece=PieceInfo(piece_num=0, range_start=0,
                                range_size=4096), meta=meta)
            await dl.close()
            return buf, meta

    buf, meta = asyncio.run(main())
    try:
        assert bytes(buf) == CONTENT[:4096]
    finally:
        POOL.release(buf)
    assert meta["chunks"] == 4               # the head's read, then three
    assert meta["direct"] == 4096            # received where they land
    assert 0 <= meta["copy_s"] < 0.02        # three sleeps would be 0.06


def test_dfdiag_splits_landing_from_staging_and_names_parked_workers(pulled):
    from dragonfly2_tpu.tools.dfdiag import render_waterfall, verdict

    summary = pulled.flight.summarize()
    said = verdict(summary)
    assert "of landing + HBM staging," in said
    assert "the sink's bookkeeping on the daemon loop" in said
    assert "the staging copy" in said        # now the storage thread's
    assert "piece workers were parked" in said
    assert "#=landing + HBM staging" in render_waterfall(summary)
    # a summary from before the split says neither, and does not raise
    old = {k: v for k, v in summary.items() if k != "sections_ms"}
    old["piece_rows"] = [{k: v for k, v in r.items()
                          if k not in ("land_ms", "stage_ms")}
                         for r in summary["piece_rows"]]
    assert "of landing + HBM staging," not in verdict(old)
    assert "piece workers" not in verdict(old)
