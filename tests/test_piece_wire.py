"""The wire's own receive path (``daemon/piece_downloader.py``) against a
stub parent on loopback: raw sockets that answer the one GET a daemon
makes, by a script. Nothing here mocks an HTTP library: the stub writes
the bytes a parent's upload server would, in the segments the test asks
for, and the client under test is the real one.
"""

import asyncio
import os
import ssl

import pytest

from dragonfly2_tpu.common import faultgate
from dragonfly2_tpu.common.bufpool import POOL
from dragonfly2_tpu.common.errors import Code, DFError
from dragonfly2_tpu.daemon.piece_downloader import PieceDownloader
from dragonfly2_tpu.idl.messages import PieceInfo

TASK = "ab" * 32
CONTENT = os.urandom(3 << 20)
SIZE = 1_000_003         # no other test parks a buffer of this size


class Request:
    def __init__(self, head: bytes):
        lines = head.decode("latin-1").split("\r\n")
        self.method, self.target, self.version = lines[0].split(" ")
        self.path, _, self.query = self.target.partition("?")
        self.headers = {k.strip().lower(): v.strip() for k, _, v in
                        (line.partition(":") for line in lines[1:] if line)}
        first, _, last = self.headers["range"].removeprefix(
            "bytes=").partition("-")
        self.start, self.size = int(first), int(last) - int(first) + 1

    def body(self) -> bytes:
        return CONTENT[self.start:self.start + self.size]


def head(status: int = 206, length: int | None = None, **headers) -> bytes:
    lines = [f"HTTP/1.1 {status} Stub"]
    if length is not None:
        lines.append(f"Content-Length: {length}")
    lines += [f"{k.replace('_', '-')}: {v}" for k, v in headers.items()]
    return ("\r\n".join(lines) + "\r\n\r\n").encode()


class StubParent:
    """``answer(req, writer)`` writes one response; returning False (or
    raising) closes the connection, anything else keeps it alive for the
    next request."""

    def __init__(self, answer, ssl_ctx=None):
        self.answer = answer
        self.ssl_ctx = ssl_ctx
        self.accepts = 0
        self.requests: list[Request] = []
        self._writers = set()

    async def __aenter__(self):
        self.server = await asyncio.start_server(
            self._serve, "127.0.0.1", 0, ssl=self.ssl_ctx)
        self.port = self.server.sockets[0].getsockname()[1]
        self.addr = f"127.0.0.1:{self.port}"
        return self

    async def __aexit__(self, *exc):
        self.server.close()
        for writer in list(self._writers):      # a failed test's leftovers
            writer.transport.abort()
        await self.server.wait_closed()

    async def _serve(self, reader, writer):
        self.accepts += 1
        self._writers.add(writer)
        try:
            while True:
                try:
                    raw = await reader.readuntil(b"\r\n\r\n")
                except (asyncio.IncompleteReadError, ConnectionError):
                    return                  # the client hung up
                req = Request(raw[:-4])
                self.requests.append(req)
                if await self.answer(req, writer) is False:
                    return
                await writer.drain()
        except ConnectionError:
            pass
        finally:
            self._writers.discard(writer)
            writer.close()


async def whole(req, writer):
    """Head and body in one segment."""
    writer.write(head(206, req.size) + req.body())


def piece(num: int = 0, start: int = 0, size: int = SIZE) -> PieceInfo:
    return PieceInfo(piece_num=num, range_start=start, range_size=size)


async def pull(dl, stub, *, start=0, size=SIZE, **kw):
    return await dl.download_piece(
        dst_addr=stub.addr, task_id=TASK, src_peer_id="child-1",
        piece=piece(0, start, size), **kw)


class Span:
    """What ``relay_open(buf)`` hands back (daemon/relay.py RelaySpan)."""

    def __init__(self, log):
        self.marks = []
        self.log = log

    def advance(self, off):
        self.marks.append(off)

    def close(self):
        self.log.append("span.close")


@pytest.fixture(autouse=True)
def _clean_pool_and_faults():
    POOL.clear()
    faultgate.reset()
    yield
    faultgate.reset()
    POOL.clear()


def run(main):
    asyncio.run(asyncio.wait_for(main(), 60))


def test_head_and_first_body_bytes_in_one_segment():
    async def main():
        async with StubParent(whole) as stub:
            dl = PieceDownloader(timeout_s=10)
            meta = {}
            buf, cost = await pull(dl, stub, start=17, meta=meta)
            assert bytes(buf) == CONTENT[17:17 + SIZE] and cost >= 0
            # what rode in behind the head was copied once and counted:
            # the rest the kernel wrote in place
            assert 0 < SIZE - meta["direct"] <= 4096
            assert meta["relayed"] is False
            req = stub.requests[0]
            assert req.path == f"/download/{TASK[:3]}/{TASK}"
            assert req.query == "peerId=child-1"
            assert req.headers["range"] == f"bytes=17-{17 + SIZE - 1}"
            assert req.headers["host"] == stub.addr
            POOL.release(buf)
            await dl.close()
    run(main)


def test_body_dribbled_in_many_segments():
    async def main():
        async def dribble(req, writer):
            writer.write(head(206, req.size))
            body = req.body()
            for i in range(0, len(body), 100_000):
                await writer.drain()
                await asyncio.sleep(0.005)
                writer.write(body[i:i + 100_000])

        async with StubParent(dribble) as stub:
            dl = PieceDownloader(timeout_s=10)
            meta, firsts, log = {}, [], []
            span = Span(log)
            buf, _ = await pull(dl, stub, meta=meta,
                                on_first_byte=lambda: firsts.append(1),
                                relay_open=lambda b: span)
            assert bytes(buf) == CONTENT[:SIZE]
            assert firsts == [1]                 # once, at the first byte
            # a read a segment (the head's among them), the watermark
            # after each, rising to the size
            assert meta["chunks"] >= 10
            assert span.marks == sorted(set(span.marks))
            assert span.marks[-1] == SIZE and len(span.marks) >= 10
            assert log == []                     # the engine retires it
            assert meta["direct"] == SIZE        # the head came alone
            # eleven sleeps of the stub's are 0.055 s: not this module's
            assert 0 <= meta["copy_s"] < 0.05
            POOL.release(buf)
            await dl.close()
    run(main)


def test_two_bodies_back_to_back_on_one_kept_alive_connection():
    async def main():
        async with StubParent(whole) as stub:
            dl = PieceDownloader(timeout_s=10)
            a, _ = await pull(dl, stub, start=0)
            b, _ = await pull(dl, stub, start=SIZE)
            assert bytes(a) == CONTENT[:SIZE]
            assert bytes(b) == CONTENT[SIZE:2 * SIZE]
            assert stub.accepts == 1 and len(stub.requests) == 2
            # four at once need four connections, and all are kept
            got = await asyncio.gather(*(pull(dl, stub, start=i)
                                         for i in range(4)))
            assert [bytes(g[0]) for g in got] == \
                [CONTENT[i:i + SIZE] for i in range(4)]
            assert stub.accepts == 4 and len(dl._idle) == 4
            for buf in (a, b, *(g[0] for g in got)):
                POOL.release(buf)
            await dl.close()
            assert not dl._conns and not dl._idle
    run(main)


def test_idle_connection_the_parent_closed_is_retried_on_a_fresh_one():
    async def main():
        async def once_then_close(req, writer):
            writer.write(head(206, req.size) + req.body())
            await writer.drain()
            await asyncio.sleep(0.05)
            return False

        async with StubParent(once_then_close) as stub:
            dl = PieceDownloader(timeout_s=10)
            a, _ = await pull(dl, stub)
            await asyncio.sleep(0.2)       # the stub has closed it by now
            b, _ = await pull(dl, stub, start=5)
            assert bytes(b) == CONTENT[5:5 + SIZE]
            assert stub.accepts == 2
            POOL.release(a)
            POOL.release(b)
            await dl.close()

        # the close races the next request: the parent takes the request
        # and hangs up without a byte. Once more on a fresh connection,
        # and only once
        hangups = []

        async def hang_up_on_reuse(req, writer):
            if len(stub2.requests) == 2 or len(hangups) > 1:
                hangups.append(1)
                return False
            writer.write(head(206, req.size) + req.body())

        async with StubParent(hang_up_on_reuse) as stub2:
            dl = PieceDownloader(timeout_s=10)
            a, _ = await pull(dl, stub2)
            b, _ = await pull(dl, stub2, start=9)
            assert bytes(b) == CONTENT[9:9 + SIZE]
            assert stub2.accepts == 2 and len(stub2.requests) == 3
            POOL.release(a)
            POOL.release(b)
            # a FRESH connection that dies before its head is a stall,
            # not retried
            hangups.append(1)
            await dl.close()
            with pytest.raises(DFError) as ei:
                await pull(dl, stub2)
            assert ei.value.fail_code == "stall"
            assert stub2.accepts == 3
    run(main)


def test_busy_parent_says_when_to_come_back_and_keeps_the_connection():
    async def main():
        async def busy(req, writer):
            if len(stub.requests) == 1:
                text = b"upload concurrency limit"
                writer.write(head(503, len(text), X_Retry_After_Ms=137)
                             + text)
            else:
                await whole(req, writer)

        async with StubParent(busy) as stub:
            dl = PieceDownloader(timeout_s=10)
            with pytest.raises(DFError) as ei:
                await pull(dl, stub)
            assert ei.value.code == Code.CLIENT_PEER_BUSY
            assert ei.value.retry_after_ms == 137
            assert POOL.pooled_bytes() == SIZE      # the buffer went back
            buf, _ = await pull(dl, stub)
            assert bytes(buf) == CONTENT[:SIZE]
            assert stub.accepts == 1       # the 503's body was drained
            POOL.release(buf)
            await dl.close()
    run(main)


@pytest.mark.parametrize("status", [404, 416, 500])
def test_any_other_status_is_refused(status):
    async def main():
        async def nope(req, writer):
            writer.write(head(status, 9) + b"not here\n")

        async with StubParent(nope) as stub:
            dl = PieceDownloader(timeout_s=10)
            with pytest.raises(DFError) as ei:
                await pull(dl, stub)
            assert ei.value.code == Code.CLIENT_PIECE_DOWNLOAD_FAIL
            assert ei.value.fail_code == "refused"
            assert f"HTTP {status}" in ei.value.message
            assert POOL.pooled_bytes() == SIZE
            await dl.close()
    run(main)


def test_connection_that_cannot_be_made_is_refused():
    async def main():
        async with StubParent(whole) as stub:
            pass                                   # the port is shut now
        dl = PieceDownloader(timeout_s=10)
        with pytest.raises(DFError) as ei:
            await pull(dl, stub)
        assert ei.value.fail_code == "refused"
        assert POOL.pooled_bytes() == SIZE and not dl._conns
    run(main)


@pytest.mark.parametrize("said,word", [(SIZE - 1, "short"),
                                       (SIZE + 1, "long"),
                                       (None, "short")])
def test_content_length_other_than_asked_is_a_stall(said, word):
    async def main():
        log = []

        async def wrong(req, writer):
            writer.write(head(206, said) + req.body()[:4096])
            await writer.drain()
            await asyncio.sleep(0.05)
            return False

        async with StubParent(wrong) as stub:
            dl = PieceDownloader(timeout_s=10)
            seen = []

            def relay_open(buf):
                seen.append(buf)
                return Span(log)

            real_release = POOL.release
            POOL.release = lambda b: (log.append("release"),
                                      real_release(b))[1]
            try:
                with pytest.raises(DFError) as ei:
                    await pull(dl, stub, relay_open=relay_open)
            finally:
                POOL.release = real_release
            assert ei.value.fail_code == "stall"
            assert f"{word} read" in ei.value.message
            # the span is retired before the buffer is anyone else's
            assert log == ["span.close", "release"]
            assert POOL.acquire(SIZE) is seen[0]    # back in the pool
            assert not dl._idle and not dl._conns   # never reused
            await dl.close()
    run(main)


def test_connection_cut_mid_body_is_a_stall():
    async def main():
        async def cut(req, writer):
            writer.write(head(206, req.size) + req.body()[:300_000])
            await writer.drain()
            await asyncio.sleep(0.05)
            writer.transport.abort()
            return False

        async with StubParent(cut) as stub:
            dl = PieceDownloader(timeout_s=10)
            log = []
            span = Span(log)
            with pytest.raises(DFError) as ei:
                await pull(dl, stub, relay_open=lambda b: span)
            assert ei.value.code == Code.CLIENT_PIECE_DOWNLOAD_FAIL
            assert ei.value.fail_code == "stall"
            assert span.marks and span.marks[-1] <= 300_000
            assert log == ["span.close"]
            assert POOL.pooled_bytes() == SIZE
            assert not dl._idle and not dl._conns
            await dl.close()
    run(main)


def test_relayed_serve_class_param_and_trace_ride_the_request():
    async def main():
        async def relayed(req, writer):
            writer.write(head(206, req.size, X_DF_Relay=1) + req.body())

        from dragonfly2_tpu.common import tracing
        async with StubParent(relayed) as stub:
            dl = PieceDownloader(timeout_s=10)
            meta = {}
            ctx = tracing.from_traceparent(f"00-{'1' * 32}-{'2' * 16}-01")
            with tracing.span("test.pull", parent=ctx):
                buf, _ = await dl.download_span(
                    dst_addr=stub.addr, task_id=TASK,
                    src_peer_id="child 1/é", qos_class="bulk", meta=meta,
                    pieces=[piece(3, 300, 500), piece(4, 800, 200)])
            assert bytes(buf) == CONTENT[300:1000]
            assert meta["relayed"] is True
            req = stub.requests[0]
            assert req.headers["range"] == "bytes=300-999"
            assert req.query == "peerId=child%201%2F%C3%A9&cls=bulk"
            assert "1" * 32 in req.headers["traceparent"]
            POOL.release(buf)
            await dl.close()
    run(main)


def test_faultgate_corrupts_the_first_read_in_place():
    async def main():
        async with StubParent(whole) as stub:
            dl = PieceDownloader(timeout_s=10)
            script = faultgate.arm("piece.wire", "corrupt", n=1)
            buf, _ = await pull(dl, stub)
            assert script.fired == 1
            assert buf[0] == CONTENT[0] ^ 0xFF      # one byte, the first
            assert bytes(buf[1:]) == CONTENT[1:SIZE]
            POOL.release(buf)
            again, _ = await pull(dl, stub)         # the script is spent
            assert bytes(again) == CONTENT[:SIZE]
            POOL.release(again)
            await dl.close()
    run(main)


def test_counters_chunks_copy_seconds_and_direct_bytes():
    async def main():
        async def two_parts(req, writer):
            body = req.body()
            writer.write(head(206, req.size) + body[:1000])
            await writer.drain()
            await asyncio.sleep(0.05)
            writer.write(body[1000:])

        async with StubParent(two_parts) as stub:
            dl = PieceDownloader(timeout_s=10)
            meta = {}
            buf, _ = await pull(dl, stub, size=50_000, meta=meta)
            assert bytes(buf) == CONTENT[:50_000]
            # the head's read (1,000 body bytes behind it, copied), then
            # the rest in place: loopback hands 49,000 bytes over in one
            # read or two
            assert meta["direct"] == 49_000
            assert 2 <= meta["chunks"] <= 3
            assert 0 < meta["copy_s"] < 0.05        # not the stub's sleep
            POOL.release(buf)
            await dl.close()
    run(main)


def test_no_more_than_max_connections_and_idle_ones_make_room():
    async def main():
        async with StubParent(whole) as one, StubParent(whole) as two:
            dl = PieceDownloader(timeout_s=10, max_connections=2)
            got = await asyncio.gather(*(pull(dl, one, start=i)
                                         for i in range(5)))
            assert [bytes(g[0]) for g in got] == \
                [CONTENT[i:i + SIZE] for i in range(5)]
            assert one.accepts == 2 and len(dl._conns) == 2
            # both are idle connections to `one`: a pull from `two`
            # closes the one used longest ago to make room
            buf, _ = await pull(dl, two)
            assert len(dl._conns) == 2 and two.accepts == 1
            assert sorted(c.addr for c in dl._idle) == \
                sorted([one.addr, two.addr])
            for b in (buf, *(g[0] for g in got)):
                POOL.release(b)
            await dl.close()
    run(main)


def test_head_over_64_KiB_is_not_read_further():
    async def main():
        async def endless(req, writer):
            writer.write(b"HTTP/1.1 206 Stub\r\n"
                         + b"X-Pad: " + b"x" * (70 << 10) + b"\r\n\r\n")

        async with StubParent(endless) as stub:
            dl = PieceDownloader(timeout_s=10)
            with pytest.raises(DFError) as ei:
                await pull(dl, stub)
            assert ei.value.fail_code == "stall"
            assert "head over 65536" in ei.value.message
            assert POOL.pooled_bytes() == SIZE and not dl._conns
    run(main)


def test_late_bytes_after_a_deadline_never_reach_a_released_buffer():
    """The safety rule: after the per-piece deadline the connection has
    let go of the buffer (view dropped, transport aborted) before the
    buffer went back to the pool, so what the parent sends afterwards
    lands nowhere."""
    async def main():
        go = asyncio.Event()
        sent = []

        async def half_then_wait(req, writer):
            body = req.body()
            writer.write(head(206, req.size) + body[:SIZE // 2])
            await writer.drain()
            await go.wait()
            try:
                writer.write(body[SIZE // 2:])
                await writer.drain()
                sent.append("rest")
            except ConnectionError:
                sent.append("reset")
            return False

        async with StubParent(half_then_wait) as stub:
            dl = PieceDownloader(timeout_s=1.0)
            seen = []
            with pytest.raises(DFError) as ei:
                await pull(dl, stub, relay_open=lambda b: seen.append(b))
            assert ei.value.fail_code == "timeout"
            assert not dl._idle and not dl._conns
            # the pool hands the very same buffer to the next download
            again = POOL.acquire(SIZE)
            assert again is seen[0]
            assert bytes(again[:SIZE // 2]) == CONTENT[:SIZE // 2]
            again[:] = b"\xa5" * SIZE
            go.set()
            for _ in range(50):
                await asyncio.sleep(0.01)
                if sent:
                    break
            await asyncio.sleep(0.1)
            assert sent
            assert again.count(0xa5) == SIZE      # every sentinel in place
            POOL.release(again)
            assert POOL.pooled_bytes() == SIZE    # and no view left on it
            await dl.close()
    run(main)


def test_cancelled_pull_lets_go_of_the_buffer_the_same_way():
    async def main():
        async def never(req, writer):
            writer.write(head(206, req.size) + req.body()[:1000])
            await writer.drain()
            await asyncio.sleep(30)

        async with StubParent(never) as stub:
            dl = PieceDownloader(timeout_s=30)
            log = []
            span = Span(log)
            task = asyncio.ensure_future(
                pull(dl, stub, relay_open=lambda b: span))
            while not span.marks:
                await asyncio.sleep(0.01)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
            assert log == ["span.close"]
            assert POOL.pooled_bytes() == SIZE and not dl._conns
            await dl.close()
    run(main)


def test_one_pull_over_https_with_issued_certs(tmp_path):
    """``http`` and ``https`` are one path: asyncio's TLS transport feeds
    the same buffered protocol. Certificates issued by a manager, as
    ``test_security.py::test_daemon_peer_plane_over_issued_certs``'s
    daemons get theirs; the stub requires a fleet client certificate."""
    from dragonfly2_tpu.common import cryptoshim
    if not cryptoshim.install():
        pytest.skip("no cryptography wheel and no openssl binary")
    from dragonfly2_tpu.manager.server import Manager, ManagerConfig
    from dragonfly2_tpu.rpc.security import obtain_certificate

    async def main():
        m = Manager(ManagerConfig(listen_ip="127.0.0.1",
                                  workdir=str(tmp_path / "mgr"),
                                  issue_certs=True))
        await m.start()
        try:
            async def leaf(name):
                return await obtain_certificate(
                    [f"127.0.0.1:{m.port}"], hosts=["127.0.0.1", name],
                    token=m.issue_token, out_dir=str(tmp_path / name))

            cert, key, ca = await leaf("parent")
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(cert, key)
            ctx.load_verify_locations(cafile=ca)
            ctx.verify_mode = ssl.CERT_REQUIRED
            async with StubParent(whole, ssl_ctx=ctx) as stub:
                dl = PieceDownloader(timeout_s=10, tls=await leaf("child"))
                assert dl.scheme == "https"
                meta = {}
                a, _ = await pull(dl, stub, meta=meta)
                b, _ = await pull(dl, stub, start=SIZE)
                assert bytes(a) == CONTENT[:SIZE]
                assert bytes(b) == CONTENT[SIZE:2 * SIZE]
                assert stub.accepts == 1            # kept alive under TLS
                # the TLS layer decrypted into the pooled buffer itself
                assert SIZE - meta["direct"] <= 4096
                POOL.release(a)
                POOL.release(b)
                await dl.close()
                # a client with no fleet certificate cannot make the
                # connection at all
                bare = PieceDownloader(timeout_s=5)
                with pytest.raises(DFError):
                    await pull(bare, stub)
                await bare.close()
        finally:
            await m.stop()
    run(main)
