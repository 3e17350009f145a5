"""Piece downloader: the bulk data path between peers.

Role parity: reference ``client/daemon/peer/piece_downloader.go:165-229`` —
``GET http://{dstAddr}/download/{taskID[:3]}/{taskID}?peerId=`` with a
``Range:`` header against the parent's upload server, verified against the
piece digest announced in the parent's PiecePacket.

The wire is the daemon's own: a small HTTP/1.1 client for that one call,
on kept-alive connections per parent address (``loop.create_connection``,
``ssl=`` for fleet mTLS: one path for ``http`` and ``https``), at most
``max_connections`` in all. Parents are fetched from many times, so
connection reuse is the difference between one RTT and three per piece.
Each connection is an ``asyncio.BufferedProtocol`` (``_Conn``): the
response head is read into a small scratch buffer and parsed by hand, and
from the first body byte on the transport's ``get_buffer`` IS the pooled
buffer the piece lands from — the kernel (or the TLS layer) writes the
body where it is going. No ``bytes`` per read, no stream reader, no slice
copy; one read takes whatever the socket holds.

Zero-stall contract: this module never traverses piece bytes on the event
loop. Bodies are received into POOLED buffers (common/bufpool.py — callers
release them once landed); digest verification happens in the storage
landing pass, off-loop, fused with the write (store.write_span) — hashing
each 4-16 MiB piece on the loop made piece bytes compete with sockets,
gossip, and gRPC for the daemon's one core, and was the dominant term in
df_loop_lag_seconds at fan-out.

Safety rule of the receive path: a transport that still held a view of a
released buffer would write a late byte into ANOTHER download's piece. So
a connection holds its view for the length of one body and no longer, and
on every exit that is not a full body (deadline, cancel, error, short or
long read) ``_Conn.drop`` first takes the view away (``get_buffer`` falls
back to the scratch buffer), then aborts the transport — it is never
returned to the idle set — and only then does ``_read_body`` release the
buffer: all on the loop's thread, in that order. The pool's export probe
is the second line, not the first.
"""

from __future__ import annotations

import asyncio
import logging
import time
from urllib.parse import quote

from ..common import faultgate, tracing
from ..common.bufpool import POOL
from ..common.errors import Code, DFError
from ..idl.messages import PieceInfo

log = logging.getLogger("df.flow.piecedl")

_HEAD_MAX = 64 << 10     # a response head over this is refused
_HEAD_READ = 4 << 10     # offered to one read while the head is awaited:
# what rides in behind the head is copied once, so keep it small

_IDLE, _HEAD, _BODY, _DRAIN, _DEAD = range(5)


def _classified(code: Code, message: str, fail_code: str) -> DFError:
    """DFError carrying a typed failure verdict (idl.FAIL_CODES): the
    engine forwards ``fail_code`` on the piece report and into the
    per-parent verdict ledger, where the *kind* of failure decides the
    response (corrupt = shun; stall/timeout/refused = congestion-shaped
    backoff only)."""
    err = DFError(code, message)
    err.fail_code = fail_code
    return err


class _Lost(ConnectionError):
    """The connection went away under a request. ``started``: a byte of
    the response had come (a stale kept-alive connection dies before)."""

    def __init__(self, message: str, started: bool):
        super().__init__(message)
        self.started = started


class _Conn(asyncio.BufferedProtocol):
    """One kept-alive connection to a parent's upload server; one request
    at a time. All of it runs on the loop's thread."""

    def __init__(self, addr: str, on_lost):
        self.addr = addr
        self.transport: asyncio.Transport | None = None
        self.served = 0              # responses read to their end
        self.keep = True             # the parent keeps the connection open
        self._on_lost = on_lost
        self._scratch = bytearray(_HEAD_MAX)
        self._sview = memoryview(self._scratch)
        self._state = _IDLE
        self._fut: asyncio.Future | None = None
        self._body: memoryview | None = None   # the pooled buffer, one body long
        self._reset(0, None, None, "")

    def _reset(self, size: int, on_first, span, what: str) -> None:
        self._size = size
        self._on_first = on_first
        self._span = span
        self._what = what
        self._head_n = 0             # head bytes in the scratch buffer
        self._off = 0                # body bytes in the pooled buffer
        self._left = 0               # bytes of an unwanted body to drain
        self.status = 0
        self.length = -1             # Content-Length (-1: none given)
        self.headers: dict[str, str] = {}
        self.reads = 0               # buffer_updated calls
        self.busy_s = 0.0            # seconds inside this class's callbacks
        self.direct = 0              # body bytes received in place

    @property
    def alive(self) -> bool:
        return self._state != _DEAD and self.transport is not None \
            and not self.transport.is_closing()

    # -- the request ----------------------------------------------------

    async def fetch(self, request: bytes, buf: bytearray, size: int,
                    what: str, on_first, span) -> None:
        """Send ``request`` and receive its answer: a 200/206 body of
        exactly ``size`` bytes into ``buf``; any other answer leaves
        ``status``/``headers``/``length`` to be judged, its small body
        drained. Returns with the connection idle again (``keep`` says
        whether it may be reused); raises ``_Lost`` when it went away."""
        if not self.alive:
            raise _Lost("connection closed while it idled", False)
        self._reset(size, on_first, span, what)
        self._body = memoryview(buf)
        self._state = _HEAD
        self._fut = asyncio.get_running_loop().create_future()
        try:
            self.transport.write(request)
            await self._fut
        finally:
            self._fut = None

    def drop(self) -> None:
        """Take the pooled buffer's view away, THEN abort the transport
        (the module docstring's safety rule). Idempotent."""
        self._state = _DEAD
        self._release_view()
        if self.transport is not None:
            self.transport.abort()

    def _release_view(self) -> None:
        if self._body is not None:
            self._body.release()
            self._body = None
        self._span = self._on_first = None

    def _finish(self, exc: BaseException | None = None) -> None:
        self._release_view()
        if self._state != _DEAD:
            self._state = _IDLE
        fut = self._fut
        if fut is not None and not fut.done():
            if exc is None:
                self.served += 1
                fut.set_result(None)
            else:
                fut.set_exception(exc)

    # -- asyncio.BufferedProtocol ----------------------------------------

    def connection_made(self, transport) -> None:
        self.transport = transport

    def get_buffer(self, sizehint: int):
        if self._state == _BODY:
            t0 = time.perf_counter()
            view = self._body[self._off:]
            self.busy_s += time.perf_counter() - t0
            return view
        if self._state == _HEAD:
            return self._sview[self._head_n:self._head_n + _HEAD_READ]
        return self._sview       # draining, idle or dead: nowhere that matters

    def buffer_updated(self, nbytes: int) -> None:
        t0 = time.perf_counter()
        state = self._state
        if state == _BODY:
            self.reads += 1
            self.direct += nbytes
            self._advance(nbytes)
        elif state == _HEAD:
            self.reads += 1
            try:
                self._head_bytes(nbytes)
            except Exception as exc:  # noqa: BLE001 - a peer's bytes
                self._state = _DEAD
                self._finish(exc)
                self.transport.abort()
        elif state == _DRAIN:
            self.reads += 1
            self._left -= nbytes
            if self._left <= 0:
                self.keep = self.keep and self._left == 0
                self._finish()
        elif state == _IDLE:
            # bytes nobody asked for: not a connection to send the next
            # request on
            self._state = _DEAD
            self.transport.abort()
        self.busy_s += time.perf_counter() - t0

    def eof_received(self) -> bool:
        self._lost(None)
        return False

    def connection_lost(self, exc) -> None:
        self._lost(exc)
        self._on_lost(self)

    def _lost(self, exc) -> None:
        started = self._state != _HEAD or self._head_n > 0
        self._state = _DEAD
        self._finish(_Lost(
            f"connection lost at {self._off}/{self._size}"
            + (f": {type(exc).__name__}: {exc}" if exc else ""), started))

    # -- the response ----------------------------------------------------

    def _advance(self, nbytes: int) -> None:
        """``nbytes`` more of the body stand at ``_off`` in the pooled
        buffer."""
        if self._off == 0:
            if faultgate.ARMED:
                first = bytes(self._body[:nbytes])
                flipped = faultgate.corrupt("piece.wire", first,
                                            key=self._what)
                if flipped is not first:
                    self._body[:nbytes] = flipped
            if self._on_first is not None:
                self._on_first()
        self._off += nbytes
        if self._span is not None:
            self._span.advance(self._off)
        if self._off >= self._size:
            self._finish()

    def _head_bytes(self, nbytes: int) -> None:
        have = self._head_n + nbytes
        end = self._scratch.find(b"\r\n\r\n", max(0, self._head_n - 3), have)
        if end < 0:
            if have >= _HEAD_MAX:
                raise ValueError(f"response head over {_HEAD_MAX} bytes")
            self._head_n = have
            return
        self._head_n = have
        lines = bytes(self._sview[:end]).decode("latin-1").split("\r\n")
        version, _, rest = lines[0].partition(" ")
        if not version.startswith("HTTP/1."):
            raise ValueError(f"not an HTTP/1 response: {lines[0][:40]!r}")
        self.status = int(rest.split(" ", 1)[0])
        for line in lines[1:]:
            name, sep, value = line.partition(":")
            if sep:
                self.headers[name.strip().lower()] = value.strip()
        said = self.headers.get("connection", "").lower()
        self.keep = said == "keep-alive" or (version == "HTTP/1.1"
                                             and said != "close")
        if "transfer-encoding" not in self.headers:   # else: no length
            self.length = int(self.headers.get("content-length", "-1"))
        tail = have - (end + 4)      # body bytes that rode in with the head
        if self.status in (200, 206) and self.length == self._size:
            if tail > self._size:
                raise ValueError(f"{tail} bytes after a head that "
                                 f"announced {self._size}")
            self._state = _BODY
            if tail:
                self._body[:tail] = self._sview[end + 4:have]
                self._advance(tail)
            elif self._size == 0:
                self._finish()
            return
        # not the body that was asked for: the caller judges the head; a
        # small body is drained so that the connection can serve again
        self._release_view()
        self._left = self.length - tail
        if 0 <= self.length <= _HEAD_MAX and self._left > 0:
            self._state = _DRAIN
            return
        self.keep = self.keep and self._left == 0
        self._finish()


class PieceDownloader:
    def __init__(self, *, timeout_s: float = 30.0, max_connections: int = 64,
                 tls: tuple[str, str, str] | None = None):
        """``tls``: (cert, key, ca) — fleet mTLS material; piece GETs then
        ride https presenting the client leaf."""
        self.timeout_s = timeout_s
        self.max_connections = max_connections
        self.tls = tls
        self._ssl_ctx = None
        self._conns: set[_Conn] = set()      # every open connection
        self._idle: list[_Conn] = []         # kept alive, oldest first
        self._slots = asyncio.Semaphore(max_connections)

    @property
    def scheme(self) -> str:
        return "https" if self.tls is not None else "http"

    def _ssl(self):
        if self.tls is not None and self._ssl_ctx is None:
            import ssl as _ssl
            cert, key, ca = self.tls
            ctx = _ssl.create_default_context(cafile=ca)
            ctx.load_cert_chain(cert, key)
            ctx.check_hostname = False   # peers are dialed by IP;
            # the fleet CA signature is the authentication
            ctx.verify_mode = _ssl.CERT_REQUIRED
            self._ssl_ctx = ctx
        return self._ssl_ctx

    async def close(self) -> None:
        for conn in list(self._conns):
            self._close(conn)

    # -- connections -----------------------------------------------------

    def _forget(self, conn: _Conn) -> None:
        self._conns.discard(conn)
        if conn in self._idle:
            self._idle.remove(conn)

    def _close(self, conn: _Conn) -> None:
        conn.drop()
        self._forget(conn)

    async def _checkout(self, addr: str, what: str,
                        fresh: bool = False) -> _Conn:
        """An idle connection to ``addr`` (the one used last first), else
        a new one; the caller holds a slot, so under ``max_connections``
        open ones there is room, or an idle one of another parent to
        close for it."""
        if not fresh:
            for i in range(len(self._idle) - 1, -1, -1):
                if self._idle[i].addr == addr:
                    conn = self._idle.pop(i)
                    if conn.alive:
                        return conn
                    self._close(conn)
        while len(self._conns) >= self.max_connections and self._idle:
            self._close(self._idle[0])
        host, _, port = addr.rpartition(":")
        conn = _Conn(addr, self._forget)
        self._conns.add(conn)
        try:
            await asyncio.get_running_loop().create_connection(
                lambda: conn, host.strip("[]"), int(port), ssl=self._ssl())
        except BaseException as exc:
            self._close(conn)
            if not isinstance(exc, (OSError, ValueError)):
                raise
            # never moved a byte: not a mid-transfer stall
            raise _classified(
                Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                f"{what}: connect: {type(exc).__name__}: {exc}",
                "refused") from None
        return conn

    def _checkin(self, conn: _Conn) -> None:
        if conn.keep and conn.alive:
            self._idle.append(conn)
        else:
            self._close(conn)

    # -- one fetch ---------------------------------------------------------

    async def _read_body(self, addr: str, request: bytes, size: int,
                         what: str, on_first=None, relay_open=None,
                         meta: dict | None = None) -> bytearray:
        """Receive the answer to ``request`` into ONE pooled buffer: the
        one place that acquires it, hands its ownership to the caller
        (released back to the pool after landing) and returns it to the
        pool on every failure path — after the connection has let go of
        it (``_Conn.drop``: view first, then the transport; the module
        docstring's safety rule). No digest folding here: verification
        rides the storage write pass off-loop, so the bytes are still
        unverified when this returns. ``on_first`` fires once at the
        first body byte (flight-recorder ttfb). ``relay_open(buf)``
        (daemon/relay.py) registers the buffer as an in-flight relay span
        once acquired; its watermark advances after every read, and a
        failed read retires the span HERE, before the buffer returns to
        the pool — a relay reader must never copy from recycled memory.
        ``meta`` (the dict that rides ``download_span``) gets
        ``relayed``; ``chunks``, the reads the answer took
        (``buffer_updated`` calls); ``copy_s``, the seconds this module's
        own callbacks ran on the loop for them (``get_buffer``,
        ``buffer_updated``, the head's parse and the copy of what rode in
        behind it: the flight journal's ``wire_copy``); and ``direct``,
        the body bytes that were received in place, uncopied."""
        if faultgate.ARMED:
            # inside the request's timeout window: a 'hang' script parks
            # here until the per-piece deadline cancels the read, exactly
            # like a parent that wedged mid-transfer; 'corrupt' flips a
            # byte BEFORE landing so digest verification trips downstream
            await faultgate.fire("piece.wire", key=what)
        buf = POOL.acquire(size)
        span = relay_open(buf) if relay_open is not None else None
        conn = None
        try:
            async with self._slots:
                fresh = False
                while True:
                    conn = await self._checkout(addr, what, fresh)
                    try:
                        # dflint: disable=DF005 — _slots is the bound on open sockets, not a lock: a slot is held for the length of the transfer it counts, and the per-piece deadline bounds that
                        await conn.fetch(request, buf, size, what, on_first,
                                         span)
                        break
                    except _Lost as lost:
                        if fresh or lost.started or not conn.served:
                            raise
                        # the parent closed a kept-alive connection while
                        # it idled, and no byte of an answer had come:
                        # once more, on a fresh one
                        self._close(conn)
                        fresh = True
                status, length, headers = \
                    conn.status, conn.length, conn.headers
                chunks, copy_s, direct = conn.reads, conn.busy_s, conn.direct
                self._checkin(conn)
                conn = None
            if status == 503:
                # upload-slot backpressure: the parent is at its
                # concurrency limit, not broken — the dispatcher reroutes
                # the piece to another holder or retries after the
                # parent's measured-transfer-time hint
                err = DFError(Code.CLIENT_PEER_BUSY, f"parent {addr} busy")
                try:
                    err.retry_after_ms = int(
                        headers.get("x-retry-after-ms", "0"))
                except ValueError:
                    err.retry_after_ms = 0
                raise err
            if status not in (200, 206):
                raise _classified(Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                                  f"{what}: HTTP {status}", "refused")
            if length != size:
                raise _classified(
                    Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                    f"{what}: {'short' if length < size else 'long'} read "
                    f"{length}/{size}", "stall")
        except BaseException:
            if conn is not None:
                self._close(conn)
            if span is not None:
                span.close()
            POOL.release(buf)
            raise
        if meta is not None:
            # cut-through serve: the parent relayed these bytes
            # mid-landing — a later corrupt verdict on them is
            # attributed at reduced weight (see verdicts.record)
            meta["relayed"] = headers.get("x-df-relay") == "1"
            meta["chunks"] = chunks
            meta["copy_s"] = copy_s
            meta["direct"] = direct
        return buf

    async def _download(self, dst_addr: str, task_id: str, src_peer_id: str,
                        start: int, size: int, what: str, on_first_byte,
                        relay_open, qos_class: str,
                        meta: dict | None) -> tuple[bytearray, int]:
        query = f"peerId={quote(src_peer_id, safe='')}"
        if qos_class:
            query += f"&cls={quote(qos_class, safe='')}"
        head = [f"GET /download/{task_id[:3]}/{task_id}?{query} HTTP/1.1",
                f"Host: {dst_addr}",
                f"Range: bytes={start}-{start + size - 1}"]
        tp = tracing.traceparent()
        if tp:   # trace ctx rides the piece request (ref piece_downloader.go:227)
            head.append(f"traceparent: {tp}")
        request = ("\r\n".join(head) + "\r\n\r\n").encode("latin-1")
        t0 = time.monotonic()
        try:
            # hard per-piece deadline around all of it: the connect, the
            # head, every read of the body, and an injected piece.wire
            # hang — a parent that wedges anywhere cannot stall the worker
            data = await asyncio.wait_for(
                self._read_body(dst_addr, request, size, what,
                                on_first=on_first_byte,
                                relay_open=relay_open, meta=meta),
                self.timeout_s)
        except asyncio.TimeoutError:
            raise _classified(Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                              f"{what}: per-piece deadline "
                              f"({self.timeout_s:.0f}s)",
                              "timeout") from None
        except DFError:
            raise
        except Exception as exc:  # noqa: BLE001 - network boundary
            # a connection that could not be made is ``refused`` where it
            # failed (_checkout); anything that died with a request in
            # flight is a mid-transfer stall
            raise _classified(Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                              f"{what}: {type(exc).__name__}: {exc}",
                              "stall") from None
        cost_ms = int((time.monotonic() - t0) * 1000)
        return data, cost_ms

    async def download_piece(self, *, dst_addr: str, task_id: str,
                             src_peer_id: str, piece: PieceInfo,
                             on_first_byte=None, relay_open=None,
                             qos_class: str = "", meta: dict | None = None,
                             ) -> tuple[bytearray, int]:
        """Fetch one piece from a parent. Returns (data, cost_ms); ``data``
        is a POOLED buffer the caller owns (release to ``bufpool.POOL``
        after landing). Bytes are NOT digest-verified here — verification
        happens off-loop in the storage landing pass (the caller treats a
        landing-time mismatch as retry-on-another-parent, same as the
        transport errors raised here as CLIENT_PIECE_DOWNLOAD_FAIL).
        ``qos_class`` rides the GET as ``?cls=`` so the parent's upload
        server can admit the transfer under the right class gate.
        """
        return await self._download(
            dst_addr, task_id, src_peer_id, piece.range_start,
            piece.range_size, f"parent {dst_addr} piece {piece.piece_num}",
            on_first_byte, relay_open, qos_class, meta)

    async def download_span(self, *, dst_addr: str, task_id: str,
                            src_peer_id: str, pieces: list[PieceInfo],
                            on_first_byte=None, relay_open=None,
                            qos_class: str = "", meta: dict | None = None,
                            ) -> tuple[bytearray, int]:
        """Fetch CONTIGUOUS pieces in one ranged GET.

        Returns (buf, cost_ms): ONE pooled buffer holding every piece's
        bytes back to back from ``pieces[0].range_start`` — the caller
        owns it (release to ``bufpool.POOL`` after landing). No per-piece
        hashing happens here: verification is fused into the storage
        landing pass (``TaskStorage.write_span``), off the event loop,
        where a digest mismatch drops that piece (the dispatcher requeues
        it) without failing its groupmates. Transport errors raise like
        ``download_piece``.
        """
        if len(pieces) == 1:
            return await self.download_piece(
                dst_addr=dst_addr, task_id=task_id,
                src_peer_id=src_peer_id, piece=pieces[0],
                on_first_byte=on_first_byte, relay_open=relay_open,
                qos_class=qos_class, meta=meta)
        start = pieces[0].range_start
        size = sum(p.range_size for p in pieces)
        return await self._download(
            dst_addr, task_id, src_peer_id, start, size,
            f"parent {dst_addr} span @{start}+{size}",
            on_first_byte, relay_open, qos_class, meta)
