"""Piece downloader: the bulk data path between peers.

Role parity: reference ``client/daemon/peer/piece_downloader.go:165-229`` —
``GET http://{dstAddr}/download/{taskID[:3]}/{taskID}?peerId=`` with a
``Range:`` header against the parent's upload server, verified against the
piece digest announced in the parent's PiecePacket.

One shared aiohttp session with keep-alive connections per daemon: parents
are fetched from many times, so connection reuse is the difference between
one RTT and three per piece.

Zero-stall contract: this module never traverses piece bytes on the event
loop. Bodies stream into POOLED buffers (common/bufpool.py — callers
release them once landed) with only the per-chunk memcpy on-loop; digest
verification happens in the storage landing pass, off-loop, fused with
the write (store.write_span) — hashing each 4-16 MiB piece on the loop
made piece bytes compete with sockets, gossip, and gRPC for the daemon's
one core, and was the dominant term in df_loop_lag_seconds at fan-out.
"""

from __future__ import annotations

import asyncio
import logging
import time

import aiohttp

from ..common import faultgate, tracing
from ..common.bufpool import POOL
from ..common.errors import Code, DFError
from ..idl.messages import PieceInfo

log = logging.getLogger("df.flow.piecedl")


def _classified(code: Code, message: str, fail_code: str) -> DFError:
    """DFError carrying a typed failure verdict (idl.FAIL_CODES): the
    engine forwards ``fail_code`` on the piece report and into the
    per-parent verdict ledger, where the *kind* of failure decides the
    response (corrupt = shun; stall/timeout/refused = congestion-shaped
    backoff only)."""
    err = DFError(code, message)
    err.fail_code = fail_code
    return err


class PieceDownloader:
    def __init__(self, *, timeout_s: float = 30.0, max_connections: int = 64,
                 tls: tuple[str, str, str] | None = None):
        """``tls``: (cert, key, ca) — fleet mTLS material; piece GETs then
        ride https presenting the client leaf."""
        self.timeout_s = timeout_s
        self.max_connections = max_connections
        self.tls = tls
        self._session: aiohttp.ClientSession | None = None

    @property
    def scheme(self) -> str:
        return "https" if self.tls is not None else "http"

    def _get_session(self) -> aiohttp.ClientSession:
        if self._session is None or self._session.closed:
            ssl_ctx = None
            if self.tls is not None:
                import ssl as _ssl
                cert, key, ca = self.tls
                ssl_ctx = _ssl.create_default_context(cafile=ca)
                ssl_ctx.load_cert_chain(cert, key)
                ssl_ctx.check_hostname = False   # peers are dialed by IP;
                # the fleet CA signature is the authentication
                ssl_ctx.verify_mode = _ssl.CERT_REQUIRED
            self._session = aiohttp.ClientSession(
                connector=aiohttp.TCPConnector(limit=self.max_connections,
                                               ssl=ssl_ctx),
                timeout=aiohttp.ClientTimeout(total=self.timeout_s))
        return self._session

    async def close(self) -> None:
        if self._session is not None and not self._session.closed:
            await self._session.close()

    @staticmethod
    async def _read_body(resp, size: int, what: str,
                         on_first=None, relay_open=None,
                         meta: dict | None = None) -> bytearray:
        """Stream the body into ONE pooled buffer. Replaces
        ``resp.read()``: no chunk-list join copy, and — unlike the PR 3/4
        shape — NO digest folding here: hashing a 4-16 MiB piece on the
        loop thread was the per-byte CPU that set the fan-out ceiling on
        core-bound hosts; verification now rides the storage write pass
        off-loop. Only the per-chunk memcpy stays on the loop. The buffer
        comes from the process buffer pool; ownership passes to the
        caller (released back to the pool after landing), and is returned
        to the pool here on every failure path. ``on_first`` fires once
        when the first body chunk lands (flight-recorder ttfb).
        ``relay_open(buf)`` (daemon/relay.py) registers the buffer as an
        in-flight relay span once acquired; the per-chunk watermark
        advance is one attribute store, and a failed read retires the
        span HERE, before the buffer returns to the pool — a relay
        reader must never copy from recycled memory. ``meta`` (the dict
        that rides ``download_span``) gets ``chunks``, the body chunks
        read, and ``copy_s``, the seconds this function itself ran on the
        loop between them (the slice copy and the watermark store, not
        the awaits): the flight journal's ``wire_copy``."""
        if faultgate.ARMED:
            # inside the request's timeout window: a 'hang' script parks
            # here until the per-piece deadline cancels the read, exactly
            # like a parent that wedged mid-transfer; 'corrupt' flips a
            # byte BEFORE landing so digest verification trips downstream
            await faultgate.fire("piece.wire", key=what)
        buf = POOL.acquire(size)
        span = relay_open(buf) if relay_open is not None else None
        try:
            mv = memoryview(buf)
            try:
                off = chunks = 0
                copy_s = 0.0
                async for chunk in resp.content.iter_any():
                    t_chunk = time.perf_counter()
                    if off == 0 and faultgate.ARMED:
                        chunk = faultgate.corrupt("piece.wire", chunk,
                                                  key=what)
                    if off == 0 and on_first is not None:
                        on_first()
                        on_first = None
                    n = len(chunk)
                    if off + n > size:
                        raise _classified(
                            Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                            f"{what}: long read {off + n} > {size}",
                            "stall")
                    mv[off:off + n] = chunk
                    off += n
                    if span is not None:
                        span.advance(off)
                    chunks += 1
                    copy_s += time.perf_counter() - t_chunk
                if meta is not None:
                    meta["chunks"] = chunks
                    meta["copy_s"] = copy_s
                if off != size:
                    raise _classified(Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                                      f"{what}: short read {off}/{size}",
                                      "stall")
            finally:
                # drop the export before any release() probes it
                mv.release()
        except BaseException:
            if span is not None:
                span.close()
            POOL.release(buf)
            raise
        return buf

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
        url = f"{self.scheme}://{dst_addr}/download/{task_id[:3]}/{task_id}"
        start, size = piece.range_start, piece.range_size
        headers = {"Range": f"bytes={start}-{start + size - 1}"}
        tp = tracing.traceparent()
        if tp:   # trace ctx rides the piece request (ref piece_downloader.go:227)
            headers["traceparent"] = tp
        params = {"peerId": src_peer_id}
        if qos_class:
            params["cls"] = qos_class
        what = f"parent {dst_addr} piece {piece.piece_num}"
        t0 = time.monotonic()

        async def fetch():
            async with self._get_session().get(
                    url, headers=headers, params=params) as resp:
                if resp.status == 503:
                    # upload-slot backpressure: the parent is at its
                    # concurrency limit, not broken — the dispatcher reroutes
                    # the piece to another holder or retries after the
                    # parent's measured-transfer-time hint
                    err = DFError(Code.CLIENT_PEER_BUSY,
                                  f"parent {dst_addr} busy")
                    try:
                        err.retry_after_ms = int(
                            resp.headers.get("X-Retry-After-Ms", "0"))
                    except ValueError:
                        err.retry_after_ms = 0
                    raise err
                if resp.status not in (200, 206):
                    raise _classified(
                        Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                        f"{what}: HTTP {resp.status}", "refused")
                if meta is not None:
                    # cut-through serve: the parent relayed these bytes
                    # mid-landing — a later corrupt verdict on them is
                    # attributed at reduced weight (see verdicts.record)
                    meta["relayed"] = \
                        resp.headers.get("X-DF-Relay") == "1"
                return await self._read_body(resp, size, what,
                                             on_first=on_first_byte,
                                             relay_open=relay_open,
                                             meta=meta)

        try:
            # hard per-piece deadline OUTSIDE aiohttp: the session's total
            # timeout only interrupts aiohttp's own awaits, so a parent (or
            # an injected piece.wire hang) that wedges BETWEEN body reads
            # would stall the worker forever without this
            data = await asyncio.wait_for(fetch(), self.timeout_s)
        except asyncio.TimeoutError:
            raise _classified(Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                              f"{what}: per-piece deadline "
                              f"({self.timeout_s:.0f}s)",
                              "timeout") from None
        except DFError:
            raise
        except Exception as exc:  # noqa: BLE001 - network boundary
            # connection-establishment failures never moved a byte
            # ("refused"); anything that died with a request in flight is
            # a mid-transfer stall
            refused = isinstance(exc, (ConnectionRefusedError,
                                       aiohttp.ClientConnectorError))
            raise _classified(Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                              f"{what}: {type(exc).__name__}: {exc}",
                              "refused" if refused else "stall") from None
        cost_ms = int((time.monotonic() - t0) * 1000)
        return data, cost_ms

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
        url = f"{self.scheme}://{dst_addr}/download/{task_id[:3]}/{task_id}"
        start = pieces[0].range_start
        size = sum(p.range_size for p in pieces)
        headers = {"Range": f"bytes={start}-{start + size - 1}"}
        tp = tracing.traceparent()
        if tp:
            headers["traceparent"] = tp
        params = {"peerId": src_peer_id}
        if qos_class:
            params["cls"] = qos_class
        what = f"parent {dst_addr} span @{start}+{size}"
        t0 = time.monotonic()

        async def fetch():
            async with self._get_session().get(
                    url, headers=headers, params=params) as resp:
                if resp.status == 503:
                    err = DFError(Code.CLIENT_PEER_BUSY,
                                  f"parent {dst_addr} busy")
                    try:
                        err.retry_after_ms = int(
                            resp.headers.get("X-Retry-After-Ms", "0"))
                    except ValueError:
                        err.retry_after_ms = 0
                    raise err
                if resp.status not in (200, 206):
                    raise _classified(
                        Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                        f"{what}: HTTP {resp.status}", "refused")
                if meta is not None:
                    # cut-through serve: the parent relayed these bytes
                    # mid-landing — a later corrupt verdict on them is
                    # attributed at reduced weight (see verdicts.record)
                    meta["relayed"] = \
                        resp.headers.get("X-DF-Relay") == "1"
                return await self._read_body(resp, size, what,
                                             on_first=on_first_byte,
                                             relay_open=relay_open,
                                             meta=meta)

        try:
            # same hard per-span deadline as download_piece (see there)
            data = await asyncio.wait_for(fetch(), self.timeout_s)
        except asyncio.TimeoutError:
            raise _classified(Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                              f"{what}: per-piece deadline "
                              f"({self.timeout_s:.0f}s)",
                              "timeout") from None
        except DFError:
            raise
        except Exception as exc:  # noqa: BLE001 - network boundary
            # connection-establishment failures never moved a byte
            # ("refused"); anything that died with a request in flight is
            # a mid-transfer stall
            refused = isinstance(exc, (ConnectionRefusedError,
                                       aiohttp.ClientConnectorError))
            raise _classified(Code.CLIENT_PIECE_DOWNLOAD_FAIL,
                              f"{what}: {type(exc).__name__}: {exc}",
                              "refused" if refused else "stall") from None
        cost_ms = int((time.monotonic() - t0) * 1000)
        return data, cost_ms
