"""dfget: download a URL through the P2P fabric.

Role parity: reference ``cmd/dfget`` + ``client/dfget/dfget.go`` —
``Download`` via the daemon's local socket, daemon spawn-on-demand, and the
direct-from-source fallback with digest check; recursive directory download
(BFS over the source lister).

Usage:
    python -m dragonfly2_tpu.tools.dfget URL -O /path/out [options]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import subprocess
import sys
import time

from ..common import digest as digestlib
from ..common.dfpath import DFPath
from ..common.errors import Code, DFError
from ..common.unit import format_bytes
from ..idl.messages import DownloadRequest, Empty, UrlMeta
from ..rpc.client import Channel, ServiceClient


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dfget", description="P2P-accelerated download")
    p.add_argument("url", help="source URL (http/https/file/gs/memory)")
    p.add_argument("-O", "--output", required=True, help="output path")
    p.add_argument("--digest", default="", help="expected digest algo:hex")
    p.add_argument("--tag", default="", help="task isolation tag")
    p.add_argument("--application", default="")
    p.add_argument("--priority", type=int, default=0, choices=range(7),
                   help="download priority LEVEL0 (highest) .. LEVEL6; "
                   "0 also means 'resolve via the application table'")
    p.add_argument("--tenant", default="",
                   help="tenant this download is accounted to "
                   "(quotas, per-tenant QoS attribution)")
    p.add_argument("--qos-class", default="", dest="qos_class",
                   choices=("", "critical", "standard", "bulk"),
                   help="QoS service class: critical (latency-sensitive "
                   "foreground), standard (default), bulk (background — "
                   "throttled/queued/shed first under brownout)")
    p.add_argument("--shards", default="",
                   help="sharded tasks: comma-joined manifest shard names "
                   "THIS host needs (requires --shard-manifest); only the "
                   "pieces covering them are pulled and the output file is "
                   "sparse outside them")
    p.add_argument("--shard-manifest", default="", dest="shard_manifest",
                   help="path to a shard-manifest JSON file ({\"shards\": "
                   "[{name, range_start, range_size, dtype?, shape?, "
                   "digest?, device?}, ...]}); per-shard ready timestamps are "
                   "printed as shards verify")
    p.add_argument("--header", action="append", default=[],
                   help="extra origin header K:V (repeatable)")
    p.add_argument("--filter", action="append", default=[],
                   help="query params excluded from the task id (repeatable)")
    p.add_argument("--range", dest="range_", default="", help="bytes=a-b sub-range")
    p.add_argument("--timeout", type=float, default=0.0)
    p.add_argument("--daemon-sock", default="", help="daemon unix socket path")
    p.add_argument("--no-daemon", action="store_true",
                   help="skip daemon; fetch straight from the source")
    p.add_argument("--spawn-daemon", action="store_true",
                   help="start a daemon if the socket is dead")
    p.add_argument("--recursive", "-r", action="store_true")
    p.add_argument("--quiet", "-q", action="store_true")
    return p


def _meta(args) -> UrlMeta:
    header = {}
    for h in args.header:
        k, _, v = h.partition(":")
        header[k.strip()] = v.strip()
    from ..idl.messages import Priority
    return UrlMeta(digest=args.digest, tag=args.tag, range=args.range_,
                   application=args.application, header=header or None,
                   filtered_query_params=args.filter or None,
                   priority=Priority(args.priority),
                   tenant=getattr(args, "tenant", ""),
                   qos_class=getattr(args, "qos_class", ""),
                   shards=getattr(args, "shards", ""))


def _load_shard_manifest(path: str):
    """Parse a shard-manifest JSON file into the wire ShardManifest.
    Accepts ``{"shards": [...]}`` or a bare list of shard objects."""
    if not path:
        return None
    import json

    from ..idl.messages import ShardInfo, ShardManifest

    # dflint: disable=DF001 — one KB-scale manifest read on dfget's CLI-private loop
    with open(path, encoding="utf-8") as f:
        raw = json.load(f)
    entries = raw.get("shards", raw) if isinstance(raw, dict) else raw
    shards = [ShardInfo(name=e["name"],
                        range_start=int(e["range_start"]),
                        range_size=int(e["range_size"]),
                        dtype=e.get("dtype", "uint8"),
                        shape=list(e["shape"]) if e.get("shape") else None,
                        digest=e.get("digest", ""),
                        device=int(e.get("device", -1)))
              for e in entries]
    return ShardManifest(shards=shards)


async def _daemon_alive(sock: str) -> bool:
    # dflint: disable=DF001 — one stat on dfget's CLI-private loop
    if not os.path.exists(sock):
        return False
    ch = Channel(f"unix:{sock}")
    try:
        health = ServiceClient(ch, "df.health.Health", max_attempts=1)
        await asyncio.wait_for(health.unary("Check", Empty()), 2.0)
        return True
    except Exception:  # noqa: BLE001
        return False
    finally:
        await ch.close()


def _spawn_daemon(sock: str) -> None:
    """Start a detached daemon process bound to ``sock``."""
    # dflint: disable=DF001 — detached daemon bootstrap from the CLI; spawn latency IS the UX here
    subprocess.Popen(
        [sys.executable, "-m", "dragonfly2_tpu.tools.daemon",
         "--unix-sock", sock],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True)


async def download_via_daemon(sock: str, args, *, progress=None) -> None:
    ch = Channel(f"unix:{sock}")
    t0 = time.monotonic()
    try:
        client = ServiceClient(ch, "df.daemon.Daemon")
        req = DownloadRequest(url=args.url, output=os.path.abspath(args.output),
                              url_meta=_meta(args), timeout_s=args.timeout,
                              recursive=args.recursive,
                              shard_manifest=_load_shard_manifest(
                                  getattr(args, "shard_manifest", "")))
        if args.recursive:
            # concurrent per-file events interleave on one stream with no
            # file identity on progress frames — a single-file percentage
            # renderer would garble them; report completed files instead
            files = 0
            total = 0
            async for resp in client.unary_stream("Download", req):
                if resp.done:
                    files += 1
                    total += resp.completed_length
                    if not args.quiet:
                        print(f"dfget: [{files}] {resp.output} "
                              f"({format_bytes(resp.completed_length)})")
            if not args.quiet:
                print(f"dfget: {files} files, {format_bytes(total)} total")
            return
        async for resp in client.unary_stream("Download", req):
            if resp.shard and not args.quiet:
                # per-shard ready timestamp: the shard's bytes all
                # verified (and its HBM handoff is enqueued when a device
                # sink rides the request) — the time-to-serving series
                print(f"\rdfget: shard {resp.shard} ready "
                      f"[{resp.shards_ready}/{resp.shards_total}] "
                      f"({resp.shard_src}) at "
                      f"{time.monotonic() - t0:.3f}s          ")
                continue
            if progress and not resp.done:
                progress(resp.completed_length, resp.content_length)
            if resp.done and progress:
                progress(resp.completed_length, resp.content_length, done=True)
    finally:
        await ch.close()


async def download_from_source(args, *, progress=None) -> None:
    """Direct origin fetch (no daemon): the reference's ``downloadFromSource``
    fallback, with digest verification. ``--recursive`` BFS-mirrors the
    listing client-side exactly like the reference's ``recursiveDownload``
    (``client/dfget/dfget.go:317``)."""
    from ..source import SourceRequest, client_for

    client = client_for(args.url)
    try:
        if getattr(args, "recursive", False):
            await _recursive_from_source(client, args, progress)
        else:
            req = SourceRequest(url=args.url, timeout_s=args.timeout)
            await _download_from_source_inner(client, req, args, progress)
    finally:
        close = getattr(client, "close", None)
        if close is not None:
            await close()


async def _recursive_from_source(client, args, progress) -> None:
    import copy

    from ..source import SourceRequest
    from ..source.client import walk

    meta = _meta(args)
    header = dict(meta.header) if meta.header else None
    async for e, rel in walk(args.url, timeout_s=args.timeout, header=header):
        sub = copy.copy(args)
        sub.url = e.url
        sub.output = os.path.join(args.output, rel)
        sub.digest = ""    # a whole-tree digest can't apply per file
        sub.range_ = ""
        await _download_from_source_inner(
            client, SourceRequest(url=e.url, header=dict(header or {}),
                                  timeout_s=args.timeout),
            sub, progress)


async def _download_from_source_inner(client, req, args, progress) -> None:
    from ..common.piece import parse_http_range
    from ..source import SourceRequest

    if args.range_:
        total = await client.content_length(SourceRequest(url=args.url))
        req.range = parse_http_range(args.range_, total)
    resp = await client.download(req)
    tmp = args.output + ".dfget.tmp"
    # dflint: disable=DF001 — daemon-less fallback on dfget's CLI-private loop; blocking it slows only this invocation
    os.makedirs(os.path.dirname(os.path.abspath(tmp)) or ".", exist_ok=True)
    hasher = None
    algo = want = ""
    if args.digest:
        algo, want = digestlib.parse(args.digest)
        hasher = digestlib.Hasher(algo)
    done = 0
    # dflint: disable=DF001 — CLI-private loop, see above
    with open(tmp, "wb") as f:
        assert resp.chunks is not None
        async for chunk in resp.chunks:
            # dflint: disable=DF001 — CLI-private loop, see above
            f.write(chunk)
            done += len(chunk)
            if hasher is not None:
                hasher.update(chunk)
            if progress:
                progress(done, resp.content_length)
    if hasher is not None:
        got = hasher.hexdigest()
        if got != want:
            # dflint: disable=DF001 — CLI-private loop, see above
            os.unlink(tmp)
            raise DFError(Code.CLIENT_DIGEST_MISMATCH,
                          f"digest mismatch from source: {algo}:{got[:12]}..")
    # dflint: disable=DF001 — CLI-private loop, see above
    os.replace(tmp, args.output)
    if progress:
        progress(done, done, done=True)


async def run(args) -> int:
    t0 = time.monotonic()
    last: dict = {"len": 0}

    def progress(completed: int, total: int, done: bool = False) -> None:
        if args.quiet:
            return
        last["len"] = completed
        if done:
            dt = time.monotonic() - t0
            rate = completed / dt if dt > 0 else 0
            print(f"\rdfget: {format_bytes(completed)} in {dt:.2f}s "
                  f"({format_bytes(rate)}/s)          ")
        else:
            pct = f"{100 * completed / total:5.1f}%" if total > 0 else "   ?  "
            print(f"\rdfget: {pct} {format_bytes(completed)}", end="", flush=True)

    if args.no_daemon:
        await download_from_source(args, progress=progress)
        return 0
    sock = args.daemon_sock or DFPath().daemon_sock()
    if not await _daemon_alive(sock):
        if args.spawn_daemon:
            _spawn_daemon(sock)
            for _ in range(50):
                await asyncio.sleep(0.2)
                if await _daemon_alive(sock):
                    break
            else:
                print("dfget: daemon did not come up; falling back to source",
                      file=sys.stderr)
                await download_from_source(args, progress=progress)
                return 0
        else:
            await download_from_source(args, progress=progress)
            return 0
    await download_via_daemon(sock, args, progress=progress)
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.shards and not args.shard_manifest:
        # without the manifest the daemon cannot map names to byte
        # ranges — silently downloading the whole checkpoint would be
        # exactly what the flag exists to avoid
        parser.error("--shards requires --shard-manifest (the daemon "
                     "needs the shard table to subset the download)")
    try:
        return asyncio.run(run(args))
    except DFError as exc:
        print(f"dfget: error: {exc.code.name}: {exc.message}", file=sys.stderr)
        return 1
    except KeyboardInterrupt:
        return 130


if __name__ == "__main__":
    sys.exit(main())
