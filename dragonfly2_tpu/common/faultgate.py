"""Deterministic fault-injection plane: named sites, scripted faults.

Role parity: none in the reference — Dragonfly2 tests its failure ladders
with ad-hoc mocks per suite. At pod scale the retry/failover behaviour IS
the product (a single stalled input shard stalls the whole training step),
so this repo gives every layer a named injection site that tests and the
stress tool can arm with deterministic scripts:

    site            fired from
    --------------  ----------------------------------------------------
    rpc.unary       rpc/client.py ServiceClient.unary (before the stub)
    rpc.stream.read rpc/client.py stream read halves
    piece.wire      daemon/piece_downloader.py body read (inside the
                    request's timeout window, so 'hang' trips the
                    per-piece deadline exactly like a wedged parent)
    source.fetch    source/client.py module-level download()
    hbm.ingest      tpu/hbm_sink.py DeviceIngest.commit (sync path)
    sched.register  daemon/scheduler_session.py register, keyed by the
                    scheduler address under attempt
    pex.gossip      daemon/pex.py gossip round, keyed by the target peer
                    address ('corrupt' flips an envelope byte so the
                    receiver's digest verify rejects it)
    relay.stall     daemon/upload_server.py streaming relay wait, keyed
                    by the task id: a parent whose landing watermark
                    stops advancing mid-relay ('hang' parks the serve so
                    the child's piece deadline fires and the piece is
                    re-pulled from another holder)
    upload.serve    daemon/upload_server.py piece-serve path, keyed by
                    "<host_id>|<task_id>": a byzantine daemon —
                    'corrupt' flips a byte in the served range so every
                    child's landing verification rejects it (the swarm
                    immune system's chaos lever; arm with pct= to poison
                    a deterministic fraction of serves,
                    ``stress.py --byzantine``)
    sched.snapshot.io
                    scheduler/statestore.py persist path, keyed by the
                    snapshot reason: torn ('corrupt' flips a byte of the
                    serialized blob so load refuses it wholesale), ENOSPC
                    ('error'/'fail' raise mid-persist), or a wedged disk
                    ('delay'; 'hang' degrades to fail — the writer is
                    sync). The store swallows every one of them: a failed
                    snapshot is counted, never raised into a ruling path

Script syntax (one clause per site, ';'-separated)::

    site[@keysub]=kind[:arg]...
    kind := fail | error | delay | hang | corrupt
    arg  := n=<count|-1>        fire count, -1 = forever   (default 1)
            code=<Code name|int>  DFError code raised      (default UNAVAILABLE)
            after_ms=<ms>       retry_after_ms hint on the raised error
            delay_s=<seconds>   sleep length for kind=delay
            pct=<1-100>         fire on this percentage of matching
                                attempts (deterministic striding, not
                                random — attempt k fires iff
                                floor(k*pct/100) > floor((k-1)*pct/100))
            <float>             positional shorthand for delay_s
            <int>               positional shorthand for n

Examples::

    sched.register@127.0.0.1:9000=fail:n=-1      # that scheduler is dead
    source.fetch=error:code=SOURCE_ERROR:after_ms=400   # origin 503 once
    piece.wire=hang:n=1                          # parent wedges mid-piece
    piece.wire=corrupt:n=1                       # digest-mismatch once
    rpc.unary=fail:n=2                           # fail twice, then succeed

Overhead contract: every call site guards with ``if faultgate.ARMED:`` —
one module-attribute load and a falsy test when disarmed; the module is
never entered on the hot path of a production process.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time

from .errors import Code, DFError
from .metrics import REGISTRY

log = logging.getLogger("df.faultgate")

# The site registry. Arming an unknown site is an error, and the tier-1
# lint (tests/test_faults.py) asserts every name here is both fired
# somewhere in the tree and documented in docs/RESILIENCE.md.
SITES = frozenset({
    "rpc.unary",
    "rpc.stream.read",
    "piece.wire",
    "source.fetch",
    "hbm.ingest",
    "sched.register",
    "pex.gossip",
    "relay.stall",
    "upload.serve",
    "sched.snapshot.io",
})

KINDS = frozenset({"fail", "error", "delay", "hang", "corrupt"})

# fast-path flag: True iff at least one script is armed
ARMED = False

_injected = REGISTRY.counter("df_fault_injected_total",
                             "faults injected by the faultgate plane",
                             ("site", "kind"))


class FaultScript:
    """One armed fault at one site, optionally key-scoped."""

    __slots__ = ("site", "kind", "key", "n", "code", "after_ms", "delay_s",
                 "pct", "attempts", "fired")

    def __init__(self, site: str, kind: str, *, key: str = "", n: int = 1,
                 code: Code = Code.UNAVAILABLE, after_ms: int = 0,
                 delay_s: float = 0.5, pct: int = 100):
        if site not in SITES:
            raise ValueError(f"unknown faultgate site {site!r} "
                             f"(known: {sorted(SITES)})")
        if kind not in KINDS:
            raise ValueError(f"unknown fault kind {kind!r} "
                             f"(known: {sorted(KINDS)})")
        if not 1 <= int(pct) <= 100:
            raise ValueError(f"pct must be 1-100, got {pct!r}")
        self.site = site
        self.kind = kind
        self.key = key
        self.n = n              # remaining fires; -1 = forever
        self.code = Code(code)
        self.after_ms = int(after_ms)
        self.delay_s = float(delay_s)
        self.pct = int(pct)     # fire on this % of matching attempts
        self.attempts = 0       # matching attempts seen (pct striding)
        self.fired = 0

    def matches(self, key: str) -> bool:
        return self.n != 0 and (not self.key or self.key in key)

    def due(self) -> bool:
        """Advance the deterministic pct stride: attempt k fires iff the
        integer floor of k*pct/100 advanced — pct=100 fires every
        attempt (the pre-pct behavior), pct=25 every 4th, with no rng
        (chaos runs must replay)."""
        self.attempts += 1
        if self.pct >= 100:
            return True
        return (self.attempts * self.pct) // 100 \
            > ((self.attempts - 1) * self.pct) // 100

    def consume(self) -> None:
        self.fired += 1
        if self.n > 0:
            self.n -= 1

    def describe(self) -> dict:
        return {"site": self.site, "kind": self.kind, "key": self.key,
                "remaining": self.n, "fired": self.fired,
                "attempts": self.attempts, "pct": self.pct,
                "code": self.code.name, "after_ms": self.after_ms,
                "delay_s": self.delay_s}


_scripts: list[FaultScript] = []
_lock = threading.Lock()   # hbm.ingest fires from the sink's caller thread


def _recompute_armed() -> None:
    global ARMED
    ARMED = any(s.n != 0 for s in _scripts)


def arm(site: str, kind: str, **kwargs) -> FaultScript:
    """Arm one scripted fault; returns the script (live counters)."""
    script = FaultScript(site, kind, **kwargs)
    with _lock:
        _scripts.append(script)
        _recompute_armed()
    log.info("faultgate armed: %s", script.describe())
    return script


def arm_script(text: str) -> list[FaultScript]:
    """Arm from the textual syntax (see module docstring)."""
    armed = []
    for clause in text.split(";"):
        clause = clause.strip()
        if not clause:
            continue
        head, _, spec = clause.partition("=")
        if not spec:
            raise ValueError(f"bad faultgate clause {clause!r} "
                             "(want site[@key]=kind[:arg]...)")
        site, _, key = head.partition("@")
        parts = spec.split(":")
        kind = parts[0].strip()
        kwargs: dict = {"key": key.strip()}
        for arg in parts[1:]:
            arg = arg.strip()
            if not arg:
                continue
            name, eq, value = arg.partition("=")
            if not eq:
                # positional: float -> delay_s, int -> n
                if "." in name:
                    kwargs["delay_s"] = float(name)
                else:
                    kwargs["n"] = int(name)
                continue
            if name == "n":
                kwargs["n"] = int(value)
            elif name == "code":
                kwargs["code"] = (Code[value] if not value.lstrip("-").isdigit()
                                  else Code(int(value)))
            elif name == "after_ms":
                kwargs["after_ms"] = int(value)
            elif name == "delay_s":
                kwargs["delay_s"] = float(value)
            elif name == "pct":
                kwargs["pct"] = int(value)
            else:
                raise ValueError(f"unknown faultgate arg {name!r} in {clause!r}")
        armed.append(arm(site.strip(), kind, **kwargs))
    return armed


def reset() -> None:
    """Disarm everything (tests call this in teardown)."""
    with _lock:
        _scripts.clear()
        _recompute_armed()


def status() -> dict:
    with _lock:
        return {"armed": ARMED, "scripts": [s.describe() for s in _scripts]}


def _claim(site: str, key: str, *, kinds: frozenset | None = None
           ) -> FaultScript | None:
    """Find-and-consume the first matching armed script. A matching
    script whose pct stride says "not this attempt" counts the attempt
    and yields no fire (later scripts still get a chance)."""
    with _lock:
        for s in _scripts:
            if s.site == site and s.matches(key) and (
                    kinds is None or s.kind in kinds):
                if not s.due():
                    continue
                s.consume()
                _recompute_armed()
                return s
    return None


def peek(site: str, key: str = "", *, kinds: frozenset | None = None) -> bool:
    """True when an armed script WOULD match (site, key) — without
    consuming a fire or advancing the pct stride. Call sites whose fast
    path bypasses Python (the upload server's sendfile branch) use this
    to route through the corruptible path only while a script is armed."""
    with _lock:
        return any(s.site == site and s.matches(key)
                   and (kinds is None or s.kind in kinds)
                   for s in _scripts)


_RAISING = frozenset({"fail", "error"})
_ASYNC_KINDS = frozenset({"fail", "error", "delay", "hang"})


def _raise(script: FaultScript) -> None:
    err = DFError(script.code,
                  f"faultgate[{script.site}]: injected {script.kind}")
    if script.after_ms:
        err.retry_after_ms = script.after_ms
    raise err


async def fire(site: str, key: str = "") -> None:
    """Fire at an async site. fail/error raise a DFError (error carries a
    retry_after_ms hint), delay sleeps, hang parks until the caller's own
    deadline cancels it. 'corrupt' scripts are not consumed here — they
    belong to maybe_corrupt()."""
    script = _claim(site, key, kinds=_ASYNC_KINDS)
    if script is None:
        return
    _injected.labels(site, script.kind).inc()
    log.info("faultgate fired: %s key=%r", script.describe(), key)
    if script.kind in _RAISING:
        _raise(script)
    elif script.kind == "delay":
        await asyncio.sleep(script.delay_s)
    elif script.kind == "hang":
        await asyncio.sleep(3600.0)   # parked; the site's deadline cancels us


def fire_sync(site: str, key: str = "") -> None:
    """Sync-path variant (hbm.ingest): fail/error raise; delay blocks the
    calling thread; hang is treated as fail (a sync site cannot park
    cancellably)."""
    script = _claim(site, key, kinds=_ASYNC_KINDS)
    if script is None:
        return
    _injected.labels(site, script.kind).inc()
    log.info("faultgate fired (sync): %s key=%r", script.describe(), key)
    if script.kind == "delay":
        time.sleep(script.delay_s)
        return
    _raise(script)


def corrupt(site: str, data: bytes, key: str = "") -> bytes:
    """Consume one 'corrupt' script if armed for (site, key): flips a byte
    so digest verification downstream fails deterministically. Returns the
    (possibly corrupted) bytes."""
    script = _claim(site, key, kinds=frozenset({"corrupt"}))
    if script is None:
        return data
    _injected.labels(site, script.kind).inc()
    log.info("faultgate corrupting %d bytes at %s key=%r", len(data), site,
             key)
    if not data:
        return data
    buf = bytearray(data)
    buf[0] ^= 0xFF
    return bytes(buf)


def add_fault_routes(router) -> None:
    """Debug control surface (mounted on the daemon upload server when
    ``upload.debug_endpoints`` is on — arming faults mutates live behaviour
    so it stays off the always-on surface):

        GET    /debug/faults   -> {"armed": bool, "scripts": [...]}
        POST   /debug/faults   -> body is a script string; arms it
        DELETE /debug/faults   -> reset()
    """
    import json

    from aiohttp import web

    async def get_faults(_r: web.Request) -> web.Response:
        return web.json_response(status())

    async def post_faults(request: web.Request) -> web.Response:
        text = (await request.text()).strip()
        try:
            armed = arm_script(text)
        except (ValueError, KeyError) as exc:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": str(exc)}),
                content_type="application/json")
        return web.json_response({"armed": [s.describe() for s in armed]})

    async def delete_faults(_r: web.Request) -> web.Response:
        reset()
        return web.json_response(status())

    router.add_get("/debug/faults", get_faults)
    router.add_post("/debug/faults", post_faults)
    router.add_delete("/debug/faults", delete_faults)
