"""Driver ``shard_stream``: one epoch of dataset shards beside a job.

The window drives ``source.stream`` (``ShardPrefetcher``: ``depth`` shards
in flight, one whole-buffer ingest each, pieces deleted once the arrays are
handed over) from the smoke's synchronous consumer, one jitted
position-weighted reduction per array, each waited for. Beside it one
thread runs the job's step back to back. The window ends at ``seconds`` or
at the epoch's end, whichever is first: no shard is asked for after
``seconds``; the one being waited for arrives and counts.

The job is the benchmark's own and synthetic, and says so: a two-matrix bf16
MLP (``hidden`` x ``hidden`` twice, batch ``batch``), forward, backward and
an SGD update in one jitted program named ``bench_job_step``: five matrix
products of 2 * batch * hidden**2 operations each. Alone it keeps the chip
busy; its rate alone is taken in set-up and printed.
"""

from __future__ import annotations

import functools
import time

from benchmarks.harness import WindowResult, job_thread, say
from benchmarks.sources import MiB, Request

REFERENCE_THREADS = 8


def _weigh():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def weigh(x, offset):
        """Position-weighted byte sum, mod 2**32."""
        i = jnp.arange(x.shape[0], dtype=jnp.uint32) + offset
        return jnp.sum(x.astype(jnp.uint32) * (i % 65521 + 1),
                       dtype=jnp.uint32)

    return weigh


_WEIGHTS = None


def weigh_np(x, offset: int) -> int:
    """The same sum in numpy, MiB at a time so that the temporaries stay in
    cache. The weights ``(i % 65521) + 1`` come as slices of one table, so
    no modulo runs per byte."""
    import numpy as np

    global _WEIGHTS
    if _WEIGHTS is None:
        _WEIGHTS = (np.arange(65521 + MiB, dtype=np.uint32) % 65521
                    + 1).astype(np.uint32)
    assert offset + x.shape[0] <= 1 << 32, "positions are 32 bits wide"
    total = 0
    for lo in range(0, x.shape[0], MiB):
        part = x[lo:lo + MiB]
        start = (offset + lo) % 65521
        w = _WEIGHTS[start:start + part.shape[0]]
        total += int((w * part).sum(dtype=np.uint32))
    return total % (1 << 32)


def make_job(seed: int, hidden: int, batch: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def init(key):
        k = jax.random.split(key, 4)
        scale = hidden ** -0.5
        params = tuple((jax.random.normal(k[i], (hidden, hidden)) * scale)
                       .astype(jnp.bfloat16) for i in range(2))
        data = tuple(jax.random.normal(k[2 + i], (batch, hidden))
                     .astype(jnp.bfloat16) for i in range(2))
        return params, data

    @functools.partial(jax.jit, donate_argnums=0)
    def bench_job_step(params, data):
        with jax.named_scope("bench_job_step"):
            x, y = data

            def loss(p):
                out = jax.nn.relu(x @ p[0]) @ p[1]
                return jnp.mean((out - y).astype(jnp.float32) ** 2)

            grads = jax.grad(loss)(params)
            return tuple((p - 1e-3 * g).astype(p.dtype)
                         for p, g in zip(params, grads))

    params, data = init(jax.random.PRNGKey(seed % (1 << 31)))
    return (lambda p: bench_job_step(p, data)), params


def _consume(ctx, shard: list, f: dict, keep: bool) -> dict:
    """The synchronous consumer: every array of the shard through the
    jitted reduction, each result waited for."""
    weigh = ctx.state["weigh"]
    import numpy as np
    offset, sums, lens = 0, [], []
    for arr in shard:
        sums.append(int(weigh(arr, np.uint32(offset % (1 << 32)))))
        lens.append(arr.shape[0])
        offset += arr.shape[0]
    return {"file": f, "sums": sums, "lens": lens,
            "arrays": list(shard) if keep else None}


def origin_extras(cell, files: list[dict]) -> list[dict]:
    """What the origin serves besides the epoch: the set-up shard."""
    return [{"name": "warmup-shard.bin", "size": files[0]["size"],
             "shards": None}]


def prepare(ctx) -> None:
    import jax
    import numpy as np

    t = ctx.cell.traffic
    ctx.state["weigh"] = _weigh()

    # the job alone: compiles its step and gives the rate the fabric is
    # held against
    job = t["job"]
    step, params = make_job(ctx.seed, job["hidden"], job["batch"])
    params = jax.block_until_ready(step(params))
    with job_thread(step, params) as alone:
        time.sleep(job["alone_seconds"])
    ctx.state["job"] = (step, alone.state)
    steps = alone.steps
    if steps:
        rate = len(steps) / (steps[-1][1] - steps[0][0])
        flop = 10 * job["batch"] * job["hidden"] ** 2
        say(f"job alone: {len(steps)} steps in {job['alone_seconds']}s = "
            f"{rate:.2f} steps/s ({flop / 1e12:.2f} TFLOP a step: "
            f"{rate * flop / 1e12:.1f} TFLOP/s)")

    # one shard more than the epoch has, pulled in set-up: every shape of
    # the window is warm and the epoch itself stays cold
    (warm,) = origin_extras(ctx.cell, ctx.files)
    t0 = time.monotonic()
    (shard,) = list(ctx.source.stream([warm], depth=1, delete_after=True))
    _consume(ctx, shard, warm, False)
    say(f"set-up shard: {warm['size'] / MiB:.0f} MiB as {len(shard)} "
        f"arrays in {time.monotonic() - t0:.1f}s")
    host = [np.asarray(a) for a in shard]
    t0 = time.monotonic()
    put = [jax.device_put(h, a.devices().pop()) for h, a in zip(host, shard)]
    jax.block_until_ready(put)
    dt = time.monotonic() - t0
    say(f"yardstick: plain jax.device_put of the same {len(host)} arrays "
        f"in {dt:.3f}s = {warm['size'] / dt / 1e9:.2f} GB/s")


def window(ctx, seconds: float) -> WindowResult:
    t = ctx.cell.traffic
    files = ctx.files
    sample = set(int(i) for i in ctx.rng(7).choice(
        len(files), min(t.get("sample_shards", 3), len(files)),
        replace=False)) | {0}
    step, params = ctx.state.pop("job")
    requests: list[Request] = []
    kept: list[dict] = []
    last = None
    bytes_ready = 0
    with job_thread(step, params) as job:
        t0 = t1 = time.monotonic()
        deadline = t0 + seconds
        it = ctx.source.stream(files, depth=t["depth"],
                               delete_after=t["delete_after"])
        try:
            for i, f in enumerate(files):
                if requests and time.monotonic() >= deadline:
                    break
                r = Request(f["name"], f["size"], time.monotonic())
                requests.append(r)
                with ctx.span("consumer waits for a shard"):
                    try:
                        shard = next(it)
                    except Exception as exc:  # noqa: BLE001 - a failed request
                        r.error = f"{type(exc).__name__}: {exc}"
                        say(f"request for {f['name']} failed: {exc!r}")
                        break
                r.t_ready = t1 = time.monotonic()
                r.ok = True
                bytes_ready += f["size"]
                with ctx.span("consumer: jitted reduction per array"):
                    last = _consume(ctx, shard, f, True)
                kept.append(last if i in sample
                            else {**last, "arrays": None})
                del shard
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()                      # cancels the shards in flight
    if last is not None:
        kept[-1] = last                      # the last delivered, always
    for r in requests:
        r.flight = ctx.source.flight_of(
            next(f for f in files if f["name"] == r.name))
    ctx.state["kept"] = kept
    return WindowResult(t0, t1, requests, bytes_ready,
                        job_steps=job.steps)


def _reference(ctx, k: dict):
    """A delivered shard as the origin's file has it: the bytes, padded
    with zeros to the delivered length, and numpy's reduction of each
    array's span of them."""
    import numpy as np

    ref = ctx.bytes_of(k["file"])
    total = sum(k["lens"])
    want = np.zeros(total, np.uint8)
    want[:min(total, ref.shape[0])] = ref[:total]
    sums, offset = [], 0
    for n in k["lens"]:
        sums.append(weigh_np(want[offset:offset + n], offset))
        offset += n
    return want, sums


def compare(ctx, result: WindowResult, obs) -> dict:
    import concurrent.futures

    import numpy as np

    kept = ctx.state.pop("kept")
    short = sums_off = bytes_off = sampled = 0
    for k in kept:
        total = sum(k["lens"])
        # the sink pads the tail to its units' alignment, never by a unit
        if total < k["file"]["size"] \
                or total - k["file"]["size"] >= max(k["lens"]):
            short += 1
    # every shard of the window: the consumer's reductions, kept from the
    # window, against numpy's over the origin's file (numpy drops the GIL:
    # a few threads, now that the window has closed and the host is free)
    with concurrent.futures.ThreadPoolExecutor(REFERENCE_THREADS) as pool:
        for k, (want, sums) in zip(kept, pool.map(
                lambda k: _reference(ctx, k), kept)):
            sums_off += sum(1 for got, w in zip(k["sums"], sums) if got != w)
            if k["arrays"] is None:
                continue
            # the sampled shards: read back and compared byte for byte
            sampled += 1
            offset = 0
            for arr in k["arrays"]:
                n = arr.shape[0]
                bytes_off += int(np.count_nonzero(
                    np.asarray(arr) != want[offset:offset + n]))
                offset += n
    say(f"compared {len(kept)} shards of the window by length and by the "
        f"consumer's reduction of every array against numpy's over the "
        f"origin's file; {sampled} sampled shards read back and compared "
        "byte for byte")
    return {"shards_short_or_long": (short, 0),
            "reductions_differing": (sums_off, 0),
            "sample_bytes_differing": (bytes_off, 0)}
