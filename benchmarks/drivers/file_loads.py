"""Driver ``file_loads``: whole loads of a checkpoint, file after file.

The window drives ``source.pull`` (``Daemon.ptm.start_file_task`` with a
device sink and the file's manifest), one file in flight, closed loop. When
a whole load is held on the device the arrays are dropped, the holder's
pieces are deleted (``ptm.delete_task``) and the next load starts, under
URLs of its own: the origin serves the same bytes under ``load-<k>/``, a
task of their own each, and the swarm holds ``urls_per_file`` such sets
before the window. No new file is issued after ``seconds``; the file in
flight finishes and counts; the window also ends when the URLs run out.
(Asking again for a task the holder has deleted is not an option today: the
holder is offered itself as the parent, or is never offered one; PERF.md,
Open questions.)

The consumer is the benchmark's own and says so: one jitted word-sum per
array, dispatched when the file is ready and not waited for. It is what a
job does first with a weight (read it), it puts one device operation per
tensor into the trace, and it lets the comparison cover every array of
every load: a load is dropped before the window closes, its sums are not.

Mix parameters (traffic/<mix>.json): ``holder_of_content`` (``peer``: a
plain daemon of the swarm pulls every file from the origin in set-up and
holds it before the window), ``urls_per_file``, ``disable_back_source``,
``sample_arrays_per_file``.
"""

from __future__ import annotations

import time

from benchmarks.harness import WindowResult, say
from benchmarks.sources import MiB, Request


def _word_sum():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def word_sum(a):
        """Sum of the array's words as unsigned integers, mod 2**32: any
        one altered word alters it."""
        words = jax.lax.bitcast_convert_type(
            a, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize])
        return jnp.sum(words.astype(jnp.uint32), dtype=jnp.uint32)

    return word_sum


def _consume(ctx, f: dict, arrays: dict, load: int, index: int) -> dict:
    """What is kept of a ready file once its load is dropped."""
    word_sum = ctx.state["word_sum"]
    names = list(arrays)
    rng = ctx.rng(load, index)
    k = min(ctx.cell.traffic.get("sample_arrays_per_file", 2), len(names))
    sampled = [names[i] for i in rng.choice(len(names), k, replace=False)]
    if load == 0 and index == ctx.state["longest"][0]:
        sampled.append(ctx.state["longest"][1])   # the longest tensor, once
    return {"file": f,
            "meta": [(n, str(a.dtype), list(a.shape))
                     for n, a in arrays.items()],
            "sums": [word_sum(a) for a in arrays.values()],
            "sample": {n: arrays[n] for n in dict.fromkeys(sampled)}}


def _loads(ctx) -> list[list[dict]]:
    """The files of each load the window may make, each load under URLs of
    its own."""
    return [[{**f, "url_path": f"load-{k}/{f['name']}"} for f in ctx.files]
            for k in range(ctx.cell.traffic["urls_per_file"])]


def prepare(ctx) -> None:
    import jax
    import numpy as np

    ctx.state["word_sum"] = _word_sum()
    sizes = [(s["range_size"], i, s["name"]) for i, f in enumerate(ctx.files)
             for s in f["shards"]]
    _size, fi, name = max(sizes)
    ctx.state["longest"] = (fi, name)

    # the swarm's holder of content pulls every file from the origin
    t0 = time.monotonic()
    loads = _loads(ctx)
    for load in loads:
        ctx.source.preseed(load)
    total = sum(f["size"] for f in ctx.files)
    say(f"pre-seeded: {total / MiB:.0f} MiB in {len(ctx.files)} files, "
        f"under {len(loads)} URLs each, from the origin into the swarm, in "
        f"{time.monotonic() - t0:.1f}s")

    # the yardstick for hbm_transfer_GB_per_s: a plain device_put of each
    # file's bytes in the same shapes, once. A rate, never a share. The
    # same arrays warm the consumer's program for every shape of the window
    for f in ctx.files:
        data = ctx.bytes_of(f)
        views = [data[s["range_start"]:s["range_start"] + s["range_size"]]
                 .view(np.dtype(s["dtype"])).reshape(s["shape"])
                 for s in f["shards"]]
        t0 = time.monotonic()
        put = [jax.device_put(v, ctx.devices[i % len(ctx.devices)])
               for i, v in enumerate(views)]
        jax.block_until_ready(put)
        dt = time.monotonic() - t0
        say(f"yardstick: plain jax.device_put of {f['name']} "
            f"({len(views)} arrays, {f['size'] / MiB:.0f} MiB) in {dt:.3f}s "
            f"= {f['size'] / dt / 1e9:.2f} GB/s")
        jax.block_until_ready([ctx.state["word_sum"](a) for a in put])
        del put, views, data


def window(ctx, seconds: float) -> WindowResult:
    t = ctx.cell.traffic
    requests: list[Request] = []
    kept: list[dict] = []
    bytes_ready = 0
    t0 = t1 = time.monotonic()
    deadline = t0 + seconds
    stop = False
    for load, files in enumerate(_loads(ctx)):
        if stop:
            break
        held = []
        for i, f in enumerate(files):
            if requests and time.monotonic() >= deadline:
                stop = True
                break
            with ctx.span("wait for a file to be ready"):
                try:
                    arrays, r = ctx.source.pull(
                        f, disable_back_source=t["disable_back_source"])
                except Exception as exc:  # noqa: BLE001 - a failed request
                    requests.append(Request(
                        f["name"], f["size"], time.monotonic(),
                        error=f"{type(exc).__name__}: {exc}"))
                    say(f"request for {f['name']} failed: {exc!r}")
                    stop = True
                    break
            requests.append(r)
            bytes_ready += f["size"]
            t1 = r.t_ready
            with ctx.span("consumer: word sums"):
                kept.append(_consume(ctx, f, arrays, load, i))
            held.append(arrays)
        with ctx.span("drop the load, its pieces leave the holder"):
            ctx.source.delete(files[:len(held)])
            held.clear()
    ctx.state["kept"] = kept
    return WindowResult(t0, t1, requests, bytes_ready)


def _reference_sums(ctx) -> dict[str, list[int]]:
    """Per file, each tensor's word sum from the origin's bytes, read where
    they were made, independent of every store in between, placed on the
    device by a plain ``jax.device_put`` and reduced by the same jitted
    program. On the device and not in numpy, because a TPU does not carry a
    bf16 NaN's payload or a denormal through any computation, a bitcast
    included (measured, PR 25: 0.77% of random words read back changed),
    so only two sums made the same way can be held to be equal. The bytes
    themselves are compared on the host, for the sampled arrays."""
    import jax
    import numpy as np

    word_sum = ctx.state["word_sum"]
    out = {}
    for f in ctx.files:
        data = ctx.bytes_of(f)
        put = [jax.device_put(
            data[s["range_start"]:s["range_start"] + s["range_size"]]
            .view(np.dtype(s["dtype"])).reshape(s["shape"]),
            ctx.devices[i % len(ctx.devices)])
            for i, s in enumerate(f["shards"])]
        out[f["name"]] = [int(v) for v in
                          jax.device_get([word_sum(a) for a in put])]
        del put, data
    return out


def compare(ctx, result: WindowResult, obs) -> dict:
    import jax
    import numpy as np

    kept = ctx.state.pop("kept")
    want_sums = _reference_sums(ctx)
    missing = sums_off = bytes_off = sampled = 0
    for k in kept:
        f = k["file"]
        want_meta = [(s["name"], s["dtype"], s["shape"]) for s in f["shards"]]
        missing += sum(1 for w in want_meta if w not in k["meta"]) \
            + max(0, len(k["meta"]) - len(want_meta))
        got = [int(v) for v in jax.device_get(k["sums"])]
        by_name = dict(zip((m[0] for m in k["meta"]), got))
        sums_off += sum(1 for s, w in zip(f["shards"], want_sums[f["name"]])
                        if by_name.get(s["name"]) != w)
        ref = ctx.bytes_of(f)
        spec = {s["name"]: s for s in f["shards"]}
        for name, arr in k["sample"].items():
            s = spec[name]
            # raw bytes, not values: random bf16 patterns include NaNs
            got_b = np.asarray(arr).view(np.uint8).reshape(-1)
            want_b = ref[s["range_start"]:s["range_start"] + s["range_size"]]
            bytes_off += (int(np.count_nonzero(got_b != want_b))
                          if got_b.shape == want_b.shape else s["range_size"])
            sampled += 1
    n_arrays = sum(len(k["meta"]) for k in kept)
    say(f"compared {len(kept)} files of the window: {n_arrays} arrays by "
        f"name, dtype, shape and word sum against the origin's files; "
        f"{sampled} sampled arrays read back and compared byte for byte")
    out = {"arrays_missing": (missing, 0),
           "word_sums_differing": (sums_off, 0),
           "sample_bytes_differing": (bytes_off, 0)}
    if ctx.cell.traffic.get("preseed") and obs.origin_bytes is not None:
        # the warm cell's guarantee: all of it over P2P, none from the origin
        out["origin_bytes_in_window"] = (obs.origin_bytes, 0)
        out["bytes_not_p2p"] = (sum(
            abs(r.size - r.bytes_p2p) + r.bytes_source
            for r in result.requests if r.ok), 0)
    return out
