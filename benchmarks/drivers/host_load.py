"""Driver ``host_load``: a host of several chips loads the whole model once.

The window drives ``source.pull`` (``Daemon.ptm.start_file_task`` with a
device sink and the file's PLACED manifest: every shard names the chip it is
to be on, ``idl.ShardInfo.device``), file after file in manifest order,
closed loop, one file in flight, each file under its own URL. Nothing is
dropped: the arrays stay on their chips until the comparison is done, as a
serving replica's do. With ``delete_after`` a file's pieces leave the
holder's store once its arrays are ready (``ptm.delete_task``, what
``ShardPrefetcher(delete_after=True)`` does); nothing is asked for twice. No
new file is issued after ``seconds``; the file in flight finishes and counts;
the window also ends when the whole model is on the chips.

The consumer is the benchmark's own, ``file_loads``' jitted word sum of every
array, dispatched on the array's own chip when its file is ready and not
waited for.

The comparison holds the program to ``benchmarks/reference_placed.py``: what
``file_loads`` compares (the word sums against those of the origin's bytes
put on the SAME chip by a plain ``jax.device_put``), and two numbers of the
placement: ``arrays_on_wrong_chip`` and ``chip_bytes_off``.

Mix parameters (traffic/<mix>.json): ``holder_of_content`` (``peer``),
``preseed``, ``delete_after``, ``disable_back_source``,
``sample_arrays_per_file``.
"""

from __future__ import annotations

import dataclasses
import time

from benchmarks import reference_placed
from benchmarks.drivers.file_loads import _word_sum
from benchmarks.harness import WindowResult, check, say
from benchmarks.sources import MiB, Request


def origin_extras(cell, files: list[dict]) -> list:
    """No file beyond the content. Asked before any data is made, so this
    is where a program that cannot run the cell says so, soon: one whose
    manifest has no placement (the commit before ``ShardInfo.device``)."""
    from dragonfly2_tpu.idl.messages import ShardInfo

    check("device" in {f.name for f in dataclasses.fields(ShardInfo)},
          f"cell {cell.name} places every array by its manifest, and this "
          "program's ShardInfo has no `device`: it cannot run the cell")
    return []


def _chips(ctx) -> list:
    """The chips the manifest's ordinals name: the first ``chips`` of the
    list the holder's sink is opened over."""
    return ctx.devices[:ctx.cell.chips]


def prepare(ctx) -> None:
    word_sum = ctx.state["word_sum"] = _word_sum()
    sizes = [(s["range_size"], -i, s["name"]) for i, f in enumerate(ctx.files)
             for s in f["shards"]]
    _size, fi, name = max(sizes)
    ctx.state["longest"] = (-fi, name)
    n_chips = ctx.cell.chips
    worst = max(s["device"] for f in ctx.files for s in f["shards"])
    check(worst < n_chips, f"the manifest names chip {worst}, the cell has "
                           f"{n_chips}")
    per_chip = reference_placed.chip_bytes(ctx.files, n_chips)
    say(f"placement: {sum(len(f['shards']) for f in ctx.files)} arrays over "
        f"{n_chips} chips, MiB a chip: "
        + " ".join(f"{b / MiB:.0f}" for b in per_chip))

    # the swarm's holder of content pulls every file from the origin
    t0 = time.monotonic()
    ctx.source.preseed(ctx.files)
    total = sum(f["size"] for f in ctx.files)
    say(f"pre-seeded: {total / MiB:.0f} MiB in {len(ctx.files)} files, each "
        f"under one URL, from the origin into the swarm, in "
        f"{time.monotonic() - t0:.1f}s")

    # the yardstick for the per-chip transfer rates: the reference's plain
    # device_put of a file's bytes onto the chips its manifest names. A
    # rate, never a share. The same arrays warm the consumer's program for
    # every shape on every chip of the window; a file that brings no new
    # (shape, chip) is not put twice
    import jax
    warmed: set = set()
    for f in ctx.files:
        combos = {(tuple(s["shape"]), s["dtype"], s["device"])
                  for s in f["shards"]}
        if combos <= warmed:
            continue
        warmed |= combos
        data = ctx.bytes_of(f)
        t0 = time.monotonic()
        put = reference_placed.put(data, f["shards"], _chips(ctx))
        dt = time.monotonic() - t0
        say(f"yardstick: plain jax.device_put of {f['name']} "
            f"({len(put)} arrays, {f['size'] / MiB:.0f} MiB) onto "
            f"{n_chips} chips in {dt:.3f}s = {f['size'] / dt / 1e9:.2f} GB/s")
        jax.block_until_ready([word_sum(a) for a in put])
        del put, data


def _consume(ctx, f: dict, arrays: dict, index: int) -> dict:
    """What is kept of a ready file: every array, where it is, and its
    word sum (dispatched, not waited for)."""
    word_sum = ctx.state["word_sum"]
    names = list(arrays)
    rng = ctx.rng(0, index)
    k = min(ctx.cell.traffic.get("sample_arrays_per_file", 2), len(names))
    sampled = [names[i] for i in rng.choice(len(names), k, replace=False)]
    if (index, ctx.state["longest"][1]) == ctx.state["longest"]:
        sampled.append(ctx.state["longest"][1])   # the longest array, once
    return {"file": f, "arrays": arrays,
            "sums": [word_sum(a) for a in arrays.values()],
            "sample": [n for n in dict.fromkeys(sampled) if n in arrays]}


def window(ctx, seconds: float) -> WindowResult:
    t = ctx.cell.traffic
    requests: list[Request] = []
    kept: list[dict] = []
    bytes_ready = 0
    t0 = t1 = time.monotonic()
    deadline = t0 + seconds
    for i, f in enumerate(ctx.files):
        if requests and time.monotonic() >= deadline:
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
                break
        requests.append(r)
        bytes_ready += f["size"]
        t1 = r.t_ready
        with ctx.span("consumer: word sums"):
            kept.append(_consume(ctx, f, arrays, i))
        if t.get("delete_after"):
            with ctx.span("the file's pieces leave the holder"):
                ctx.source.delete([f])
    ctx.state["kept"] = kept
    return WindowResult(t0, t1, requests, bytes_ready)


def compare(ctx, result: WindowResult, obs) -> dict:
    import jax
    import numpy as np

    kept = ctx.state.pop("kept")
    word_sum = ctx.state["word_sum"]
    chips = _chips(ctx)
    missing = sums_off = bytes_off = sampled = wrong_chip = 0
    ready = [0] * len(chips)
    for k in kept:
        f, arrays = k["file"], k["arrays"]
        data = ctx.bytes_of(f)
        # the reference: the origin's bytes on the chips the manifest
        # names, reduced by the same program (on the device and not in
        # numpy: a TPU carries no bf16 NaN payload through a computation,
        # so only two sums made the same way can be held equal)
        ref = reference_placed.put(data, f["shards"], chips)
        want_sums = [int(v) for v in
                     jax.device_get([word_sum(a) for a in ref])]
        got_sums = dict(zip(arrays, (int(v) for v in
                                     jax.device_get(k["sums"]))))
        missing += max(0, len(arrays) - len(f["shards"]))
        for s, want, want_sum in zip(f["shards"], ref, want_sums):
            got = arrays.get(s["name"])
            if (got is None or got.dtype != want.dtype
                    or got.shape != want.shape):
                missing += 1
                continue
            sums_off += got_sums[s["name"]] != want_sum
            where = got.devices()
            wrong_chip += where != {reference_placed.chip_of(s, chips)}
            for d in where & set(chips):
                ready[chips.index(d)] += s["range_size"]
        del ref
        spec = {s["name"]: s for s in f["shards"]}
        for name in k["sample"]:
            s = spec[name]
            # raw bytes, not values: random bf16 patterns include NaNs
            got_b = np.asarray(arrays[name]).view(np.uint8).reshape(-1)
            want_b = data[s["range_start"]:s["range_start"] + s["range_size"]]
            bytes_off += (int(np.count_nonzero(got_b != want_b))
                          if got_b.shape == want_b.shape else s["range_size"])
            sampled += 1
    want_ready = reference_placed.chip_bytes([k["file"] for k in kept],
                                             len(chips))
    say(f"compared {len(kept)} files of the window: "
        f"{sum(len(k['arrays']) for k in kept)} arrays by name, dtype, "
        f"shape, chip and word sum against the origin's files put on the "
        f"same chips; {sampled} sampled arrays read back and compared byte "
        f"for byte; MiB ready a chip: "
        + " ".join(f"{b / MiB:.0f}" for b in ready))
    out = {"arrays_missing": (missing, 0),
           "word_sums_differing": (sums_off, 0),
           "sample_bytes_differing": (bytes_off, 0),
           "arrays_on_wrong_chip": (wrong_chip, 0),
           "chip_bytes_off": (sum(abs(a - b) for a, b in
                                  zip(ready, want_ready)), 0)}
    if ctx.cell.traffic.get("preseed") and obs.origin_bytes is not None:
        # the warm cell's guarantee: all of it over P2P, none from the origin
        out["origin_bytes_in_window"] = (obs.origin_bytes, 0)
        out["bytes_not_p2p"] = (sum(
            abs(r.size - r.bytes_p2p) + r.bytes_source
            for r in result.requests if r.ok), 0)
    return out
