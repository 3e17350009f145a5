"""Content kind ``raw_shards``: ``shards`` files of ``shard_bytes`` opaque
bytes each, the shape of a sharded dataset as the fabric sees it (framing is
the consumer's). No manifest: each shard is one whole-buffer ingest. Files
are named by the configuration's ``shard_name`` (a format of ``i``, counted
from ``first_shard``). Where the machine's file bound is under a shard's
size, every shard is cut to the bound and a ``CUT:`` note says so."""

from __future__ import annotations

MiB = 1 << 20


def files(config: dict, cap: int) -> tuple[list[dict], list[str]]:
    size, notes = config["shard_bytes"], []
    if size > cap:
        size = cap
        notes.append(f"CUT: dataset shards of {cap / MiB:.0f} MiB each")
    name = config.get("shard_name", "shard-{i:05d}.bin")
    first = config.get("first_shard", 0)
    return ([{"name": name.format(i=first + i), "size": size, "shards": None}
             for i in range(config["shards"])], notes)
