"""Content kind ``moe_checkpoint_placed``: ``moe_checkpoint``'s files (the
embedding, then one file per MoE layer of ``n_routed_experts`` x gate/up/down,
bf16, whole tensors back to back) with a manifest that says where each array
goes on a host whose chips share every layer by expert.

The configuration's ``placement.chips_sharing_a_layer`` chips divide a layer:
chip ``c`` holds routed experts ``c*E/chips .. (c+1)*E/chips - 1`` of every
MoE layer, all three matrices of an expert on one chip, and rows
``c*V/chips .. (c+1)*V/chips - 1`` of ``embed_tokens`` (a contiguous byte
range of the tensor, an array of its own). Each shard of a file's manifest
carries that chip's ordinal as ``device`` (``idl.ShardInfo.device``). The
tensor names, widths and file layout are ``moe_checkpoint``'s; the cut under
a machine's file bound is the same, at whole arrays.
"""

from __future__ import annotations

from benchmarks.content.moe_checkpoint import PUBLISHED_WIDTHS, _tensor

EMBED = "model.embed_tokens.weight"


def groups(config: dict) -> list[list[dict]]:
    """The arrays of each intended file, in order, each with its chip."""
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    chips = config["placement"]["chips_sharing_a_layer"]
    experts, vocab = config["n_routed_experts"], config["vocab_size"]
    if experts % chips or vocab % chips:
        raise ValueError(f"{chips} chips do not divide {experts} experts and "
                         f"{vocab} rows evenly")
    rows, per_chip = vocab // chips, experts // chips
    out = [[{**_tensor(f"{EMBED}.rows_{c * rows}_{(c + 1) * rows}",
                       (rows, hidden)), "device": c} for c in range(chips)]]
    for layer in range(config["first_k_dense_replace"],
                       config["num_hidden_layers"]):
        group = []
        for e in range(experts):
            base = f"model.layers.{layer}.mlp.experts.{e}"
            group += [{**t, "device": e // per_chip} for t in (
                _tensor(f"{base}.gate_proj.weight", (width, hidden)),
                _tensor(f"{base}.up_proj.weight", (width, hidden)),
                _tensor(f"{base}.down_proj.weight", (hidden, width)))]
        out.append(group)
    return out


def files(config: dict, cap: int) -> tuple[list[dict], list[str]]:
    """``[{"name", "size", "shards"}]`` with no file over ``cap``, and the
    notes to print; ``shards`` is the file's placed manifest."""
    out: list[dict] = []
    left_out: list[str] = []
    for group in groups(config):
        new_file = True
        for t in group:
            if t["range_size"] > cap:
                left_out.append(t["name"])
                continue
            if new_file or out[-1]["size"] + t["range_size"] > cap:
                out.append({"size": 0, "shards": []})
                new_file = False
            out[-1]["shards"].append({**t, "range_start": out[-1]["size"]})
            out[-1]["size"] += t["range_size"]
    for i, f in enumerate(out, 1):
        f["name"] = f"model-{i:05d}-of-{len(out):05d}.bf16"
    notes = []
    if left_out:
        notes.append(f"CUT: {len(left_out)} arrays larger than the file "
                     f"bound are in no file: {left_out[:3]}")
    if any(config.get(k) != v for k, v in PUBLISHED_WIDTHS.items()):
        notes.append("WIDTHS CUT TOO: test sizes, not the model's")
    return out, notes
