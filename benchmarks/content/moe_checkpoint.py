"""Content kind ``moe_checkpoint``: the embedding and the routed experts of a
deepseek_v3-style MoE model, as bf16 tensors back to back in files of whole
tensors. A copy of ``chip_smoke.checkpoint_manifest``/``checkpoint_files``,
reading the model's own config keys.

Layout: ``model.embed_tokens.weight`` in a file of its own, then one file per
MoE layer (``n_routed_experts`` x gate/up/down), the way a published
checkpoint is sharded. Where the machine's file bound is under a file's
size, that file is cut further at whole tensors; a tensor larger than the
bound can be in no file and is named in a ``CUT:`` note.
"""

from __future__ import annotations

PUBLISHED_WIDTHS = {"vocab_size": 163840, "hidden_size": 2048,
                    "n_routed_experts": 64, "moe_intermediate_size": 1408}


def _tensor(name: str, shape: tuple[int, int]) -> dict:
    return {"name": name, "range_size": shape[0] * shape[1] * 2,
            "dtype": "bfloat16", "shape": list(shape)}


def groups(config: dict) -> list[list[dict]]:
    """The tensors of each intended file, in order. Layers before
    ``first_k_dense_replace`` are dense and hold no routed expert."""
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    out = [[_tensor("model.embed_tokens.weight",
                    (config["vocab_size"], hidden))]]
    for layer in range(config["first_k_dense_replace"],
                       config["num_hidden_layers"]):
        group = []
        for e in range(config["n_routed_experts"]):
            base = f"model.layers.{layer}.mlp.experts.{e}"
            group += [_tensor(f"{base}.gate_proj.weight", (width, hidden)),
                      _tensor(f"{base}.up_proj.weight", (width, hidden)),
                      _tensor(f"{base}.down_proj.weight", (hidden, width))]
        out.append(group)
    return out


def files(config: dict, cap: int) -> tuple[list[dict], list[str]]:
    """``[{"name", "size", "shards"}]`` with no file over ``cap``, and the
    notes to print. ``shards`` is the file's manifest: each tensor with the
    offset it has in that file."""
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
        notes.append(f"CUT: {len(left_out)} tensors larger than the file "
                     f"bound are in no file: {left_out[:3]}")
    if any(config.get(k) != v for k, v in PUBLISHED_WIDTHS.items()):
        notes.append("WIDTHS CUT TOO: test sizes, not the model's")
    return out, notes
