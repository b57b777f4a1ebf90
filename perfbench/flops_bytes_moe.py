"""Bytes a step of a model of several layer kinds with expert layers must
read, computed from shapes alone (see ``perfbench/flops_bytes.py`` for the
rules: the benchmark's own arithmetic, nothing of the program's).

Both kernels these serve are bound by bytes at decode: one query row a
sequence against the cache, a few rows an expert against 19 MB of weights.
"""

from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def expert_weight_bytes(c: dict) -> int:
    """Bytes of ONE routed expert's three matrices (gate, in, out)."""
    return 3 * c["d_model"] * c["moe_dff"] * _DTYPE_BYTES[c["dtype"]]


def experts_hit_bytes(c: dict, experts_hit: float) -> float:
    """Bytes the routed experts' products must read when ``experts_hit``
    experts (summed over the expert layers) received a token: each hit
    expert's weights once. The rows themselves are negligible beside them at
    decode, and the bytes are the same whatever implements the layer."""
    return experts_hit * expert_weight_bytes(c)


def kv_bytes_per_position_per_layer(c: dict) -> int:
    """Keys and values one cached position holds in ONE layer: every layer
    kind has the model's KV heads and head size."""
    heads = c.get("num_kv_heads") or c["num_heads"]
    head = c.get("head_size") or c["d_model"] // c["num_heads"]
    return 2 * heads * head * _DTYPE_BYTES[c["dtype"]]


def layers_by_kind(c: dict) -> tuple[int, int]:
    """(layers that attend the whole cache, layers that attend a window)."""
    kinds = {k["name"]: k for k in c["attention_kinds"]}
    pattern = c["layer_pattern"]
    windowed = sum(1 for l in range(c["num_layers"]) if kinds[pattern[l % len(pattern)]].get("window", 0))
    return c["num_layers"] - windowed, windowed


def banded_attention_bytes(c: dict, positions_full: float, positions_band: float) -> float:
    """Bytes decode attention must read over steps whose active slots sum to
    ``positions_full`` cached positions and ``positions_band`` positions
    inside the window: a full layer reads all of a slot's positions, a window
    layer its band."""
    full, windowed = layers_by_kind(c)
    return kv_bytes_per_position_per_layer(c) * (full * positions_full + windowed * positions_band)


def expert_layers(c: dict) -> int:
    return c["num_layers"] - c.get("moe_leading_dense", 0)


def laguna_params(c: dict) -> int:
    """Weights held here: attention by kind, the leading dense FFNs, the held
    experts, shared expert and router of each expert layer, norms, embedding
    and untied head."""
    d, head = c["d_model"], c["head_size"]
    kv = c["num_kv_heads"] * head
    kinds = {k["name"]: k for k in c["attention_kinds"]}
    pattern = c["layer_pattern"]
    total = 0
    for l in range(c["num_layers"]):
        h = kinds[pattern[l % len(pattern)]]["num_heads"]
        total += 2 * d * h * head + 2 * d * kv + d * h + 2 * d  # q, out, k, v, gate, two norms
        if l < c.get("moe_leading_dense", 0):
            total += 3 * d * c["dff"]
        else:
            total += 3 * d * c["moe_dff"] * c["moe_experts_held"] + 3 * d * c["moe_shared_dff"] + d * c["moe_experts"]
    return total + d + c["input_vocab_size"] * d + d * c["target_vocab_size"]
