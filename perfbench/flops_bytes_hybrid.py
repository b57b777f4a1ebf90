"""Bytes and parameters of a model some of whose layers keep no KV rows (a
gated short convolution in attention's place), computed from shapes alone (see
``perfbench/flops_bytes.py`` for the rules: the benchmark's own arithmetic,
nothing of the program's).

A short-convolution layer's state is ``conv_kernel - 1`` rows of ``d_model``
a sequence, however long the sequence; decode attention reads nothing for it.
"""

from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def kinds_of_layers(c: dict) -> list[dict]:
    """Layer by layer, the kind the model's ``layer_pattern`` gives it."""
    kinds = {k["name"]: k for k in c["attention_kinds"]}
    pattern = c["layer_pattern"]
    return [kinds[pattern[l % len(pattern)]] for l in range(c["num_layers"])]


def layers_by_state(c: dict) -> tuple[int, int, int]:
    """(layers that attend the whole cache, layers that attend a window,
    layers that keep no KV rows)."""
    kinds = kinds_of_layers(c)
    conv = sum(1 for k in kinds if k.get("conv_kernel", 0))
    windowed = sum(1 for k in kinds if not k.get("conv_kernel", 0) and k.get("window", 0))
    return len(kinds) - conv - windowed, windowed, conv


def kv_bytes_per_position_per_layer(c: dict) -> int:
    """Keys and values one cached position holds in ONE attention layer."""
    heads = c.get("num_kv_heads") or c["num_heads"]
    head = c.get("head_size") or c["d_model"] // c["num_heads"]
    return 2 * heads * head * _DTYPE_BYTES[c["dtype"]]


def kv_bytes_per_token(c: dict) -> int:
    """What one position of a sequence costs the pool: the attention layers' rows."""
    full, windowed, _ = layers_by_state(c)
    return (full + windowed) * kv_bytes_per_position_per_layer(c)


def state_bytes_per_slot(c: dict) -> int:
    """What a slot holds for the layers that keep no KV rows."""
    return sum((k["conv_kernel"] - 1) * c["d_model"] * _DTYPE_BYTES[c["dtype"]]
               for k in kinds_of_layers(c) if k.get("conv_kernel", 0))


def hybrid_attention_bytes(c: dict, positions_full: float, positions_band: float = 0.0) -> float:
    """Bytes decode attention must read over steps whose active slots sum to
    ``positions_full`` cached positions (and ``positions_band`` inside a
    window): a full layer reads all of a slot's positions, a window layer its
    band, a short-convolution layer nothing."""
    full, windowed, _ = layers_by_state(c)
    return kv_bytes_per_position_per_layer(c) * (full * positions_full + windowed * positions_band)


def lfm2_params(c: dict) -> int:
    """Weights held here: the short-convolution mixers (in 3x, taps, out), the
    attention mixers (q, k, v, out and the two head-wide q/k scales), the
    leading dense SwiGLUs, the held experts with the router and its selection
    bias, two norms a layer, the final norm and the tied embedding."""
    d = c["d_model"]
    head = c.get("head_size") or d // c["num_heads"]
    kv = (c.get("num_kv_heads") or c["num_heads"]) * head
    total = 0
    for l, kind in enumerate(kinds_of_layers(c)):
        taps = kind.get("conv_kernel", 0)
        if taps:
            total += 3 * d * d + taps * d + d * d
        else:
            heads = kind.get("num_heads") or c["num_heads"]
            total += 2 * d * heads * head + 2 * d * kv + 2 * head
        total += 2 * d  # ln1, ln_ffn
        if l < c.get("moe_leading_dense", 0):
            total += 3 * d * c["dff"]
        else:
            held = c.get("moe_experts_held") or c["moe_experts"]
            total += 3 * d * c["moe_dff"] * held + d * c["moe_experts"] + c["moe_experts"]
    return total + d + c["target_vocab_size"] * d
