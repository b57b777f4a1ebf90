"""Bytes and parameters of a model whose layers are delta-rule linear attention
(KDA: a matrix state a head a slot) beside latent attention (MLA: one row a
position), computed from shapes alone (see ``perfbench/flops_bytes.py`` for the
rules: the benchmark's own arithmetic, nothing of the program's).

Both kernels these serve are bound by bytes at decode. ``kda_step`` reads and
writes a slot's float32 state once a layer a step: ``heads x head_dim x
head_dim x 4`` bytes each way, whatever the sequence's length. The latent
kernel reads ``latent_rank + latent_shared_dim`` channels a cached position a
layer, ONCE for all the heads: what the mathematics needs, whatever padding the
pool stores (padding reads as a lower share).
"""

from __future__ import annotations

from perfbench.flops_bytes_hybrid import _DTYPE_BYTES, kinds_of_layers


def kda_layers(c: dict) -> list[dict]:
    return [k for k in kinds_of_layers(c) if k.get("kda_heads", 0)]


def latent_layers(c: dict) -> list[dict]:
    return [k for k in kinds_of_layers(c) if k.get("latent_rank", 0)]


def kda_state_bytes_per_slot_per_layer(k: dict) -> int:
    """One KDA layer's matrix state a sequence: float32, a (D, D) matrix a head."""
    return k["kda_heads"] * k["kda_head_dim"] * k["kda_head_dim"] * 4


def kda_conv_bytes_per_slot_per_layer(c: dict, k: dict) -> int:
    """The last ``taps - 1`` inputs of the q, k and v convolutions."""
    return 3 * (k.get("kda_conv_kernel", 4) - 1) * k["kda_heads"] * k["kda_head_dim"] * _DTYPE_BYTES[c["dtype"]]


def state_bytes_per_slot(c: dict) -> int:
    """What a slot holds for the layers that keep no rows a position."""
    return sum(kda_state_bytes_per_slot_per_layer(k) + kda_conv_bytes_per_slot_per_layer(c, k) for k in kda_layers(c))


def latent_bytes_per_position(c: dict) -> int:
    """What one cached position holds over the latent layers, unpadded."""
    return sum(k["latent_rank"] + k["latent_shared_dim"] for k in latent_layers(c)) * _DTYPE_BYTES[c["dtype"]]


def kda_step_bytes(c: dict, slot_steps: float) -> float:
    """Bytes ``kda_step`` must move over steps whose live slots sum to
    ``slot_steps``: each KDA layer's state read once and written once."""
    return slot_steps * sum(2 * kda_state_bytes_per_slot_per_layer(k) for k in kda_layers(c))


def latent_attention_bytes(c: dict, positions: float) -> float:
    """Bytes latent decode attention must read over steps whose live slots
    sum to ``positions`` cached positions: every latent layer's row once."""
    return positions * latent_bytes_per_position(c)


def kimi_params(c: dict) -> int:
    """Weights held here: the KDA mixers (q, k, v and their taps, the two
    low-rank gates, beta, A_log, dt_bias, the output norm, out), the MLA mixers
    (q, kv_a, its norm, kv_b, out), the leading dense SwiGLUs, the held
    experts with the shared expert, the router and its selection bias, two
    norms a layer, the final norm, the embedding and the untied head."""
    d = c["d_model"]
    total = 0
    for l, k in enumerate(kinds_of_layers(c)):
        if k.get("kda_heads", 0):
            heads, dim, taps = k["kda_heads"], k["kda_head_dim"], k.get("kda_conv_kernel", 4)
            width, rank = heads * dim, k.get("kda_gate_rank", 0) or dim
            total += 3 * (d * width + taps * width) + 2 * (d * rank + rank * width) + d * heads
            total += heads + width + dim + width * d  # A_log, dt_bias, o_norm, out
        else:
            heads = k.get("num_heads") or c["num_heads"]
            r, n, p, v = k["latent_rank"], k["latent_nope_dim"], k["latent_shared_dim"], k["latent_value_dim"]
            total += d * heads * (n + p) + d * (r + p) + r + r * heads * (n + v) + heads * v * d
        total += 2 * d  # ln1, ln_ffn
        if l < c.get("moe_leading_dense", 0):
            total += 3 * d * c["dff"]
        else:
            held = c.get("moe_experts_held") or c["moe_experts"]
            total += 3 * d * c["moe_dff"] * held + 3 * d * c["moe_shared_dff"] + d * c["moe_experts"] + c["moe_experts"]
    return total + d + c["input_vocab_size"] * d + d * c["target_vocab_size"]
