"""Bytes and parameters of a model whose layers are a Mamba-2 state-space mixer
(a float32 matrix state a head a slot), GQA attention, or a latent expert layer
(ungated experts in a latent narrower than the model), computed from shapes
alone (see ``perfbench/flops_bytes.py`` for the rules: the benchmark's own
arithmetic, nothing of the program's).

Both kernels these serve are bound by bytes at decode. The state-space step
reads and writes a slot's float32 state once a layer a step, ``heads x
head_dim x state x 4`` bytes each way, and the convolution's last ``taps - 1``
inputs beside it, whatever the sequence's length. A latent expert is two
matrices of ``latent x moe_dff``, read once a step where it is hit.
"""

from __future__ import annotations

from perfbench.flops_bytes_hybrid import _DTYPE_BYTES, kinds_of_layers


def ssm_layers(c: dict) -> list[dict]:
    return [k for k in kinds_of_layers(c) if k.get("ssm_heads", 0) and k.get("sublayers", "both") != "ffn"]


def ssm_state_bytes_per_slot_per_layer(k: dict) -> int:
    """One state-space layer's matrix state a sequence: float32, (P, N) a head."""
    return k["ssm_heads"] * k["ssm_head_dim"] * k["ssm_state"] * 4


def ssm_conv_bytes_per_slot_per_layer(c: dict, k: dict) -> int:
    """The last ``taps - 1`` inputs of the convolution over x, B and C."""
    channels = k["ssm_heads"] * k["ssm_head_dim"] + 2 * k.get("ssm_groups", 1) * k["ssm_state"]
    return (k.get("ssm_conv_kernel", 4) - 1) * channels * _DTYPE_BYTES[c["dtype"]]


def state_bytes_per_slot(c: dict) -> int:
    """What a slot holds for the layers that keep no rows a position."""
    return sum(ssm_state_bytes_per_slot_per_layer(k) + ssm_conv_bytes_per_slot_per_layer(c, k) for k in ssm_layers(c))


def ssm_step_bytes(c: dict, slot_steps: float) -> float:
    """Bytes a decode step's state update must move over steps whose live slots
    sum to ``slot_steps``: each state-space layer's state and convolution
    inputs read once and written once."""
    return slot_steps * 2 * state_bytes_per_slot(c)


def latent_expert_bytes(c: dict) -> int:
    """Bytes of ONE routed expert's two matrices (in, out) in the latent."""
    return 2 * c["moe_latent_dim"] * c["moe_dff"] * _DTYPE_BYTES[c["dtype"]]


def latent_experts_hit_bytes(c: dict, experts_hit: float) -> float:
    """Bytes the routed experts' products must read when ``experts_hit``
    experts (summed over the expert layers) received a token: each hit
    expert's weights once."""
    return experts_hit * latent_expert_bytes(c)


def nemotron_params(c: dict) -> int:
    """Weights held here: the Mamba mixers (in-projection, the convolution's
    taps and bias, dt_bias, A_log, D, the gated norm, out), the attention
    layers (q, k, v, out), the expert layers (router and its selection bias,
    the latent projections, the held experts, the shared expert), one norm a
    layer, the final norm, the embedding and the untied head."""
    d = c["d_model"]
    total = 0
    for k in kinds_of_layers(c):
        if k.get("sublayers", "both") == "ffn":
            held = c.get("moe_experts_held") or c["moe_experts"]
            latent, experts = c["moe_latent_dim"], c["moe_experts"]
            total += d * experts + experts + 2 * d * latent + 2 * latent * c["moe_dff"] * held
            total += 2 * d * c["moe_shared_dff"]
        elif k.get("ssm_heads", 0):
            width = k["ssm_heads"] * k["ssm_head_dim"]
            conv = width + 2 * k["ssm_groups"] * k["ssm_state"]
            total += d * (width + conv + k["ssm_heads"]) + k.get("ssm_conv_kernel", 4) * conv + conv
            total += 3 * k["ssm_heads"] + width + width * d
        else:
            heads, kv = k.get("num_heads") or c["num_heads"], c.get("num_kv_heads") or c["num_heads"]
            head = c.get("head_size") or d // c["num_heads"]
            total += d * (heads + 2 * kv) * head + heads * head * d
        total += d  # the layer's one norm
    return total + d + c["input_vocab_size"] * d + d * c["target_vocab_size"]
