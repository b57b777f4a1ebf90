"""Operations and bytes an algorithm needs, computed from shapes alone.

The benchmark's own arithmetic: nothing here reads the program's cost model
(``transformer_tpu/analysis/costs.py``), so a PR that changes the program
cannot change what a metric divides by. A multiply-add counts as 2 operations.
Only matrix multiplications are counted (projections, feed-forward, logits,
attention scores and values); recomputation does not count.
"""

from __future__ import annotations

import numpy as np

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def _attn_proj_params(c: dict) -> int:
    """Weights of one attention block's four projections (GQA aware)."""
    d, h = c["d_model"], c["num_heads"]
    hd = d // h
    kv = (c.get("num_kv_heads") or h) * hd
    return d * d + 2 * d * kv + d * d


def _ffn_params(c: dict) -> int:
    gated = c.get("ffn_activation", "relu") in ("swiglu", "geglu", "reglu")
    return (3 if gated else 2) * c["d_model"] * c["dff"]


def seq2seq_weight_flops_per_position(c: dict) -> dict:
    """Forward matmul operations through the weights for ONE source position,
    ONE target position, and one target position's logits."""
    L = c["num_layers"]
    enc = L * (_attn_proj_params(c) + _ffn_params(c))
    dec = L * (2 * _attn_proj_params(c) + _ffn_params(c))
    logits = c["d_model"] * c["target_vocab_size"]
    return {"src": 2 * enc, "tgt": 2 * (dec + logits)}


def seq2seq_train_flops(c: dict, src_lens: np.ndarray, tgt_lens: np.ndarray) -> float:
    """Forward + backward (3x forward) matmul operations for sentence pairs of
    these NON-PAD lengths: ``src_lens`` source tokens each, ``tgt_lens`` target
    positions each (the shifted target, one less than the framed sentence).
    Attention: encoder self n_s^2, decoder self causal n_t^2 / 2, cross
    n_t * n_s; scores and values are 2 matmuls of d_model width per layer."""
    w = seq2seq_weight_flops_per_position(c)
    s = np.asarray(src_lens, np.float64)
    t = np.asarray(tgt_lens, np.float64)
    d, L = c["d_model"], c["num_layers"]
    attn = L * 2 * 2 * d * (s * s + t * t / 2 + t * s)
    fwd = w["src"] * s + w["tgt"] * t + attn
    return float(3.0 * fwd.sum())


def kv_bytes_per_token(c: dict) -> int:
    """Bytes of keys and values one cached position holds over all layers."""
    h = c.get("num_kv_heads") or c["num_heads"]
    hd = c["d_model"] // c["num_heads"]
    return 2 * h * hd * c["num_layers"] * _DTYPE_BYTES[c["dtype"]]


def paged_attention_step_bytes(c: dict, blocks_in_use: int, block_tokens: int) -> int:
    """Bytes one decode step's attention must read: every block in use, K and
    V, in every layer (queries and outputs are negligible beside them)."""
    return blocks_in_use * block_tokens * kv_bytes_per_token(c)


def decoder_lm_params(c: dict) -> int:
    """Weights of a decoder-only LM (biases and norms included, tied head once)."""
    d, L, v = c["d_model"], c["num_layers"], c["target_vocab_size"]
    h = c["num_heads"]
    hd = d // h
    kv = (c.get("num_kv_heads") or h) * hd
    per_layer = _attn_proj_params(c) + (2 * d + 2 * kv) + _ffn_params(c) + c["dff"] + d + 4 * d
    head = 0 if c.get("tie_output") else d * v + v
    return v * d + L * per_layer + 2 * d + head
