"""The paged decode-attention kernel's share of its HBM roofline in the traced
slice, for a model whose full layers read all of a slot's cache and whose
window layers read a band: bytes = keys and values of one position of one
layer x (full layers x ``attn_pos_full`` + window layers x ``attn_pos_band``),
summed over the slice's ``scheduler.step`` spans (the program's counts;
``perfbench/flops_bytes_moe.py``), over the chip's peak bandwidth, divided by
the device seconds of ``paged_flash_attention``. Bound by bytes (one query
row a sequence). Nothing where the program counts no positions or no
operation has that name."""

from perfbench import flops_bytes_moe, moe_counts


def read(record):
    counts = moe_counts.slice_attention(record)
    seconds = moe_counts.kernel_seconds(record, "paged_flash_attention")
    if counts is None or seconds <= 0:
        return None
    need = flops_bytes_moe.banded_attention_bytes(record["config"]["model"], counts["attn_pos_full"], counts["attn_pos_band"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / seconds
