"""The paged decode-attention kernel's share of its HBM roofline in the traced
slice, for a model some of whose layers keep no KV rows: bytes = keys and
values of one position of one layer x (layers that attend x ``attn_pos_full``
+ window layers x ``attn_pos_band``; a short-convolution layer nothing),
summed over the slice's ``scheduler.step`` spans (the program's counts;
``perfbench/flops_bytes_hybrid.py``), over the chip's peak bandwidth, divided
by the device seconds of ``paged_flash_attention``. Bound by bytes (one query
row a sequence). ``kern.paged_attn_band_roofline`` would count every layer
without a window as one that attends. Nothing where the program counts no
positions or no operation has that name."""

from perfbench import flops_bytes_hybrid, hybrid_counts, moe_counts


def read(record):
    counts = hybrid_counts.slice_positions(record)
    seconds = moe_counts.kernel_seconds(record, "paged_flash_attention")
    if counts is None or seconds <= 0:
        return None
    need = flops_bytes_hybrid.hybrid_attention_bytes(
        record["config"]["model"], counts["attn_pos_full"], counts["attn_pos_band"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / seconds
