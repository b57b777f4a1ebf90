"""The latent decode-attention kernel's share of its HBM roofline in the traced
slice: bytes = (latent rank + shared key part) channels of one position x
latent layers x ``attn_pos_full`` summed over the slice's ``scheduler.step``
spans (the program's count of the positions a full layer attends;
``perfbench/flops_bytes_kimi.py``), over the chip's peak bandwidth, divided by
the device seconds of ``paged_latent_attention``. A row is counted once for all
the heads and without the padding the pool stores, so padding reads as a lower
share. 60 FLOP a byte, under the chip's ridge: bound by bytes. Nothing where
the program counts no positions or no operation has that name."""

from perfbench import flops_bytes_kimi, kimi_counts, moe_counts


def read(record):
    counts = kimi_counts.slice_steps(record)
    seconds = moe_counts.kernel_seconds(record, "paged_latent_attention")
    if counts is None or seconds <= 0:
        return None
    need = flops_bytes_kimi.latent_attention_bytes(record["config"]["model"], counts["attn_pos_full"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / seconds if need else None
