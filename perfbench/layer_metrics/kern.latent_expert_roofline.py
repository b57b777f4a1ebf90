"""The latent routed experts' share of their HBM roofline in the traced slice:
the bytes of the experts hit (two matrices of ``moe_latent_dim x moe_dff``
each; ``perfbench/flops_bytes_nemotron.py``) over the chip's peak bandwidth,
divided by the device seconds of ``moe_expert_ffn``. Experts hit in the slice
= the window's mean of ``moe_hit / moe_steps`` (the program's counts, read
every few steps) times the slice's decode steps. Bound by bytes: at decode an
expert sees a few rows. The same bytes whatever implements the layer. The
prefill's calls of the kernel are in the seconds and not in the bytes, so the
share reads low by their part. Nothing where the model has no latent width,
the program counts no experts or no operation has that name."""

from perfbench import flops_bytes_nemotron, moe_counts


def read(record):
    m = record["config"]["model"]
    counts = moe_counts.window_moe(record)
    seconds = moe_counts.kernel_seconds(record, "moe_expert_ffn")
    steps = moe_counts.slice_pool_steps(record)
    if not m.get("moe_latent_dim") or counts is None or seconds <= 0 or not steps:
        return None
    hit = counts["moe_hit"] / counts["moe_steps"] * steps
    need = flops_bytes_nemotron.latent_experts_hit_bytes(m, hit)
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / seconds
