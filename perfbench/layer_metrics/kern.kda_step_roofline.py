"""The delta-rule state update's share of its HBM roofline in the traced
slice: bytes = the slots the slice's steps fed (``active``, summed over its
``scheduler.step`` spans: the program's count) x KDA layers x 2 x the float32
state a layer a slot (read once, written once; from shapes,
``perfbench/flops_bytes_kimi.py``), over the chip's peak bandwidth, divided by
the device seconds of ``kda_step``. Bound by bytes: a few operations an
element. The q, k, v and gates beside the state are a thirtieth of it and are
not counted. Nothing where the program counts no steps or no operation has
that name."""

from perfbench import flops_bytes_kimi, kimi_counts, moe_counts


def read(record):
    counts = kimi_counts.slice_steps(record)
    seconds = moe_counts.kernel_seconds(record, "kda_step")
    if counts is None or seconds <= 0:
        return None
    need = flops_bytes_kimi.kda_step_bytes(record["config"]["model"], counts["active"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / seconds if need else None
