"""The state-space state update's share of its HBM roofline in the traced
slice: bytes = the slots the slice's steps fed (``active``, summed over its
``scheduler.step`` spans: the program's count) x state-space layers x 2 x
(the float32 state + the convolution's inputs) a layer a slot (read once,
written once; from shapes, ``perfbench/flops_bytes_nemotron.py``), over the
chip's peak bandwidth, divided by the device seconds of ``ssm_step``. Bound by
bytes: a few operations an element. The same bytes whatever implements it.
Nothing where the program counts no steps or no operation has that name."""

from perfbench import flops_bytes_nemotron, kimi_counts, moe_counts


def read(record):
    counts = kimi_counts.slice_steps(record)
    seconds = moe_counts.kernel_seconds(record, "ssm_step")
    if counts is None or seconds <= 0:
        return None
    need = flops_bytes_nemotron.ssm_step_bytes(record["config"]["model"], counts["active"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / seconds if need else None
