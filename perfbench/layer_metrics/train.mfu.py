"""Forward + backward matmul operations of the NON-PAD tokens trained inside the
window (perfbench/flops_bytes.py, from shapes), over the window and the chip's
peak bf16 rate. An end-to-end utilization, not a kernel's roofline share."""


def read(record):
    t = record.get("train")
    if t is None:
        return None
    peak = record["peaks"]["bf16_flops_per_s"] * record["cell"]["chips"]
    return 100.0 * t["matmul_flops"] / record["window_s"] / peak
