"""The paged decode-attention kernel's share of its HBM roofline in the traced
slice: bytes the algorithm must read (blocks in use x block bytes, K and V, all
layers; perfbench/flops_bytes.py) over the chip's peak bandwidth, divided by
the kernel's summed device time. Bound by bytes, not operations (one query row
per sequence).

The kernel has no name of its own in the trace: every Mosaic call of the step
is ``%_pool_step_paged_flash.<n> = .. custom-call(..)``, the attention kernel
and the fused feed-forward alike (PERF.md, list for the tracing issue). The
attention calls are told by their operands: they alone read the KV pool in
place, ``[pool blocks, block tokens, KV heads, head size]`` (the allocator
does not count the sink block, so the first size is matched as any number).
Returns nothing where no such call is in the trace."""

import re

from perfbench import flops_bytes


def read(record):
    s, tr = record.get("serve"), record.get("trace")
    if s is None or tr is None:
        return None
    m, dep = record["config"]["model"], record["cell"]["deployment"]
    block = dep.get("kv_block", 16)
    pool = re.compile(rf"\[\d+,{block},{m.get('num_kv_heads') or m['num_heads']},{m['d_model'] // m['num_heads']}\]")
    kernel_s = sum(t for name, t, _ in tr["ops"] if "custom-call(" in name and pool.search(name))
    if kernel_s <= 0:
        return None
    t0, t1 = record["trace_host"]
    need = sum(
        flops_bytes.paged_attention_step_bytes(m, st[3], block)
        for st in s["all_steps"] if st[0] >= t0 and st[1] <= t1
    )
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / kernel_s
