"""Device time of the routed experts' products in one decode step: the summed
device seconds of the operations named ``%moe_expert_ffn`` (the grouped
kernel's own name; one call an expert layer) over the number of
``_pool_step_paged_flash`` module events, in the traced slice. The calls
inside an admission's prefill carry the same name and are few beside the
steps'; they count here as the attention kernel's do in ``kern.paged_attn_ms``.
Nothing where no operation has that name."""

from perfbench.kernel_time import kernel_ms_per_step


def read(record):
    if record.get("serve") is None:
        return None
    return kernel_ms_per_step(record, "moe_expert_ffn", "_pool_step_paged_flash")
