"""Device time of the fused LayerNorm + feed-forward kernel in one decode step:
the summed device seconds of the operations named ``%fused_ln_ffn`` (one call a
layer) over the number of ``_pool_step_paged_flash`` module events, in the
traced slice. Nothing where no operation has that name."""

from perfbench.kernel_time import kernel_ms_per_step


def read(record):
    if record.get("serve") is None:
        return None
    return kernel_ms_per_step(record, "fused_ln_ffn", "_pool_step_paged_flash")
