"""Device time of the paged decode-attention kernel in one decode step: the
summed device seconds of the operations named ``%paged_flash_attention`` (the
kernel's own name; one call a layer) over the number of
``_pool_step_paged_flash`` module events, in the traced slice. Nothing where
no operation has that name (an earlier commit, where every Mosaic call is
named after the jitted function)."""

from perfbench.kernel_time import kernel_ms_per_step


def read(record):
    if record.get("serve") is None:
        return None
    return kernel_ms_per_step(record, "paged_flash_attention", "_pool_step_paged_flash")
