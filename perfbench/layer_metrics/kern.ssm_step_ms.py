"""Device time of the state-space state update in one decode step: the summed
device seconds of the operations named ``%ssm_step`` (the kernel's own name;
one call a state-space layer) over the number of ``_pool_step_paged_flash``
module events, in the traced slice. Nothing where no operation has that name
(an earlier commit, a model without such layers)."""

from perfbench.kernel_time import kernel_ms_per_step


def read(record):
    if record.get("serve") is None:
        return None
    return kernel_ms_per_step(record, "ssm_step", "_pool_step_paged_flash")
