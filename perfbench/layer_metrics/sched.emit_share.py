"""Share of the window's slot-steps that gave a client a token: the sum of
``emitted`` over the sum of ``active`` on the window's ``scheduler.step`` spans
(the program's own counts, made where the slots are walked). The rest walked a
prompt tail one token a step. Nothing where the program records no such
counts."""

from perfbench import program_api_spans as api


def read(record):
    if record.get("serve") is None:
        return None
    steps = [s for s in api.window_spans(record, "scheduler.step") or [] if "emitted" in s]
    active = sum(s["active"] for s in steps)
    return 100.0 * sum(s["emitted"] for s in steps) / active if active else None
