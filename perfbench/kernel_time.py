"""A named kernel's device time per call of the program that holds it, from the
reduced trace (``perfbench/trace_reduce.py``).

A device event's name is the whole HLO instruction, and a Pallas kernel given
a ``name=`` is ``%<name>.<n> = .. custom-call(..)``. The kernel's seconds are
summed over every instruction of that name (one a layer) and divided by the
number of the program's events on the ``XLA Modules`` line: device time of the
kernel in one call of the program. The slice opens and closes between two
steps of the loop, so every call in it is whole.
"""

from __future__ import annotations

import re


def kernel_ms_per_step(record: dict, kernel: str, module: str) -> float | None:
    tr = record.get("trace")
    if tr is None:
        return None
    named = re.compile(rf"^%?{re.escape(kernel)}(\.\d+)* ")
    seconds = sum(t for name, t, _ in tr["ops"] if named.match(name))
    calls = sum(1 for name, _ in tr["module_events"] if module in name)
    if seconds <= 0 or not calls:
        return None
    return 1e3 * seconds / calls
