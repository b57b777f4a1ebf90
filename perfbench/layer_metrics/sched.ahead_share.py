"""The steps the device did not wait for the host to be given: ``ahead``
summed over the window's ``scheduler.step`` spans over their count. The note
gives the steps that were not ahead by their ``drain`` (why the device had
nothing queued) and the rows the overlap spends on slots nobody reads:
``overstepped`` over ``active + overstepped``. Nothing where the program's
spans carry no ``prefills`` (an earlier commit's ``ahead`` meant less)."""

import collections

from perfbench import device_gaps
from perfbench import program_api_spans as api


def read(record):
    steps = device_gaps.window_steps(record)
    if steps is None:
        return None
    drains = collections.Counter(s.get("drain", "not_ahead") for s in steps if not s.get("ahead"))
    over = sum(s.get("overstepped", 0) for s in steps)
    rows = over + sum(s.get("active", 0) for s in steps)
    api.say("sched.ahead", {"steps": len(steps), "not_ahead_by_drain": drains, "overstepped_rows": over,
                            "overstepped_share_pct": 100.0 * over / rows if rows else None})
    return 100.0 * sum(1 for s in steps if s.get("ahead")) / len(steps)
