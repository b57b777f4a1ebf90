"""One admission as the scheduler sees it: median duration of the
``serve.admit`` spans begun inside the window (validation and encoding, block
allocation, the prefill's dispatch and, where the whole prompt was prefilled,
the first pick, which waits for the device). Nothing where the program records
no such spans."""

import statistics

from perfbench import program_api_spans as api


def read(record):
    if record.get("serve") is None:
        return None
    admits = api.window_spans(record, "serve.admit")
    return 1e3 * statistics.median(a["dur_s"] for a in admits) if admits else None
