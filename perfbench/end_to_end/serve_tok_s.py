"""Output tokens emitted inside the window (the scheduler's own counter
``serve_generated_tokens_total``, end minus start), over the window, per chip."""


def read(record):
    s = record.get("serve")
    if s is None:
        return None
    return (s["tokens_at_t1"] - s["tokens_at_t0"]) / record["window_s"] / record["cell"]["chips"]
