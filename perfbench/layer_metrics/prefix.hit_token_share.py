"""The share of the prompt tokens admitted in the window that the prefix cache
served: ``prefix_hit_tokens`` over ``prompt_tokens``, both summed over the
``serve.admit`` spans that began in the window (the scheduler's own counts of
an admission, ``perfbench/program_api_spans.py``), in %. A token served from
the cache is one the prefill does not compute. Nothing where the program
records no such span or no prompt token was admitted."""

from perfbench import program_api_spans


def read(record):
    if record.get("serve") is None:
        return None
    admits = [s for s in program_api_spans.window_spans(record, "serve.admit") or [] if "prompt_tokens" in s]
    prompt = sum(s["prompt_tokens"] for s in admits)
    if not prompt:
        return None
    return 100.0 * sum(s.get("prefix_hit_tokens", 0) for s in admits) / prompt
