"""Non-pad target tokens of all batches the trainer took inside the window, over
the whole window (host clock between two ``block_until_ready``), per chip."""


def read(record):
    t = record.get("train")
    if t is None:
        return None
    return t["nonpad_target_tokens"] / record["window_s"] / record["cell"]["chips"]
