"""Seconds from the start of the process to the opening of the measured window:
import, weights from the seed, compile or cache load, warm-up, correctness check."""


def read(record):
    return record["setup_s"]
