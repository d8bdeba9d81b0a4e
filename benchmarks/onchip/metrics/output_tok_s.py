"""Tokens emitted in the window over the window (host clock)."""


def read(record):
    return record["tokens_in_window"] / record["window_s"]
