"""Process start to the end of the warm-up (host clock)."""


def read(record):
    return record["setup_s"]
