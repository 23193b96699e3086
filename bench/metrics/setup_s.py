"""Process start to the first step of the window."""


def read(rec):
    return rec["setup_s"]
