"""Mean time a window step waited for its batch (``Trainer.timer``)."""


def read(rec):
    s = rec["steps"]
    return sum(x["data_wait_s"] for x in s) / len(s) * 1e3 if s else None
