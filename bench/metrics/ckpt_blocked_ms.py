"""Mean time the training thread was blocked in a periodic save."""


def read(rec):
    p = rec["periodic_saves"]
    return sum(x["blocked_s"] for x in p) / len(p) * 1e3 if p else None
