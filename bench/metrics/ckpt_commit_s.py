"""Mean durability lag of the window's periodic saves: from ``save()`` to
the commit on the slow tier, saves still draining at the close waited for."""


def read(rec):
    p = rec["periodic_saves"]
    return sum(x["commit_s"] for x in p) / len(p) if p else None
