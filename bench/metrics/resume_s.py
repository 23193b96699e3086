"""Mean resume: from building a fresh trainer and pipeline to the first
resumed step's loss on the host."""


def read(rec):
    r = rec["resumes"]
    return sum(x["s"] for x in r) / len(r) if r else None
