"""Mean ``ResumeResult.restore_s``: reading the checkpoint back and placing
the input pipeline at its position."""


def read(rec):
    r = rec["resumes"]
    return sum(x["restore_s"] for x in r) / len(r) * 1e3 if r else None
