"""Mean duration of the program's ``bb_drain`` spans (fast tier to slow
tier) that began in the window."""


def read(rec):
    sp = rec["spans"]
    d = sp["drain_s"] if sp else []
    return sum(d) / len(d) * 1e3 if d else None
