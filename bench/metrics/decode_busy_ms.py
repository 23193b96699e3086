"""Host busy time of the input pipeline's map functions (the program's
``decode`` spans, summed over threads) per window step."""


def read(rec):
    sp, n = rec["spans"], len(rec["steps"])
    return sp["decode_s"] / n * 1e3 if sp and n else None
