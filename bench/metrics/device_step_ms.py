"""Device time of the jitted train step's program runs per window step
(mean over the cell's devices)."""
from bench.trace_reduce import matching


def read(rec):
    d, n = rec["device"], len(rec["steps"])
    if not d or not n:
        return None
    t = matching(d["module_s"], r"train_step")
    return t / n * 1e3 if t > 0 else None
