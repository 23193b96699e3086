"""Share of the traced window in which no operation ran on a device, mean
over the cell's devices."""


def read(rec):
    d = rec["device"]
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"]) if d else None
