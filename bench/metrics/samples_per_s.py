"""Goodput: images of every step whose loss reached the host inside the
window, over the window; saves, preemptions and resumes stay in it."""


def read(rec):
    return len(rec["steps"]) * rec["batch"] / rec["window_s"]
