"""95th percentile of the window's step times: from asking for the batch
to the loss on the host, plus the blocked part of the save the step made.
The first step after a resume belongs to the resume and is left out."""
import numpy as np


def read(rec):
    s = [x["s"] + x["save_blocked_s"] for x in rec["steps"]
         if x["kind"] != "resumed"]
    return float(np.percentile(s, 95)) * 1e3 if s else None
