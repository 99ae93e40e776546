"""Median time, in ms, from a request's start (its prompt read from the
lake) to its first generated token on the host."""

import numpy as np


def read(rec):
    ttft = rec.layer.get("ttft_s")
    if not ttft:
        return None
    return float(np.median(ttft)) * 1e3
