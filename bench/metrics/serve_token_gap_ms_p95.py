"""95th percentile, in ms, of the gaps between consecutive generated tokens
of a request, over every decode step in the window (host clock)."""

import numpy as np


def read(rec):
    gaps = rec.layer.get("token_gaps_s")
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
