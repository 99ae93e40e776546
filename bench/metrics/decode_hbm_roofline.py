"""Roofline share, in %, of the serve path's ``decode_step`` program: the
least time one call could take on the chip, the larger of its bytes over
the HBM bandwidth (every weight once, the valid cache, ``decode_bytes`` of
``bench/flops/<family>.py``) and its operations over the bf16 peak, over
the device time per call of ``jit_decode_step`` in the trace."""

PROGRAM = "jit_decode_step"


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "serve" or rec.trace is None or not lay.get("peaks"):
        return None
    got = rec.trace.module(PROGRAM)
    if got is None or got[1] <= 0:
        return None
    per_call = got[0] / got[1]
    pk = lay["peaks"]
    least = max(lay["decode_bytes"] / pk["hbm_bw"],
                lay["decode_flops"] / (lay["chips"] * pk["peak_flops_bf16"]))
    return least / per_call * 100
