"""Model FLOP utilisation of the whole train step, in %: model FLOPs per
token (``bench/flops/<family>.py``: 6 N plus the masked attention products,
no recompute) times trained tokens per second over the traced part of the
window, over the chips' bf16 peak."""


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "train" or not lay.get("peaks"):
        return None
    peak = lay["chips"] * lay["peaks"]["peak_flops_bf16"]
    return lay["traced_tokens_per_s"] * lay["flops_per_token"] / peak * 100
