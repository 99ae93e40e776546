"""Model FLOP utilisation of serving, in %: forward model FLOPs per token
(``bench/flops/<family>.py``, attention at the mean context of a call)
times the absorbed and generated tokens per second of the window, over the
chips' bf16 peak."""


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "serve" or not lay.get("peaks"):
        return None
    rate = lay["processed_tokens"] / lay["window_s"]
    peak = lay["chips"] * lay["peaks"]["peak_flops_bf16"]
    return rate * lay["flops_per_token"] / peak * 100
