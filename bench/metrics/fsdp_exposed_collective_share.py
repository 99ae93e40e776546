"""Share, in %, of the train step's device time in which a chip waited on
collectives: per chip, the self time of the collective operations on the
ops line inside ``jit_train_step`` (all-gather, reduce-scatter,
all-reduce, all-to-all, collective-permute, their ``-start``/``-done``
halves and fusions named after them, a v5e's ``async-collective-start``
and ``-done``; ``bench/trace.py``) over that chip's
``jit_train_step`` device time, averaged over the chips.  A transfer that
overlaps other operations does not count: only the time the chip spends
in the collective's own operations."""

PROGRAM = "jit_train_step"


def read(rec):
    if rec.layer.get("kind") != "train" or rec.trace is None:
        return None
    shares = []
    for chip in rec.trace.chips:
        hits = [v for k, v in chip.items() if k.startswith(PROGRAM)]
        seconds = sum(h[0] for h in hits)
        if seconds <= 0:
            return None
        shares.append(sum(h[1] for h in hits) / seconds)
    if not shares:
        return None
    return sum(shares) / len(shares) * 100
