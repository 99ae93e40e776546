"""A reference module under a name of its own, for the test that adds a
cell from new files only.  It keeps the contract of ``bench/harness.py``
by handing every call to ``bench/reference/starcoder2.py``, and records
which it was handed."""

from bench.reference import starcoder2 as _starcoder2

Sizes = _starcoder2.Sizes
CONTROLS = _starcoder2.CONTROLS
CALLS = []


def train_steps(*args, **kw):
    CALLS.append("train_steps")
    return _starcoder2.train_steps(*args, **kw)


def served_gaps(*args, **kw):
    CALLS.append("served_gaps")
    return _starcoder2.served_gaps(*args, **kw)
