"""Share of the traced window, in %, in which no operation ran on the
chips of a serve cell: 1 - busy / window, from the device trace."""


def read(rec):
    if rec.layer.get("kind") != "serve" or rec.trace is None:
        return None
    return rec.trace.idle_share * 100
