"""Decode-step calls per ``Server.generate`` call: the program's
``gen.decode_calls`` counter over the serve driver's rounds, one
``generate`` call each.  The counter is process-wide and counts only
inside ``generate`` (the driver's warm-up calls the decode step itself),
and a benchmark process runs one cell, so it holds the window's calls."""


def read(rec):
    from repro.core import telemetry
    calls = telemetry.registry().snapshot().get("gen_decode_calls")
    if rec.layer.get("kind") != "serve" or calls is None \
            or not rec.layer.get("rounds"):
        return None
    return calls / rec.layer["rounds"]
