"""Milliseconds per step the feed spent cutting token blocks into batches:
the program's ``feed.pack`` spans (``TokenBatcher``) over the window, per
step.  Read only in a traced run:
``bench/drivers/train.py`` turns the program's tracer on for the window,
and ``telemetry.tracing()`` clears the tracer as the window opens and keeps
its spans once it closes."""


def read(rec):
    from repro.core import telemetry
    spans = telemetry.get_tracer().find("feed.pack")
    if rec.layer.get("kind") != "train" or not spans \
            or not rec.layer.get("steps"):
        return None
    return sum(e.dur for e in spans) / rec.layer["steps"] * 1e3
