"""Milliseconds per step the feed spent issuing host-to-device copies: the
program's ``feed.h2d`` spans (``DeviceFeeder``'s producer thread) over the
window, per step.  Read only in a traced run:
``bench/drivers/train.py`` turns the program's tracer on for the window,
and ``telemetry.tracing()`` clears the tracer as the window opens and keeps
its spans once it closes."""


def read(rec):
    from repro.core import telemetry
    spans = telemetry.get_tracer().find("feed.h2d")
    if rec.layer.get("kind") != "train" or not spans \
            or not rec.layer.get("steps"):
        return None
    return sum(e.dur for e in spans) / rec.layer["steps"] * 1e3
