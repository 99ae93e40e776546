"""Milliseconds per step the step's thread blocked on ``DeviceFeeder`` for
its next device batch: the program's ``feed.wait`` spans over the window,
per step: the inside measure of what ``input_wait_ms_per_step`` times from
outside.  Read only in a traced run:
``bench/drivers/train.py`` turns the program's tracer on for the window,
and ``telemetry.tracing()`` clears the tracer as the window opens and keeps
its spans once it closes."""


def read(rec):
    from repro.core import telemetry
    spans = telemetry.get_tracer().find("feed.wait")
    if rec.layer.get("kind") != "train" or not spans \
            or not rec.layer.get("steps"):
        return None
    return sum(e.dur for e in spans) / rec.layer["steps"] * 1e3
