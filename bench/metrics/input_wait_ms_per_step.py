"""Milliseconds per step the window blocked in ``next()`` on the
``DeviceFeeder`` iterator (pack and host-to-device included), timed by the
benchmark around the call."""


def read(rec):
    wait = rec.layer.get("input_wait_s")
    if wait is None or not rec.layer.get("steps"):
        return None
    return wait / rec.layer["steps"] * 1e3
