"""Milliseconds per step the lake loader's consumer blocked waiting for a
ready unit: the program's ``loader.stall`` spans over the window, per step
(read only in a traced run, where the program's spans are on)."""


def read(rec):
    stall = rec.layer.get("loader_stall_s")
    if stall is None or not rec.layer.get("steps"):
        return None
    return stall / rec.layer["steps"] * 1e3
