"""Requests the simulated object store served during the window, per train
step (the provider's ``requests`` counter)."""


def read(rec):
    s3 = rec.layer.get("s3")
    if not s3 or not rec.layer.get("steps"):
        return None
    return s3["requests"] / rec.layer["steps"]
