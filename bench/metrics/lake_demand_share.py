"""Share, in %, of the object store's simulated seconds in the window that
readers waited for on demand rather than prefetched ahead:
``sim_s_demand / (sim_s_demand + sim_s_prefetch)``."""


def read(rec):
    s3 = rec.layer.get("s3")
    if not s3:
        return None
    total = s3["sim_s_demand"] + s3["sim_s_prefetch"]
    if total <= 0:
        return None
    return s3["sim_s_demand"] / total * 100
