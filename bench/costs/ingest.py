"""The work behind ``ingest_roofline``, counted from the deployment.

Each ingested id is an int32 that the device reads once from HBM; that is
the least any implementation of the ingest layer can move. The chip
publishes no peak for int32 vector compares, so no compute bound is
taken: the roofline here is the memory bound alone.
"""
ID_BYTES = 4


def least_time_s(*, ids: float, peaks: dict) -> float:
    """Seconds one device needs at least to read ``ids`` ids."""
    return ids * ID_BYTES / peaks["hbm_bytes_per_s"]
