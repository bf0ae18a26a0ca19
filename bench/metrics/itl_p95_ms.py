"""95th percentile of every gap between two consecutive output tokens of a
request in the window, on the host clock at which the client sees them."""
import numpy as np


def read(record):
    gaps = record.host.get("itl_s")
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
