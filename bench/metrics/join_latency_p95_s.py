"""95th percentile of the latency of every session served in the window
(linear interpolation between order statistics)."""
import numpy as np


def read(rec):
    lat = [s.latency_s for s in rec.served]
    return float(np.percentile(lat, 95)) if lat else None
