"""frame_p50_ms: median of (completion - scheduled arrival) over every
frame due in the window; a frame that failed or never came counts."""

import numpy as np


def read(ctx):
    lat = ctx.latencies_ms()
    return float(np.percentile(lat, 50)) if lat.size else None
