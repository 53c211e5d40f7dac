"""frame_p95_ms: 95th percentile of (completion - scheduled arrival) over
every frame due in the window; a frame that failed or never came counts."""

import numpy as np


def read(ctx):
    lat = ctx.latencies_ms()
    return float(np.percentile(lat, 95)) if lat.size else None
