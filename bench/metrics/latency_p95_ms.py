"""95th percentile, over every acquisition of the window, of arrival to
image on the host (host clock; the arrival is the schedule's instant)."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_s, 95)) * 1e3
