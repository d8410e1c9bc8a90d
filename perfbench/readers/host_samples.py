"""A percentile of one of the window's host-side sample lists."""

import numpy as np


def read(ctx, samples: str, percentile: float, scale: float = 1e3):
    values = getattr(ctx.window, samples)
    if not values:
        return None
    return scale * float(np.percentile(values, percentile))
