"""The quotient of two of the program's ``ServingStats`` counters over the
whole window, times ``scale`` and, where ``scale_by_cfg`` names a key of the
configuration, times that too. A program whose stats lack either counter (the
commit before they existed) reads as nothing."""


def read(ctx, top: str, bottom: str, scale: float = 1.0, scale_by_cfg=None):
    c = ctx.window.counters
    if not c.get(top) or not c.get(bottom):
        return None
    if scale_by_cfg is not None:
        scale = scale * ctx.cfg[scale_by_cfg]
    return scale * c[top] / c[bottom]
