"""A number out of the program's process-wide metrics registry: the sum of
``metric`` over the instruments whose labels pass ``where`` (label -> the
values allowed) and ``where_not`` (label -> the values refused), times
``scale``; or, with ``over`` and ``by``, the largest
quotient sum(``metric``) / sum(``over``) among the values of the label
``by`` (the fullest device). The registry outlives the served model, so
what the scheduler last wrote is still there when the readers run. A
program that never wrote the instrument reads as nothing."""


def _sums(metric: str, where: dict, where_not: dict, by):
    from fairness_llm_tpu.telemetry import get_registry

    out = {}
    for m in get_registry().instruments():
        if m.name != metric or not hasattr(m, "value"):
            continue
        if any(m.labels.get(k) not in allowed for k, allowed in where.items()):
            continue
        if any(m.labels.get(k) in refused
               for k, refused in where_not.items()):
            continue
        key = m.labels.get(by) if by else None
        out[key] = out.get(key, 0.0) + float(m.value)
    return out


def read(ctx, metric: str, where=None, where_not=None, over=None, by=None,
         scale: float = 1.0):
    where, where_not = where or {}, where_not or {}
    top = _sums(metric, where, where_not, by)
    if not top:
        return None
    if over is None:
        return scale * sum(top.values())
    bottom = _sums(over, where, where_not, by)
    shares = [top[k] / bottom[k] for k in top if bottom.get(k)]
    return scale * max(shares) if shares else None
