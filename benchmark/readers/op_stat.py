"""A number each op of the window recorded: its wall time on the benchmark's
clock (`wall_s`) or a field of the stats the entry returned (`stats.<path>`,
e.g. `stats.stage_seconds.get`), optionally less another field, reduced
over the ops (`mean`, `max`, or `ratio_of_sums` against `per`)."""


def _get(op: dict, path: str):
    value = op
    for part in path.split("."):
        if not isinstance(value, dict) or part not in value:
            return None
        value = value[part]
    return value


def read(ctx, field, minus=None, per=None, reduce="mean", scale=1.0):
    values, weights = [], []
    for op in ctx["ops"]:
        v = _get(op, field)
        if v is None:
            continue
        if minus is not None:
            m = _get(op, minus)
            if m is None:
                continue
            v -= m
        values.append(v)
        if per is not None:
            weights.append(_get(op, per) or 0)
    if not values:
        return None
    if reduce == "max":
        return max(values) * scale
    if reduce == "ratio_of_sums":
        return sum(values) / sum(weights) * scale if sum(weights) else None
    return sum(values) / len(values) * scale
