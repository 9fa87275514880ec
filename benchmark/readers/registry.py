"""What a series of the program's metrics registry gained over the window
(run.py snapshots its exposition text before and after): a histogram's mean
observation (`histogram_mean`: gain of `_sum` over gain of `_count`), or a
counter's gain, optionally per unit of the window's work (`per_work` names a
key of the driver's `work`, e.g. `hashed_user_bytes`)."""


def _series(name, labels):
    if not labels:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return name + "{" + inner + "}"


def _gain(ctx, name, labels):
    key = _series(name, labels)
    if key not in ctx["registry_after"]:
        return None
    return ctx["registry_after"][key] - ctx["registry_before"].get(key, 0.0)


def read(ctx, kind, series, labels=None, per_work=None, scale=1.0):
    if kind == "histogram_mean":
        total = _gain(ctx, series + "_sum", labels)
        count = _gain(ctx, series + "_count", labels)
        return total / count * scale if total is not None and count else None
    gain = _gain(ctx, series, labels)
    if gain is None:
        return None
    if per_work is not None:
        work = ctx["work"].get(per_work)
        return gain / work * scale if work else None
    return gain * scale
