"""What a series of the program's metrics registry read when the window
started (run.py's first snapshot): everything the process did to it during
set-up. Series of the same name are summed over the labels not given (a
histogram's `_sum` or `_count` over its children); nothing where the
program registers no such series."""


def read(ctx, series, labels=None, scale=1.0):
    want = {f'{k}="{v}"' for k, v in (labels or {}).items()}
    values = [
        value for key, value in ctx["registry_before"].items()
        if key.partition("{")[0] == series
        and want <= set(key.partition("{")[2].rstrip("}").split(","))
    ]
    return sum(values) * scale if values else None
