"""A number the harness itself took: `values` (compilations counted inside
the window, the peak of device memory after it) or the sum of some of the
set-up clock's `marks` (seconds since the process started, or lengths)."""


def read(ctx, name=None, sum_marks=None, scale=1.0):
    if sum_marks is not None:
        parts = [ctx["marks"].get(m) for m in sum_marks]
        return None if None in parts else sum(parts) * scale
    v = ctx["values"].get(name)
    return None if v is None else v * scale
