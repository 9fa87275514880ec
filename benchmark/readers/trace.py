"""A number of the reduced profiler trace (benchmark/lib/xtrace.py):
`field`, optionally over `per`; or, with `idle_of`, the share of the traced
window in which the named busy time did not run, in percent."""


def read(ctx, field=None, per=None, idle_of=None, scale=1.0):
    trace = ctx.get("trace")
    if not trace or not trace["busy_by_device"]:
        return None
    if idle_of is not None:
        return 100.0 * (1.0 - trace[idle_of] / trace["window_s"])
    if per is not None:
        return trace[field] / trace[per] * scale if trace[per] else None
    return trace[field] * scale
