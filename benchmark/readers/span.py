"""The mean length of one of the benchmark's own spans (benchmark/lib/
spans.py), which only a traced run records."""


def read(ctx, name, scale=1.0):
    durations = ctx["spans"].get(name)
    return sum(durations) / len(durations) * scale if durations else None
