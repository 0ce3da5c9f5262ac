"""The value a /metrics family had at the window's first scrape, times
`scale`: what the serving child had counted between its own start and the
first measured request (set-up's layers; the loader child, where a run
has one, is another process and is not in it). Labels are summed over, or
chosen by `where` as in counter_ratio. A program that lacks the family:
nothing to read."""

from harness.server import series_sum


def read(ctx, metric, where=None, scale=1.0):
    first = ctx["scrapes"]["window"][0]
    if not any(family == metric for family, _ in first):
        return None
    return scale * series_sum(first, metric, where)
