"""The growth of one set of /metrics series over the growth of another,
between two scrapes, times `scale`.

`numerator` and `denominator` are {"metric": family, "where": {label:
value or [values]}}; a histogram's `_sum` and `_count` are families of
their own, so a histogram's mean is the ratio of the two. The scrapes
are those at the window's edges. Nothing to divide by: nothing to read.
The numerator's family must exist on the later page, whatever its labels:
a program that lacks the series (a parent commit from before it was
added) has nothing to read there, which is not a reading of 0."""

from harness.server import delta


def read(ctx, numerator, denominator, scale=1.0):
    before, after = ctx["scrapes"]["window"]
    if not any(family == numerator["metric"] for family, _ in after):
        return None
    den = delta(before, after, denominator["metric"], denominator.get("where"))
    if den <= 0:
        return None
    num = delta(before, after, numerator["metric"], numerator.get("where"))
    return scale * num / den
