"""Lazy rows of fused buckets concretised into arrays of their own per
solve, each an eager slice dispatch that no backend counter sees.  Mean
over the window's solves that the profiler did not record."""

import bind_spans


def read(rec):
    solves = bind_spans.per_solve(rec)
    if solves is None:
        return None
    return sum(rows for _, rows in solves) / len(solves)
