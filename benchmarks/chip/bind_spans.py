"""Host time per solve in the parts of a Bind executor's work, and the
lazy rows it materialised, from the program's own spans.

The executor times its work in named spans (``repro.core.stats.Span``)
and keeps its most recent finished span trees, each an outermost span
with every span inside it, in the bounded ``repro.core.stats.SPAN_HISTORY``
on the host clock the harness times its solves with.  A tree belongs to
the solve whose start and end enclose it.  Nothing to read where the
program keeps no span history.
"""

from __future__ import annotations

import sys


def per_solve(rec) -> list | None:
    """``[(self seconds by span name, rows materialised)]`` for each of the
    window's solves that the profiler did not record; ``None`` where there
    is none.  Solves older than the history's oldest tree are left out,
    and their number is said on standard error."""
    try:
        from repro.core import stats
    except ImportError:
        return None
    trees = list(getattr(stats, "SPAN_HISTORY", ()))
    if not trees:
        return None
    oldest = trees[0][0]
    solves = rec["host_solves"]
    kept = [s for s in solves if s["start"] >= oldest]
    if len(kept) < len(solves):
        print(f"bind_spans: {len(solves) - len(kept)} of {len(solves)} "
              "window solves are older than the span history and left out",
              file=sys.stderr, flush=True)
    out = []
    for s in kept:
        self_s: dict = {}
        rows = 0
        for start, end, tree, n in trees:
            if s["start"] <= start and end <= s["end"]:
                for name, t in tree.items():
                    self_s[name] = self_s.get(name, 0.0) + t
                rows += n
        out.append((self_s, rows))
    return out or None


def mean_ms(rec, names) -> float | None:
    """Mean over the solves of the summed self time of the spans
    ``names``, in milliseconds."""
    solves = per_solve(rec)
    if solves is None:
        return None
    return 1e3 * sum(sum(s.get(n, 0.0) for n in names)
                     for s, _ in solves) / len(solves)
