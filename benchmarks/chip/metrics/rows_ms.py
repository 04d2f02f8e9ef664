"""Host time per solve concretising lazy rows of fused buckets into
arrays of their own, for a fetch, a ship, an unfused or unstacked
consumer, or a spill (self time of the ``bind.rows`` spans).  Mean over
the window's solves that the profiler did not record."""

import bind_spans


def read(rec):
    return bind_spans.mean_ms(rec, ("bind.rows",))
