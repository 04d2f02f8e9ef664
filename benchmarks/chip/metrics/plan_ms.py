"""Host time per solve in planning: plan resolution and its cache probes,
and the placement of the workflow's initial arrays (self time of the
executor's ``bind.plan`` spans).  Mean over the window's solves that the
profiler did not record."""

import bind_spans


def read(rec):
    return bind_spans.mean_ms(rec, ("bind.plan",))
