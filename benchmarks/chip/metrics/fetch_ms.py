"""Host time per solve reading results back: each fetch's own part
around the flush it starts, and the assembly of the output from its
tiles (self time of the ``bind.fetch`` and ``bind.assemble`` spans).
Mean over the window's solves that the profiler did not record."""

import bind_spans


def read(rec):
    return bind_spans.mean_ms(rec, ("bind.fetch", "bind.assemble"))
