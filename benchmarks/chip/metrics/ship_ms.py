"""Host time per solve staging and running tile ships, lowered to
collectives across chips (self time of the ``bind.ship`` spans, net of
the rows they materialise).  Mean over the window's solves that the
profiler did not record."""

import bind_spans


def read(rec):
    return bind_spans.mean_ms(rec, ("bind.ship",))
