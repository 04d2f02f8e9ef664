"""Host time per solve in the executor's flush and the backend's level
and chain dispatch, net of the planning, ships and row materialisation
inside them (self time of the ``bind.flush``, ``bind.level`` and
``bind.chain`` spans).  Mean over the window's solves that the profiler
did not record."""

import bind_spans


def read(rec):
    return bind_spans.mean_ms(rec, ("bind.flush", "bind.level", "bind.chain"))
