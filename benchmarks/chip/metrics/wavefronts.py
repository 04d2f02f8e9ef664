"""Wavefronts per solve: the dependency levels the executor ran, each a
barrier between dispatches (the solve's ``wavefronts`` counter).  Mean
over the window's solves that the profiler did not record; nothing to read
where the program does not count them."""


def read(rec):
    counts = [s["counters"]["wavefronts"] for s in rec["host_solves"]
              if "wavefronts" in s["counters"]]
    return sum(counts) / len(counts) if counts else None
