"""Wavefronts of a single op per solve: levels no batching can widen, so
each costs a dispatch and a device round trip on the critical path (the
solve's ``single_op_wavefronts`` counter).  Mean over the window's solves
that the profiler did not record; nothing to read where the program does
not count them."""


def read(rec):
    counts = [s["counters"]["single_op_wavefronts"]
              for s in rec["host_solves"]
              if "single_op_wavefronts" in s["counters"]]
    return sum(counts) / len(counts) if counts else None
