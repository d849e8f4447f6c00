"""Device milliseconds per decode step that each chip spends in the
collectives of the decode program (``all-reduce``, ``all-gather``,
``reduce-scatter``, ``collective-permute``, ``all-to-all`` by their HLO
names), in the traced slice.  Reads every chip's plane and averages over
them.  Silent where no collective ran: a program on one chip has none."""

import mesh_work


def read(run):
    s = mesh_work.collective_s_per_decode(run)
    return None if s is None else 1e3 * s
