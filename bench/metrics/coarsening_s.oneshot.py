"""Coarsening (``core/coarsen.py``): the program's own host-clock phase
time ``times["coarsen_s"]``, mean per partition of the window."""


def read(run):
    parts = run.get("partitions")
    if not parts:
        return None
    return sum(p["times"]["coarsen_s"] for p in parts) / len(parts)
