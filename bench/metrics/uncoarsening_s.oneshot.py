"""Initial partition, uncoarsening and Jet refinement (``core/initial.py``,
``core/partition.py:uncoarsen_level``, ``core/refine.py``,
``core/rebalance.py``, ``core/connectivity.py``): the program's own
host-clock phase time ``times["uncoarsen_s"]``, which ends with the one
result fetch, mean per partition of the window."""


def read(run):
    parts = run.get("partitions")
    if not parts:
        return None
    return sum(p["times"]["uncoarsen_s"] for p in parts) / len(parts)
