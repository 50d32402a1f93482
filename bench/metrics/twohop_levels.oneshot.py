"""Coarsening (``core/coarsen.py:coarsen_level``): the number of levels of
a partition at which the two-hop matching pass ran (the counter
``twohop``), mean per partition of the window.  A count; None where the
records carry no such counter."""


def read(run):
    counts = []
    for p in run.get("partitions") or []:
        flags = [lv["twohop"] for lv in p.get("level_counts") or []
                 if lv.get("twohop") is not None]
        if flags:
            counts.append(sum(flags))
    return sum(counts) / len(counts) if counts else None
