"""Coarsening (``core/coarsen.py:coarsen_level``): the share of a level's
real vertices that heavy-edge matching left unmatched (the counter
``hem_unmatched`` over the level's ``n``), in percent, averaged over the
levels of a partition at which a matching ran and then over the window's
partitions.  None where the records carry no such counter."""


def read(run):
    shares = []
    for p in run.get("partitions") or []:
        levels = [lv for lv in p.get("level_counts") or []
                  if lv.get("hem_unmatched") is not None]
        if levels:
            shares.append(sum(lv["hem_unmatched"] / lv["n"] for lv in levels)
                          / len(levels))
    return 100.0 * sum(shares) / len(shares) if shares else None
