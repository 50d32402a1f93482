"""Jet refinement (``core/refine.py``): iterations of the Jet loop summed
over the levels of one partition (``lp_iters + rb_iters`` of each
level's stats), mean per partition of the window.  A count."""


def read(run):
    parts = run.get("partitions")
    if not parts:
        return None
    return sum(p["refine_iters"] for p in parts) / len(parts)
