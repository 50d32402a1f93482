"""Device: the share of the traced window in which no operation ran on
the chip, in percent, from ``bench/trace.py``."""


def read(run):
    share = run["trace"].get("idle_share")
    return None if share is None else 100.0 * share
