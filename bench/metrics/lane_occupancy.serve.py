"""Server admission and bucketing (``launch/partition_serve.py``,
``core/graph.py:BucketAssembler``): real lanes over dispatched lanes, in
percent, over the window's dispatches, from the server's counts of
buckets and filler lanes."""


def read(run):
    lanes = run.get("lanes")
    if not lanes or not lanes["buckets"]:
        return None
    total = lanes["buckets"] * lanes["lanes"]
    return 100.0 * (total - lanes["filler_lanes"]) / total
