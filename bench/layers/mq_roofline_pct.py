"""The bucket kernel's share of its roofline: the least time its handed
(query, set) pairs need at the chip's peaks, over its device time."""
from _spans import named
from _work import bucket_flush, is_mq, least_seconds


def read(observed):
    t = observed["trace"]
    if t is None:
        return None
    secs = t.op_seconds(is_mq)
    if secs <= 0:
        return None
    parent = {s["span_id"]: s for s in named(observed["spans"], "index.search_batch")}
    least = 0.0
    for s in named(observed["spans"], "cascade.stage2a"):
        uq = parent.get(s["parent_id"], {}).get("attrs", {}).get("unique_queries")
        if uq:
            least += least_seconds(*bucket_flush(s["attrs"].get("pairs", 0), uq,
                                                 observed["work"]), observed["peaks"])
    return 100.0 * least / secs if least > 0 else None
