"""Full recomputations of a tree's leaf counts or leaf keys (the program's
``postree_leaf_index_rebuilds_total`` counter, its delta over the window)
per block folded in the traced window (``engine.commit_epoch`` spans)."""


def read(rec: dict) -> float | None:
    rebuilds = rec["counters"].get("postree_leaf_index_rebuilds_total")
    spans = (rec["program"] or {}).get("spans", {})
    blocks = spans.get("engine.commit_epoch", {}).get("count", 0)
    if rebuilds is None or blocks <= 0:
        return None
    return rebuilds / blocks
