"""Share of the traced window spent rebuilding the tree's index levels:
the self time of the program's ``postree.rebuild_index`` spans (one per
fold), less the hash launches inside them."""


def read(rec: dict) -> float | None:
    p = rec["program"]
    sp = (p or {}).get("spans", {}).get("postree.rebuild_index")
    if sp is None or p["window_s"] <= 0:
        return None
    return 100.0 * sp["self_s"] / p["window_s"]
