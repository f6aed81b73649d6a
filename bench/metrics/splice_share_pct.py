"""Share of the traced window in the tree splice's own code: the self time
of the program's ``postree.splice`` spans (one per locality cluster of a
fold), less the kernel launches and other spans inside them."""


def read(rec: dict) -> float | None:
    p = rec["program"]
    sp = (p or {}).get("spans", {}).get("postree.splice")
    if sp is None or p["window_s"] <= 0:
        return None
    return 100.0 * sp["self_s"] / p["window_s"]
