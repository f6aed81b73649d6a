"""Share of the traced window spent loading a tree for a proof: the self
time of the program's ``postree.from_root`` spans (one per proof-cache
miss)."""


def read(rec: dict) -> float | None:
    p = rec["program"]
    sp = (p or {}).get("spans", {}).get("postree.from_root")
    if sp is None or p["window_s"] <= 0:
        return None
    return 100.0 * sp["self_s"] / p["window_s"]
