"""Share of the traced window inside the program's ``engine.heads_snapshot``
spans: sync's serialisation of the branch heads and its durable write."""


def read(rec: dict) -> float | None:
    p = rec["program"]
    sp = (p or {}).get("spans", {}).get("engine.heads_snapshot")
    if sp is None or p["window_s"] <= 0:
        return None
    return 100.0 * sp["total_s"] / p["window_s"]
