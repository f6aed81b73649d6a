"""Share of the traced window inside the program's ``ledger.maps``
spans: the archival ledger's level-2 and level-1 map puts (the map splice and index rebuild inside them)."""


def read(rec: dict) -> float | None:
    p = rec["program"]
    sp = (p or {}).get("spans", {}).get("ledger.maps")
    if sp is None or p["window_s"] <= 0:
        return None
    return 100.0 * sp["total_s"] / p["window_s"]
