"""Share of the traced window inside the program's ``engine.commit_values``
spans: the batched chunk and hash of a put_batch's values (the block's key versions)."""


def read(rec: dict) -> float | None:
    p = rec["program"]
    sp = (p or {}).get("spans", {}).get("engine.commit_values")
    if sp is None or p["window_s"] <= 0:
        return None
    return 100.0 * sp["total_s"] / p["window_s"]
