"""Share of the window spent inside ``verify_member``, timed by the benchmark
around each call (host clock)."""


def read(rec: dict) -> float | None:
    secs = rec["spans"].get("verify_member", 0.0)
    if secs <= 0 or rec["window_s"] <= 0:
        return None
    return 100.0 * secs / rec["window_s"]
