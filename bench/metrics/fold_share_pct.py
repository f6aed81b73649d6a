"""Share of the window spent inside ``commit_epoch``, timed by the benchmark
around each call (host clock)."""


def read(rec: dict) -> float | None:
    secs = rec["spans"].get("commit_epoch", 0.0)
    if secs <= 0 or rec["window_s"] <= 0:
        return None
    return 100.0 * secs / rec["window_s"]
