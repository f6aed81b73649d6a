"""What every deployment driver shares: the window's samples and the
end-to-end metrics taken from them."""
from __future__ import annotations

import numpy as np


class Base:
    """A driver fills ``commit_lat`` / ``read_lat`` (seconds),
    ``completed`` (operations of the kinds in ``counted``), ``user_bytes``
    committed and ``stored0`` / ``stored1`` (the store's physical bytes at
    the window's start and at its last commit)."""

    def reset_window(self) -> None:
        self.commit_lat: list[float] = []
        self.read_lat: list[float] = []
        self.completed = 0
        self.user_bytes = 0
        self.stored0 = self.stored1 = self.db.store.stats.physical_bytes

    def metrics(self, window_s: float) -> dict:
        out = {"ops_per_s": self.completed / window_s}
        if self.commit_lat:
            out["commit_p95_ms"] = 1e3 * float(
                np.percentile(self.commit_lat, 95))
            out["stored_per_user_byte"] = ((self.stored1 - self.stored0)
                                           / max(1, self.user_bytes))
        if self.read_lat:
            out["read_p95_ms"] = 1e3 * float(np.percentile(self.read_lat, 95))
        return out

    def timings(self) -> dict:
        """Median and count of each timing, for the earlier lines."""
        return {name: {"median_ms": 1e3 * float(np.median(v)), "n": len(v)}
                for name, v in (("commit", self.commit_lat),
                                ("read", self.read_lat)) if v}
