"""Traffic from a mix file, and the closed loop that drives a cell.

A mix file holds parameters only.  ``weights`` draws each operation's
kind at random in those proportions (YCSB's way); ``cycle`` repeats a
fixed pattern of ``[kind, count]`` runs, from its ``start``-th operation
(negative: counted from the end).  Keys are popularity ranks
drawn from a Zipf law over the configuration's key count, rank 0 the
hottest; the driver maps a rank to its key.
"""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

import numpy as np

_BLOCK = 4096


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), theta)
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


class Traffic:
    """An endless stream of ``(kind, rank)`` made from one seed."""

    def __init__(self, mix: dict, n_keys: int, theta: float,
                 rng: np.random.Generator, start: int = 0):
        self.rng = rng
        self.cdf = zipf_cdf(n_keys, theta)
        if ("weights" in mix) == ("cycle" in mix):
            raise ValueError("a mix gives exactly one of weights, cycle")
        if "weights" in mix:
            self.kinds = sorted(mix["weights"])
            p = np.array([mix["weights"][k] for k in self.kinds], float)
            self.p = p / p.sum()
            self.cycle = None
        else:
            self.cycle = [k for k, n in mix["cycle"] for _ in range(int(n))]
            self.kinds = sorted(set(self.cycle))
        self._pos = start % len(self.cycle) if self.cycle else 0

    def _block(self):
        ranks = np.searchsorted(self.cdf, self.rng.random(_BLOCK),
                                side="right").tolist()
        if self.cycle is None:
            kinds = [self.kinds[i] for i in
                     self.rng.choice(len(self.kinds), _BLOCK, p=self.p)]
        else:
            n = len(self.cycle)
            kinds = [self.cycle[(self._pos + i) % n] for i in range(_BLOCK)]
            self._pos = (self._pos + _BLOCK) % n
        return zip(kinds, ranks)

    def __iter__(self):
        while True:
            yield from self._block()


class Spans:
    """Seconds spent in each named call, from the host clock, each call
    also marked in the profiler's trace (when one is being taken) by a
    ``TraceAnnotation`` of the same name."""

    def __init__(self, annotation):
        self._annotation = annotation
        self.seconds: dict[str, float] = defaultdict(float)
        self.on = False              # record only inside the window

    @contextmanager
    def __call__(self, name: str):
        with self._annotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                if self.on:
                    self.seconds[name] += time.perf_counter() - t0


def run_window(driver, traffic, seconds: float) -> dict:
    """One client, closed loop: issue the next operation as soon as the
    last returns, until ``seconds`` have passed.  An operation that raises
    is counted as failed and the loop goes on."""
    ops = iter(traffic)
    attempted = failed = 0
    errors: list[str] = []
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        kind, rank = next(ops)
        attempted += 1
        try:
            driver.do(kind, rank)
        except Exception as e:         # counted, reported, never hidden
            failed += 1
            if len(errors) < 3:
                errors.append(f"{kind}: {type(e).__name__}: {e}")
    t1 = time.perf_counter()
    driver.end_window()
    return {"window_s": t1 - t0, "attempted": attempted, "failed": failed,
            "errors": errors}
