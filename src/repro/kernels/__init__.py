"""Pallas TPU kernels for the paper's Put hot spots: the content-defined
chunking scan (chunker.py) and the cid hash (fphash.py), with their
numpy oracles (ref.py) and the engine hooks (ops.py)."""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True off the TPU.  The one place the kernels' platform decision is
    made, read per call (never snapshotted at import): on a TPU every
    kernel runs compiled; elsewhere the Pallas kernels run in the
    interpreter and ``fphash_many`` takes its vectorized numpy sponge —
    the CPU path the tests use."""
    return jax.default_backend() != "tpu"
