"""Compile, before the window, every kernel shape the window can reach.

The chunker's row count and fphash's (rows, blocks) bucket are static
shapes: each new one traces and compiles.  Set-up calls the kernels'
launch wrappers on zero input, and the wrappers bucket it as they bucket
any other, so the bucketing stays the program's own: this module takes
the chunker's row stride and rows per grid step, the rolling window and
pattern bits, and fphash's block size and rows per launch from the
program."""
from __future__ import annotations

import numpy as np


def warm_chunker(max_rows: int) -> int:
    """Every row bucket of streams up to ``max_rows`` rows, launched once
    each; returns how many."""
    from repro.core.chunker import DEFAULT_PARAMS
    from repro.kernels.chunker import (ROW_STRIDE, SUBLANES,
                                       boundary_bitmap_pallas)
    n = 0
    for rows in range(SUBLANES, max_rows + 1, SUBLANES):
        boundary_bitmap_pallas(np.zeros(rows * ROW_STRIDE, dtype=np.uint8),
                               DEFAULT_PARAMS.window, DEFAULT_PARAMS.q)
        n += 1
    return n


def warm_fphash(max_batch_bytes: int, max_chunk_bytes: int) -> int:
    """Every bucket a batch of chunks of at most ``max_chunk_bytes`` each
    and ``max_batch_bytes`` in all can launch: a power of two chunks, up
    to the kernel's rows per launch, each a power of two blocks long;
    returns how many."""
    from repro import kernels
    from repro.kernels.fphash import _MAX_ROWS, fphash_many_kernel
    from repro.kernels.ref import FP_BLOCK_WORDS
    if kernels.interpret():          # off the chip fphash is numpy
        return 0
    block = 4 * FP_BLOCK_WORDS
    n, blocks = 0, 1
    while (blocks // 2) * block < max_chunk_bytes:
        rows = 1
        while rows <= _MAX_ROWS and (rows // 2) * blocks * block <= max(
                max_batch_bytes, block):
            fphash_many_kernel([bytes(blocks * block)] * rows)
            rows *= 2
            n += 1
        blocks *= 2
    return n
