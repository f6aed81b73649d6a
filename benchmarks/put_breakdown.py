"""Table 4: cost breakdown of a Put — serialization, deserialization,
cryptographic hash, rolling hash, persistence — for String and Blob at
1 KB / 20 KB.  Also reports the Pallas-kernel rolling-hash path, and the
per-chunk vs batched commit pipeline (put vs put_many, §4.6.1), emitting
BENCH_storage.json so the storage perf trajectory is tracked per PR."""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import FBlob, ForkBase, FString
from repro.core.chunk import encode_chunk
from repro.core.chunker import DEFAULT_PARAMS, boundary_bitmap
from repro.core.chunkstore import ChunkStore
from repro.core.fobject import FObject
from repro.core.hashing import sha256
from repro.kernels.ops import boundary_bitmap as pallas_bitmap

from .common import bench, emit

BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_storage.json")


def storage_batching(n_chunks: int = 2048, chunk_size: int = 4096) -> dict:
    """Per-chunk put loop vs one put_many batch, plus the end-to-end value
    commit (POS-Tree build -> single batch) — the §4.6.1 pipeline win."""
    rng = np.random.default_rng(7)
    raws = [encode_chunk(3, rng.bytes(chunk_size)) for _ in range(n_chunks)]
    mb = n_chunks * (chunk_size + 1) / 1e6

    s1 = ChunkStore()
    t0 = time.perf_counter()
    for raw in raws:
        s1.put(raw)
    per_chunk_s = time.perf_counter() - t0

    s2 = ChunkStore()
    t0 = time.perf_counter()
    s2.put_many(raws)
    batched_s = time.perf_counter() - t0

    db = ForkBase()
    value = rng.bytes(8 << 20)
    t0 = time.perf_counter()
    db.put("v", FBlob(value))
    value_s = time.perf_counter() - t0
    st = db.store.stats

    result = {
        "chunks": n_chunks,
        "chunk_size": chunk_size,
        "per_chunk_put_us": per_chunk_s / n_chunks * 1e6,
        "batched_put_us": batched_s / n_chunks * 1e6,
        "per_chunk_put_mb_s": mb / per_chunk_s,
        "batched_put_mb_s": mb / batched_s,
        "batched_speedup": per_chunk_s / batched_s,
        "value_commit_mb_s": len(value) / 1e6 / value_s,
        "value_chunks": st.puts,
        "value_put_batches": st.put_batches,
    }
    emit("storage_put_per_chunk", result["per_chunk_put_us"],
         f"{result['per_chunk_put_mb_s']:.0f}MB/s")
    emit("storage_put_batched", result["batched_put_us"],
         f"{result['batched_put_mb_s']:.0f}MB/s "
         f"x{result['batched_speedup']:.2f}")
    emit("storage_value_commit", value_s * 1e6,
         f"{st.puts}chunks/{st.put_batches}batches "
         f"{result['value_commit_mb_s']:.0f}MB/s")
    return result


def run():
    rng = np.random.default_rng(0)
    for size, tag in [(1024, "1KB"), (20480, "20KB")]:
        payload = rng.bytes(size)
        arr = np.frombuffer(payload, dtype=np.uint8)
        obj = FObject(FString.TYPE, b"key", payload, 3,
                      (b"\x01" * 32,), b"")
        raw = obj.serialize()
        emit(f"serialize_string_{tag}", bench(lambda: obj.serialize(), 2000))
        emit(f"deserialize_string_{tag}",
             bench(lambda: FObject.deserialize(raw, b"\x00" * 32), 2000))
        emit(f"cryptohash_{tag}", bench(lambda: sha256(payload), 2000))
        emit(f"rollinghash_numpy_{tag}",
             bench(lambda: boundary_bitmap(arr, DEFAULT_PARAMS), 500))
        emit(f"rollinghash_pallas_{tag}",
             bench(lambda: pallas_bitmap(arr), 100),
             "Pallas interpreter off the TPU: a CPU timing")
        store = ChunkStore()
        chunkraw = encode_chunk(3, payload)
        n = [0]

        def persist():
            store.put(chunkraw + str(n[0]).encode()); n[0] += 1
        emit(f"persistence_{tag}", bench(persist, 1000))
    batching = storage_batching()
    with open(BENCH_JSON, "w") as f:
        json.dump(batching, f, indent=2)
    print(f"# wrote {BENCH_JSON}")
