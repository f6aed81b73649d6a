"""The plain reference: ForkBase's content-addressed formats written out
from their definitions, with numpy and the standard library only.

Nothing here imports the system under test.  It rebuilds, from a value
alone, what the engine must have stored for it:

  * content-defined chunk boundaries of a map (cyclic-polynomial rolling
    hash over a 48-byte window, pattern = low 12 bits zero, cut after the
    element that holds it, forced split at 8 x 4 KB);
  * index levels (an entry ends its node when its child cid's first byte
    has its low 6 bits zero, or when the node holds 512 entries);
  * chunk, index-node and meta-chunk encodings;
  * the fphash sponge that names every chunk (its cid).

So a version's root and meta chunk can be recomputed here and compared,
and a membership proof can be re-checked without the engine.
"""
from __future__ import annotations

import bisect
import struct

import numpy as np

WINDOW = 48            # rolling-hash window, bytes
Q = 12                 # leaf pattern bits: 4 KB average chunk
MAX_CHUNK = 8 << Q     # forced split at 8 x the average
INDEX_R = 6            # index pattern bits: 64 average fan-out
INDEX_MAX = 512        # forced index split
HASH_SEED = 0xF0B

META, UINDEX, SINDEX, BLOB, LIST, SET, MAP = range(7)

_GOLD = 0x9E3779B9
_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")


# ------------------------------------------------------------ mixing

def mix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer."""
    x = np.asarray(x, dtype=np.uint32).copy()
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x85EBCA6B)
        x ^= x >> np.uint32(13)
        x *= np.uint32(0xC2B2AE35)
        x ^= x >> np.uint32(16)
    return x


def _rotl(x: np.ndarray, r: np.ndarray) -> np.ndarray:
    r = r & np.uint32(31)
    return (x << r) | (x >> ((np.uint32(32) - r) & np.uint32(31)))


BYTE_TABLE = mix32((np.arange(256, dtype=np.uint64)
                    + np.uint64(HASH_SEED * _GOLD)) & np.uint64(0xFFFFFFFF))


# --------------------------------------------------- chunk boundaries

def pattern_bitmap(data: np.ndarray, block: int = 1 << 24) -> np.ndarray:
    """bool[n]: True at i when the window of WINDOW bytes ending at i has
    a rolling hash whose low Q bits are zero.

    The hash is P_i = XOR_{j<WINDOW} rotl(T[b_{i-j}], j), T = BYTE_TABLE.
    With G_m = rotr(T[b_m], m) and S the running XOR of G, the window's
    terms are S_i ^ S_{i-WINDOW} rotated left by i (all rotations mod 32),
    which this evaluates block by block, carrying S across blocks."""
    data = np.asarray(data, dtype=np.uint8)
    n = data.shape[0]
    out = np.zeros(n, dtype=bool)
    mask = np.uint32((1 << Q) - 1)
    carry = np.uint32(0)
    tail = np.zeros(WINDOW, dtype=np.uint32)   # S of the WINDOW bytes before
    for lo in range(0, n, block):
        hi = min(n, lo + block)
        pos = np.arange(lo, hi, dtype=np.uint32)
        t = BYTE_TABLE[data[lo:hi]]
        r = pos & np.uint32(31)
        g = (t >> r) | (t << ((np.uint32(32) - r) & np.uint32(31)))
        s = np.bitwise_xor.accumulate(g) ^ carry
        prev = np.concatenate([tail, s])[:hi - lo]    # S_{i-WINDOW}
        p = _rotl(s ^ prev, pos)
        out[lo:hi] = (p & mask) == 0
        carry = s[-1]
        tail = np.concatenate([tail, s])[-WINDOW:]
    out[:WINDOW - 1] = False                      # no full window yet
    return out


def element_cuts(lengths: np.ndarray, bitmap: np.ndarray) -> list[int]:
    """Exclusive element indices ending each leaf of an element stream:
    a leaf ends after an element that holds a pattern byte, and before an
    element that would take it past MAX_CHUNK bytes (a leaf always holds
    at least one element)."""
    lengths = np.asarray(lengths, dtype=np.int64)
    ends = np.cumsum(lengths)
    starts = ends - lengths
    marked = np.concatenate([[0], np.cumsum(bitmap, dtype=np.int64)])
    has_pattern = ((marked[ends] - marked[starts]) > 0).tolist()
    ends, starts = ends.tolist(), starts.tolist()
    cuts: list[int] = []
    first, first_byte = 0, 0          # the open leaf's first element
    for e in range(len(ends)):
        if e > first and ends[e] - first_byte > MAX_CHUNK:
            cuts.append(e)
            first, first_byte = e, starts[e]
        if has_pattern[e]:
            cuts.append(e + 1)
            first, first_byte = e + 1, ends[e]
    if not cuts or cuts[-1] != len(ends):
        cuts.append(len(ends))
    return cuts


# --------------------------------------------------------- the sponge

_ROUNDS = 4
_BLOCK = 4096
_STATE = (8, 128)
_INIT = mix32(np.arange(1024, dtype=np.uint32).reshape(_STATE)
              + np.uint32(_GOLD))


def _rotr_c(x: np.ndarray, r: int) -> np.ndarray:
    return (x >> np.uint32(r)) | (x << np.uint32(32 - r))


def _round(s: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        s = s * np.uint32(_GOLD)
        s = s ^ _rotr_c(s, 13)
        s = s + np.roll(s, 1, axis=-1)
        s = s ^ _rotr_c(s, 7)
        s = s + np.roll(s, 1, axis=-2)
    return s


def fphash_many(blobs: list[bytes]) -> list[bytes]:
    """The 256-bit cid of each blob: zero-pad to 4 KB blocks, absorb each
    block into an (8, 128) u32 state by XOR and four rounds, XOR in the
    byte length, two more rounds, XOR the 128 lanes of each row together
    and finalize the 8 words.  Blobs with the same block count are hashed
    together as one array."""
    out: list[bytes | None] = [None] * len(blobs)
    groups: dict[int, list[int]] = {}
    for i, b in enumerate(blobs):
        groups.setdefault(max(1, -(-len(b) // _BLOCK)), []).append(i)
    for nb, idx in groups.items():
        buf = np.zeros((len(idx), nb * _BLOCK), dtype=np.uint8)
        for r, i in enumerate(idx):
            buf[r, :len(blobs[i])] = np.frombuffer(blobs[i], dtype=np.uint8)
        words = buf.view("<u4").astype(np.uint32).reshape(
            len(idx), nb, *_STATE)
        s = np.broadcast_to(_INIT, (len(idx), *_STATE)).copy()
        for b in range(nb):
            s ^= words[:, b]
            for _ in range(_ROUNDS):
                s = _round(s)
        lens = np.array([len(blobs[i]) & 0xFFFFFFFF for i in idx],
                        dtype=np.uint32)
        s = _round(_round(s ^ lens[:, None, None]))
        folded = np.bitwise_xor.reduce(s, axis=-1)
        with np.errstate(over="ignore"):
            folded = mix32(folded ^ (np.arange(8, dtype=np.uint32)
                                     * np.uint32(_GOLD)))
        digests = folded.astype("<u4")
        for r, i in enumerate(idx):
            out[i] = digests[r].tobytes()
    return out  # type: ignore[return-value]


def fphash(data: bytes) -> bytes:
    return fphash_many([data])[0]


# ------------------------------------------------------------ encodings

def pack_kv(k: bytes, v: bytes) -> bytes:
    return _U32.pack(len(k)) + k + _U32.pack(len(v)) + v


def unpack_kv(payload: bytes) -> list[tuple[bytes, bytes]]:
    out, i = [], 0
    while i < len(payload):
        (kl,) = _U32.unpack_from(payload, i)
        k = payload[i + 4:i + 4 + kl]
        i += 4 + kl
        (vl,) = _U32.unpack_from(payload, i)
        out.append((k, payload[i + 4:i + 4 + vl]))
        i += 4 + vl
    return out


def encode_index(entries: list[tuple[bytes, int, bytes | None]],
                 sorted_kind: bool) -> bytes:
    parts = [bytes([SINDEX if sorted_kind else UINDEX])]
    for cid, count, key in entries:
        parts += [cid, _U64.pack(count)]
        if sorted_kind:
            parts += [_U32.pack(len(key)), key]
    return b"".join(parts)


def decode_index(raw: bytes) -> list[tuple[bytes, int, bytes | None]]:
    kind, p, i, out = raw[0], raw[1:], 0, []
    while i < len(p):
        cid = p[i:i + 32]
        (count,) = _U64.unpack_from(p, i + 32)
        i += 40
        key = None
        if kind == SINDEX:
            (kl,) = _U32.unpack_from(p, i)
            key = p[i + 4:i + 4 + kl]
            i += 4 + kl
        out.append((cid, count, key))
    return out


def encode_meta(kind: int, key: bytes, data: bytes, depth: int,
                bases: tuple[bytes, ...], context: bytes = b"") -> bytes:
    return b"".join([bytes([META, kind]), _U32.pack(len(key)), key,
                     _U32.pack(len(data)), data, _U64.pack(depth),
                     _U16.pack(len(bases)), *bases,
                     _U32.pack(len(context)), context])


def decode_meta(raw: bytes) -> dict:
    if raw[0] != META:
        raise ValueError("not a meta chunk")
    p, i = raw[1:], 1
    kind = p[0]
    (kl,) = _U32.unpack_from(p, i)
    key = p[i + 4:i + 4 + kl]
    i += 4 + kl
    (dl,) = _U32.unpack_from(p, i)
    data = p[i + 4:i + 4 + dl]
    i += 4 + dl
    (depth,) = _U64.unpack_from(p, i)
    (nb,) = _U16.unpack_from(p, i + 8)
    i += 10
    bases = tuple(p[i + 32 * j:i + 32 * (j + 1)] for j in range(nb))
    i += 32 * nb
    (cl,) = _U32.unpack_from(p, i)
    return {"kind": kind, "key": key, "data": data, "depth": depth,
            "bases": bases, "context": p[i + 4:i + 4 + cl]}


# ---------------------------------------------------------------- trees

class Tree:
    """Every chunk of one value's POS-Tree, as the reference builds it:
    ``chunks`` maps cid -> raw chunk, ``root`` is the root cid."""

    def __init__(self, root: bytes, chunks: dict[bytes, bytes],
                 leaves: list[bytes]):
        self.root = root
        self.chunks = chunks
        self.leaves = leaves          # leaf cids in order


def _index_levels(kind: int, leaves: list[tuple[bytes, int, bytes | None]],
                  chunks: dict[bytes, bytes]) -> bytes:
    sorted_kind = kind in (SET, MAP)
    level = leaves
    while len(level) > 1:
        groups, cur = [], []
        for e in level:
            cur.append(e)
            if (e[0][0] & ((1 << INDEX_R) - 1)) == 0 or len(cur) >= INDEX_MAX:
                groups.append(cur)
                cur = []
        if cur:
            groups.append(cur)
        raws = [encode_index(g, sorted_kind) for g in groups]
        cids = fphash_many(raws)
        chunks.update(zip(cids, raws))
        level = [(c, sum(e[1] for e in g), g[-1][2])
                 for c, g in zip(cids, groups)]
    return level[0][0]


def map_tree(items: list[tuple[bytes, bytes]]) -> Tree:
    """The POS-Tree of a non-empty map given its (key, value) pairs in
    key order."""
    elems = [pack_kv(k, v) for k, v in items]
    stream = np.frombuffer(b"".join(elems), dtype=np.uint8)
    lengths = np.fromiter((len(e) for e in elems), dtype=np.int64,
                          count=len(elems))
    cuts = element_cuts(lengths, pattern_bitmap(stream))
    raws, entries, start = [], [], 0
    for c in cuts:
        raws.append(bytes([MAP]) + b"".join(elems[start:c]))
        entries.append((c - start, items[c - 1][0]))
        start = c
    cids = fphash_many(raws)
    chunks = dict(zip(cids, raws))
    root = _index_levels(MAP, [(c, n, k) for c, (n, k) in zip(cids, entries)],
                         chunks)
    return Tree(root, chunks, cids)


# ------------------------------------------------------------- proofs

def check_map_proof(root: bytes, nodes: list[bytes], leaf: bytes,
                    key: bytes) -> bytes | None:
    """Walk a membership proof from a trusted map root: every node must
    hash to the cid its parent names, navigation must follow the first
    entry whose max key covers ``key``, and the leaf must hold ``key``.
    Returns the value the proof establishes, or None when it does not
    establish one."""
    raws = list(nodes) + [leaf]
    digests = fphash_many(raws)
    want = root
    for raw, got in zip(nodes, digests):
        if got != want or raw[0] != SINDEX:
            return None
        entries = decode_index(raw)
        keys = [e[2] for e in entries]
        want = entries[min(bisect.bisect_left(keys, key),
                           len(entries) - 1)][0]
    if digests[-1] != want or leaf[0] != MAP:
        return None
    for k, v in unpack_kv(leaf[1:]):
        if k == key:
            return v
    return None
