"""Faults planted under the timed path, and the control: the ways the
program could be wrong that the check must catch.

Each plant patches the program (never the benchmark) and is armed only
while the window runs, so set-up builds a sound state and the check reads
what the broken window left behind.

  control         ``sync`` flushes the chunks but skips the heads snapshot
                  (the durability guarantee broken: a cheaper sync a later
                  change could be tempted by)
  drop_write      a write returns with the state unchanged
                  (``ForkBase.put``, which a fold commits through, returns
                  the old head; ``ForkBase.put_batch`` returns each
                  request's old head; ``live.put`` does nothing)
  half_batch      half of a batch left out: a put's chunk batch reaches the
                  store half written; a block's fold folds half its updates
  alter_answer    an answer altered where it is produced: a live table's
                  read, a proof's claimed value, and every point read of a
                  committed value off the live path (``FBlob.read``,
                  ``FMap.get``, ``FList.get``, ``FSet.contains``, a
                  primitive value's ``ValueHandle.string`` and
                  ``ValueHandle.primitive``)
  kernel_output   a kernel's output altered: the chunker reports one more
                  boundary, fphash one digest byte flipped

The plants patch the engine's general verbs, so a deployment whose reads
and writes do not go through the live table is held to them too.  The
exchange between chips does not exist in one-chip cells.
"""
from __future__ import annotations

import contextlib
import dataclasses

PLANTS = ("control", "drop_write", "half_batch", "alter_answer",
          "kernel_output")


def _flip(b: bytes) -> bytes:
    return bytes([b[0] ^ 1]) + b[1:] if b else b"\x01"


def _patches(name: str, armed: dict) -> list[tuple[object, str, object]]:
    from repro import proof
    from repro.core import FBlob, FList, FMap, ForkBase, FSet
    from repro.core.branch import DEFAULT_BRANCH
    from repro.core.db import ValueHandle, _k
    from repro.kernels import fphash as fp
    from repro.kernels import ops
    from repro.live.table import LiveTable
    from repro.storage import WriteBuffer

    def when_armed(orig, broken):
        def call(*a, **k):
            return (broken if armed["on"] else orig)(*a, **k)
        return call

    if name == "control":
        orig = ForkBase.sync

        def sync(self):
            self.store.flush()
        return [(ForkBase, "sync", when_armed(orig, sync))]
    if name == "drop_write":
        def put(self, key, value, branch=None, **_):
            head = self.branches.head(_k(key), branch or DEFAULT_BRANCH)
            return head if head is not None else b"\0" * 32

        def put_batch(self, requests):
            return [put(self, r[0], r[1], r[2] if len(r) > 2 else None)
                    for r in requests]

        def live_put(self, k, v):
            return None
        return [(ForkBase, "put", when_armed(ForkBase.put, put)),
                (ForkBase, "put_batch",
                 when_armed(ForkBase.put_batch, put_batch)),
                (LiveTable, "put", when_armed(LiveTable.put, live_put))]
    if name == "half_batch":
        flush, fold = WriteBuffer.flush, LiveTable.fold

        def half_flush(self):
            keep = max(1, len(self._raws) // 2)
            self._raws, self._cids = self._raws[:keep], self._cids[:keep]
            return flush(self)

        def half_fold(self, **k):
            items = list(self._dirty.items())
            self._dirty = dict(items[:max(1, len(items) // 2)])
            return fold(self, **k)
        return [(WriteBuffer, "flush", when_armed(flush, half_flush)),
                (LiveTable, "fold", when_armed(fold, half_fold))]
    if name == "alter_answer":
        verify, read, contains = (proof.verify_member, FBlob.read,
                                  FSet.contains)
        string, primitive = ValueHandle.string, ValueHandle.primitive

        def flipped(orig):
            def call(*a, **k):
                v = orig(*a, **k)
                return None if v is None else _flip(v)
            return call

        def bad_verify(root, p):
            c = verify(root, p)
            return dataclasses.replace(c, value=_flip(c.value))

        def bad_read(self, *a, **k):
            # a blob with no tree yet reads back its own new bytes, which
            # FBlob.commit builds the blob's first tree from: a write, not
            # an answer
            v = read(self, *a, **k)
            return v if self._tree is None else _flip(v)

        def bad_contains(self, k):
            return not contains(self, k)

        def bad_string(self):
            s = string(self)
            return type(s)(_flip(s.value))

        def bad_primitive(self):
            return primitive(ValueHandle(self.db, dataclasses.replace(
                self.obj, data=_flip(self.obj.data))))
        return [(LiveTable, "get", when_armed(LiveTable.get,
                                              flipped(LiveTable.get))),
                (proof, "verify_member", when_armed(verify, bad_verify)),
                (FBlob, "read", when_armed(read, bad_read)),
                (FMap, "get", when_armed(FMap.get, flipped(FMap.get))),
                (FList, "get", when_armed(FList.get, flipped(FList.get))),
                (FSet, "contains", when_armed(contains, bad_contains)),
                (ValueHandle, "string", when_armed(string, bad_string)),
                (ValueHandle, "primitive",
                 when_armed(primitive, bad_primitive))]
    if name == "kernel_output":
        bitmap, many = ops.boundary_bitmap_pallas, fp.fphash_many

        def bad_bitmap(data, window, q, *a, **k):
            out = bitmap(data, window, q, *a, **k)
            if len(out) > window:
                out[len(out) // 2] = not out[len(out) // 2]
            return out

        def bad_many(blobs):
            return [_flip(d) for d in many(blobs)]
        return [(ops, "boundary_bitmap_pallas", when_armed(bitmap, bad_bitmap)),
                (fp, "fphash_many", when_armed(many, bad_many))]
    raise ValueError(f"no plant {name!r}; one of {PLANTS}")


@contextlib.contextmanager
def planted(name: str):
    """Patch the program with the plant ``name``, armed while the window
    runs (``fbbench.loop.run_window``).  Yields the switch: setting its
    ``"on"`` arms the plant outside a window."""
    from fbbench import loop
    armed = {"on": False}
    window = loop.run_window

    def run_window(*a, **k):
        armed["on"] = True
        try:
            return window(*a, **k)
        finally:
            armed["on"] = False
    patches = _patches(name, armed) + [(loop, "run_window", run_window)]
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    try:
        for obj, attr, new in patches:
            setattr(obj, attr, new)
        yield armed
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)
