"""Each plant of ``fbbench/plants.py`` reaches the engine's general verbs,
off the live table, so a deployment that reads and writes through them is
held to the plants as the ledger cells are.

On a small durable engine with the device path switched on (the Pallas
chunker interpreted off the TPU), the plant armed: ``ForkBase.put`` of an
``FBlob``, an ``FMap``, an ``FList``, an ``FSet``, a string and an
integer, an edit of the blob, ``ForkBase.put_batch`` of a blob and a map,
``sync``; then every value read back through its read verb, each
version's meta chunk and each map's tree held against the plain
reference, and the durable root reopened.  A plain model says what each
must give; a plant has to make one of them differ."""
from contextlib import nullcontext

import numpy as np
import pytest

from fbbench import reference as ref
from fbbench.plants import PLANTS, planted

# what each plant has to break, at the least
EXPECTED = {
    None: set(),
    "control": {"durable"},
    "drop_write": {"put", "put_batch"},
    "half_batch": {"chunks"},
    "alter_answer": {"FBlob.read", "FMap.get", "FList.get", "FSet.contains",
                     "ValueHandle.string", "ValueHandle.primitive"},
    "kernel_output": {"chunks", "uids"},
}
SINGLE = (b"blob", b"map", b"list", b"set", b"str", b"int")
BATCH = (b"batch.blob", b"batch.map")


def _model() -> dict:
    rng = np.random.default_rng(16)
    return {b"blob": rng.bytes(12_000),
            b"map": {rng.bytes(20): rng.bytes(100) for _ in range(200)},
            b"list": [rng.bytes(40) for _ in range(100)],
            b"set": {rng.bytes(20) for _ in range(100)},
            b"str": rng.bytes(64),
            b"int": 7_000_000_019,
            b"batch.blob": rng.bytes(9_000),
            b"batch.map": {rng.bytes(20): rng.bytes(100) for _ in range(150)},
            "edit": (300, 50, rng.bytes(80)),
            "absent": rng.bytes(20)}


def _writes(db, model: dict) -> tuple[dict[bytes, bytes], set[str]]:
    """Every write of the scenario; returns each key's uid as the engine
    reported it, and what already showed: the blob's first version
    missing a chunk when the edit reads it back."""
    from repro.core import FBlob, FInt, FList, FMap, FSet
    from repro.errors import ChunkMissing
    bad = set()
    uids = {b"blob": db.put(b"blob", FBlob(model[b"blob"])),
            b"map": db.put(b"map", FMap(model[b"map"])),
            b"list": db.put(b"list", FList(model[b"list"])),
            b"set": db.put(b"set", FSet(sorted(model[b"set"]))),
            b"str": db.put(b"str", model[b"str"]),
            b"int": db.put(b"int", FInt(model[b"int"]))}
    pos, n, data = model["edit"]

    def edit():
        head = db.get(b"blob")
        blob = head.blob() if head is not None else FBlob(model[b"blob"])
        blob.replace(pos, n, data)
        return db.put(b"blob", blob)
    try:
        uids[b"blob"] = edit()
    except ChunkMissing:          # the first version lost a chunk
        bad.add("chunks")
    uids.update(zip(BATCH, db.put_batch(
        [(b"batch.blob", FBlob(model[b"batch.blob"])),
         (b"batch.map", FMap(model[b"batch.map"]))])))
    db.sync()
    return uids, bad


def _answers(db, model: dict) -> set[str]:
    """The read verbs whose answers differ from the model."""
    pos, n, data = model["edit"]
    blob = model[b"blob"][:pos] + data + model[b"blob"][pos + n:]
    bad = set()
    if (db.get(b"blob").blob().read() != blob or db.get(
            b"batch.blob").blob().read() != model[b"batch.blob"]):
        bad.add("FBlob.read")
    for key in (b"map", b"batch.map"):
        m = db.get(key).map()
        if any(m.get(k) != v for k, v in model[key].items()):
            bad.add("FMap.get")
    lst = db.get(b"list").list()
    if any(lst.get(i) != v for i, v in enumerate(model[b"list"])):
        bad.add("FList.get")
    s = db.get(b"set").set()
    if not all(s.contains(k) for k in model[b"set"]) or s.contains(
            model["absent"]):
        bad.add("FSet.contains")
    if db.get(b"str").string().value != model[b"str"]:
        bad.add("ValueHandle.string")
    if db.get(b"int").primitive().value != model[b"int"]:
        bad.add("ValueHandle.primitive")
    return bad


def _findings(root, model: dict, db) -> set[str]:
    """What differs from the plain model, by where it shows."""
    from repro.core import ForkBase
    from repro.core.branch import DEFAULT_BRANCH
    from repro.errors import ReproError
    uids, bad = _writes(db, model)
    # the heads: each key's newest version is the one its write returned
    for key in SINGLE + BATCH:
        if db.branches.head(key, DEFAULT_BRANCH) in (None, bytes(32)):
            bad.add("put_batch" if key in BATCH else "put")
    # the answers
    try:
        bad |= _answers(db, model)
    except (AttributeError, ReproError):    # a version or chunk is gone
        bad.add("answers")
    # the chunks: every version's meta chunk hashes to its uid by the
    # reference; the store holds every chunk of each map's reference tree,
    # and the map's version names that tree's root
    for uid in uids.values():
        if not db.store.has_many([uid])[0] or ref.fphash(
                db.store.get(uid)) != uid:
            bad.add("uids")
    for key in (b"map", b"batch.map"):
        tree = ref.map_tree(sorted(model[key].items()))
        if not all(db.store.has_many(list(tree.chunks))):
            bad.add("chunks")
        elif not db.store.has_many([uids[key]])[0] or ref.decode_meta(
                db.store.get(uids[key]))["data"] != tree.root:
            bad.add("chunks")
    # durability: a reopen of the durable root finds every head
    re = ForkBase(durable_root=str(root))
    if any(re.branches.head(k, DEFAULT_BRANCH) != uids[k] for k in uids):
        bad.add("durable")
    re.store.close()
    return bad


@pytest.mark.parametrize("plant", [None, *PLANTS])
def test_plant_reaches_the_general_verbs(plant, tmp_path):
    from repro.core import ForkBase, hashing
    from repro.kernels import ops
    model = _model()
    db = ForkBase(durable_root=str(tmp_path))
    try:
        with planted(plant) if plant else nullcontext({}) as armed:
            # as run_cell does: the device path is chosen under the plant
            ops.use_pallas_chunker(True)
            hashing.use_fphash()
            armed["on"] = True
            bad = _findings(tmp_path, model, db)
            armed["on"] = False
    finally:
        ops.use_pallas_chunker(False)
        hashing.use_sha256()
    if plant is None:
        assert bad == set()
    else:
        assert EXPECTED[plant] <= bad, bad
