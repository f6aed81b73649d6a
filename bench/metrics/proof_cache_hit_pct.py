"""Share of the window's proofs that the proof cache served: one less the
program's ``postree.from_root`` spans inside ``engine.prove_member`` (a
miss loads the tree) over the ``engine.prove_member`` spans."""


def read(rec: dict) -> float | None:
    spans = (rec["program"] or {}).get("spans", {})
    proofs = spans.get("engine.prove_member", {}).get("count", 0)
    if proofs <= 0:
        return None
    misses = spans.get("postree.from_root", {}).get("inside", {}).get(
        "engine.prove_member", 0)
    return 100.0 * (1.0 - misses / proofs)
