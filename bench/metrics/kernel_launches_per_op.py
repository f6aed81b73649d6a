"""Kernel launches (chunker and fphash, from the program's
``kernel_launches`` counters) per operation completed in the window."""


def read(rec: dict) -> float | None:
    k = rec["kernels"]
    launches = k["kernel_launches.chunker"] + k["kernel_launches.fphash"]
    if rec["ops"] <= 0 or launches <= 0:
        return None
    return launches / rec["ops"]
