"""The Pallas fphash kernel's share of its HBM roofline: the chunks'
logical bytes (``kernel_bytes`` for fphash) at peak HBM bandwidth, over
the kernel's device time in the trace.  HBM-bound only."""


def read(rec: dict) -> float | None:
    t, peaks = rec["trace"], rec["peaks"]
    nbytes = rec["kernels"]["kernel_bytes.fphash"]
    secs = (t or {}).get("kernel_s", {}).get("fphash", 0.0)
    if not peaks or secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * (nbytes / peaks["hbm_bytes_per_s"]) / secs
