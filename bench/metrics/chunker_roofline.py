"""The Pallas chunker's share of its HBM roofline: the least time the
chip could take to read the logical byte stream (``kernel_bytes`` for the
chunker, input bytes, not padded rows) at peak HBM bandwidth, over the
kernel's device time in the trace.  HBM-bound only: no VPU integer peak
is published for the chip."""


def read(rec: dict) -> float | None:
    t, peaks = rec["trace"], rec["peaks"]
    nbytes = rec["kernels"]["kernel_bytes.chunker"]
    secs = (t or {}).get("kernel_s", {}).get("chunker", 0.0)
    if not peaks or secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * (nbytes / peaks["hbm_bytes_per_s"]) / secs
