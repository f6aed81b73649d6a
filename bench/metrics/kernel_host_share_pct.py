"""Share of the traced window the host spent in the kernels' launch
wrappers while no operation ran on the device: time inside the program's
``kernel.*`` spans (pad, copy in, launch, copy back) and outside every
device operation, averaged over the devices."""


def read(rec: dict) -> float | None:
    p = rec["program"]
    if not p or p["devices"] == 0 or p["window_s"] <= 0 or not any(
            n.startswith("kernel.") for n in p["spans"]):
        return None
    return 100.0 * p["kernel_host_s"] / p["window_s"]
