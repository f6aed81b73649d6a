"""Trace reduction, the table of peaks and the per-layer readers.

``data/v5e_probe.xplane.pb`` is a profiler trace recorded on one TPU v5
lite chip: three ``ForkBase.put``s of 20 KB, 70 KB and 300 KB blobs on the
device path inside a ``bench.window`` annotation."""
import json
from pathlib import Path

import pytest

import run
from fbbench import trace

DATA = Path(__file__).resolve().parent / "data"
NAMES = trace.load_names()


def test_recorded_chip_trace_reduces_to_kernels_and_gaps():
    got = trace.read_trace(DATA / "v5e_probe.xplane.pb", NAMES, chips=1,
                           activities={"put"})
    assert got["devices"] == 1
    assert 0.05 < got["window_s"] < 1.0
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["kernel_s"]["chunker"] > 0 and got["kernel_s"]["fphash"] > 0
    # every device operation in this window is one of the two kernels
    assert sum(got["kernel_s"].values()) == pytest.approx(
        sum(s for _, s in got["device_ops"]), rel=1e-9)
    assert {n.split()[0] for n, _ in got["device_ops"]} == {"chunker",
                                                           "fphash"}
    idle = dict(got["idle_gaps"])
    assert set(idle) <= {"put", "client"} and idle["put"] > 0
    assert sum(idle.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)


@pytest.mark.parametrize("activity", ["put", "smallbank.transfer"])
def test_reduction_of_hand_made_events(activity):
    """Idle gaps go to whatever annotation the caller names, and to no
    other host event."""
    ms = 1_000_000
    device = {"/device:TPU:0": [
        ("%_run.1 = u8[8,4992]{1,0} custom-call(u8[8,5120] %rows.1)",
         2 * ms, 3 * ms),
        ("%_run.1 = u32[1,8,128]{2,1,0} custom-call(s32[4] %lengths.1, "
         "u32[4,8,128] %words.1, u32[8,128] %init.1)", 5 * ms, 7 * ms),
        ("copy.3", 6 * ms, 8 * ms),          # overlaps the hash
        ("late", 11 * ms, 12 * ms)]}          # outside the window
    host = [("bench.window", 0, 10 * ms), (activity, 1 * ms, 9 * ms),
            ("other", 0, 10 * ms)]
    got = trace.reduce_events(device, host, NAMES, {activity})
    assert got["window_s"] == pytest.approx(0.010)
    assert got["busy_s"] == pytest.approx(0.004)         # [2,3] + [5,8]
    assert got["kernel_s"] == pytest.approx({"chunker": 0.001,
                                             "fphash": 0.002})
    assert dict(got["idle_gaps"]) == pytest.approx(
        {activity: 0.004, "client": 0.002})
    assert dict(got["device_ops"])["copy.3"] == pytest.approx(0.002)


def test_peaks_are_keyed_by_device_kind():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        run.peaks_for("TPU v9 imaginary")


def _reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py", name)


def test_roofline_and_idle_readers():
    peaks = json.loads((run.BENCH / "peaks.json").read_text())["TPU v5 lite"]
    rec = {"window_s": 2.0, "ops": 100,
           "spans": {"sync": 0.5, "prove_member": 1.0, "verify_member": 0.1},
           "kernels": {"kernel_bytes.chunker": 819_000,
                       "kernel_bytes.fphash": 0,
                       "kernel_launches.chunker": 30,
                       "kernel_launches.fphash": 20},
           "peaks": peaks,
           "trace": {"window_s": 2.0, "busy_s": 0.5, "devices": 1,
                     "kernel_s": {"chunker": 4e-6}}}
    # 819 KB at 819 GB/s is 1 us, in 4 us of kernel time
    assert _reader("chunker_roofline").read(rec) == pytest.approx(25.0)
    assert _reader("fphash_roofline").read(rec) is None    # nothing to read
    assert _reader("device_idle_pct").read(rec) == pytest.approx(75.0)
    assert _reader("kernel_launches_per_op").read(rec) == pytest.approx(0.5)
    assert _reader("sync_share_pct").read(rec) == pytest.approx(25.0)
    assert _reader("fold_share_pct").read(rec) is None
    assert _reader("prove_share_pct").read(rec) == pytest.approx(50.0)
    assert _reader("verify_share_pct").read(rec) == pytest.approx(5.0)
    rec["trace"] = None
    assert _reader("device_idle_pct").read(rec) is None
