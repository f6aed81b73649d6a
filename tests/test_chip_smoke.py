"""chip_smoke.py rehearsed on the CPU: every phase runs at a tiny size,
every root matches the host reference, and without a TPU the script
never reports a result."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", mod)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_matches_host_reference(smoke, tmp_path, capsys):
    rc = smoke.main(["--rehearse", "--out", str(tmp_path / "smoke")])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out
    phases = {rec["phase"]: rec for rec in
              (json.loads(line[len("phase "):])
               for line in out.splitlines() if line.startswith("phase "))}
    assert list(phases) == ["kernels", "wiki", "ledger"]
    assert phases["wiki"]["roots_match_host"]
    assert phases["ledger"]["roots_match_host"]
    assert phases["ledger"]["proofs_verified"] == smoke.TINY.proofs
    assert not (tmp_path / "smoke").exists()


def test_no_result_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""
