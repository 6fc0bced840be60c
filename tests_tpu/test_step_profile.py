"""``tools/step_profile.py --target resilient`` on the chip: the ISSUE 6
acceptance line, where a roofline and an MFU can be measured.

Runs ``main()`` in this process — the pytest process holds the chip, and
a child that needed it would fail or hang."""

import importlib.util
import json
import os

import jax
import pytest

from apex_tpu.observability import meter as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _step_profile():
    spec = importlib.util.spec_from_file_location(
        "step_profile", os.path.join(REPO, "tools", "step_profile.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_resilient_target_fractions_roofline_and_mfu_agreement(tmp_path):
    out = tmp_path / "profile.json"
    rc = _step_profile().main(
        ["--target", "resilient", "--steps", "8", "--json", str(out)]
    )
    assert rc == 0
    p = json.loads(out.read_text())
    assert p["device"]["kind"] == jax.devices()[0].device_kind
    assert p["source"] == "device-ops", p["source"]
    assert p["fraction_sum"] == pytest.approx(1.0, abs=0.02)
    assert set(p["fractions"]) == {"compute", "collective", "host_stall"}
    assert all(0.0 <= v <= 1.0 for v in p["fractions"].values())
    assert set(p["bucket_fractions"]) == set(M.BUCKETS)
    assert p["roofline"][-1]["bucket"] == "total"
    assert p["roofline"][-1]["flops"] > 0
    assert 0.0 < p["mfu"]["meter"] < 1.0
    assert p["mfu"]["agreement"] <= 0.05, p["mfu"]
