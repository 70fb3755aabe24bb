"""tools/check_plans.py wired into tier-1: the committed plans must lint
clean, and the linter must actually catch the staleness classes it
advertises (a linter that passes everything protects nothing)."""
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))

import check_plans  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import plan as plan_mod  # noqa: E402

PLANS = sorted(
    os.path.join(_ROOT, "plans", p)
    for p in os.listdir(os.path.join(_ROOT, "plans"))
    if p.endswith(".json")
)


def test_committed_plans_exist():
    assert PLANS, "plans/ must ship tuned artifacts"


@pytest.mark.parametrize("path", PLANS, ids=os.path.basename)
def test_committed_plan_lints_clean(path):
    assert check_plans.check_plan(path) == []


def test_cli_green_on_committed_plans(capsys):
    assert check_plans.main([]) == 0
    assert "ok" in capsys.readouterr().out


def _mutate(tmp_path, mutate, name=None):
    """Copy the first committed plan, apply ``mutate`` to its dict."""
    doc = json.load(open(PLANS[0]))
    mutate(doc)
    p = tmp_path / (name or os.path.basename(PLANS[0]))
    p.write_text(json.dumps(doc))
    return str(p)


def test_catches_wrong_version(tmp_path):
    def m(doc):
        doc["version"] = plan_mod.PLAN_VERSION + 1
        doc["provenance"]["version"] = plan_mod.PLAN_VERSION + 1
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert any("version" in f for f in findings)


def test_catches_stale_config_hash(tmp_path):
    def m(doc):
        doc["provenance"]["config"] = "0" * 12
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert any("stale config hash" in f for f in findings)


def test_catches_stale_hardware_hash(tmp_path):
    def m(doc):
        doc["provenance"]["hardware"] = "0" * 12
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert any("stale hardware hash" in f for f in findings)


def test_catches_unknown_hardware(tmp_path):
    def m(doc):
        doc["provenance"]["hardware_name"] = "tpu-v9"
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert any("not a known HardwareSpec" in f for f in findings)


def test_catches_missing_kv_dtype(tmp_path):
    def m(doc):
        del doc["ops"]["paged"]["kv_dtype"]
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert any("kv_dtype" in f for f in findings)


def test_catches_invalid_knob_value(tmp_path):
    def m(doc):
        doc["ops"]["paged"]["kv_dtype"] = "int3"
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert findings and any("schema" in f for f in findings)


def test_catches_missing_weight_dtype(tmp_path):
    def m(doc):
        del doc["ops"]["matmul"]["weight_dtype"]
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert any("weight_dtype" in f for f in findings)


def test_catches_invalid_weight_dtype(tmp_path):
    def m(doc):
        doc["ops"]["matmul"]["weight_dtype"] = "int3"
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert findings and any("schema" in f for f in findings)


def test_catches_missing_provenance(tmp_path):
    def m(doc):
        del doc["provenance"]
    findings = check_plans.check_plan(_mutate(tmp_path, m))
    assert any("provenance" in f for f in findings)


def test_catches_wrong_filename(tmp_path):
    findings = check_plans.check_plan(
        _mutate(tmp_path, lambda doc: None, name="renamed.json"))
    assert any("filename" in f for f in findings)


def test_current_registry_is_consistent():
    """The linter's own premise: every named spec hashes to itself and
    every committed provenance names a real config."""
    specs = check_plans._hardware_registry()
    assert "tpu-v5e" in specs
    for path in PLANS:
        doc = json.load(open(path))
        prov = doc["provenance"]
        assert prov["hardware_name"] in specs
        configs.get(prov["config_name"])   # must not raise
