"""BENCHMARK.json and the files it names: every cell resolves its
configuration, traffic, metric readers, work counter and reference by
name, and the device checks refuse what they must."""
import importlib
import json
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert all(m["bound"] <= 0.25 and m["bound"] >= 0.01
               for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_its_files(cell):
    name = cell["name"]
    spec = json.loads((ROOT / "chipbench/workloads" / f"{name}.json")
                      .read_text())
    assert (spec["config"], spec["traffic"]) == (cell["config"],
                                                 cell["traffic"])
    conf = json.loads((ROOT / "chipbench/configs"
                       / f"{cell['config']}.json").read_text())
    assert conf["name"] == cell["config"]
    entry = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert entry["file"] == f"chipbench/configs/{cell['config']}.json"
    assert entry["reduced"] == conf["reduced"]
    # the decode's named scopes, which the traced run splits its time by
    assert conf["scopes"] and len(set(conf["scopes"])) == len(conf["scopes"])
    mix = json.loads((ROOT / "chipbench/traffic"
                      / f"{cell['traffic']}.json").read_text())
    assert spec["clients"] >= 1 and mix["round"] >= 1
    assert all(p + o <= spec["max_ctx"] for p, o, _ in mix["requests"])
    # the program's prefill splits a prompt of p tokens into p // 1024
    # equal blocks, and fails where they do not divide p
    assert all(p % max(p // 1024, 1) == 0 for p, _, _ in mix["requests"])
    assert len(cell["why"]) <= 200
    importlib.import_module(f"chipbench.work.{conf['work']}")
    importlib.import_module(f"chipbench.reference.{conf['reference']}")
    reported = set()
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            if name in m.get("workloads", [name]):
                reported.add(m["name"])
                if kind == "per_layer":
                    mod = importlib.import_module(
                        f"chipbench.metrics.{m['name']}")
                    assert callable(mod.read)
    assert "setup_s" in reported
    assert len(reported & {m["name"] for m in BENCH["end_to_end"]}) >= 2
    assert reported & {m["name"] for m in BENCH["per_layer"]}


def test_per_layer_arrows_point_at_reported_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(
            target.get("workloads", [c["name"] for c in BENCH["workloads"]]))


@pytest.fixture(scope="module")
def run_module():
    return importlib.import_module("chipbench.run")


def fake(platform, kind):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_no_tpu_is_refused(run_module, monkeypatch):
    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    monkeypatch.setattr(run_module.jax, "devices",
                        lambda: [fake("cpu", "cpu")])
    with pytest.raises(run_module.Refused, match="not a TPU"):
        run_module.device_of(1, False, peaks)


def test_unknown_device_kind_is_refused(run_module, monkeypatch):
    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    monkeypatch.setattr(run_module.jax, "devices",
                        lambda: [fake("tpu", "TPU v99")])
    with pytest.raises(run_module.Refused, match="peaks.json"):
        run_module.device_of(1, False, peaks)
    monkeypatch.setattr(run_module.jax, "devices",
                        lambda: [fake("tpu", "TPU v5 lite")])
    with pytest.raises(run_module.Refused, match="needs 4 chips"):
        run_module.device_of(4, False, peaks)
    assert run_module.device_of(1, False, peaks) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert all("source" in v for v in peaks.values())
