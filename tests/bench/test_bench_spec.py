"""BENCHMARK.json against the benchmark's contract, and finding
configurations, mixes and metric readers by name: a new one is new
files and entries only."""

import argparse
import json
import re
import shutil

import pytest

import benchpath  # noqa: F401
from benchkit import cli
from benchkit.spec import ROOT, Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
DATA = json.loads((ROOT / "BENCHMARK.json").read_text())


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_shape():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(DATA["paths"]) <= 16
    for p in DATA["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = DATA["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd[1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in DATA["paths"])
    rs = DATA["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits the driver's day
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_and_cells():
    names = [c["name"] for c in DATA["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in DATA["workloads"]}
    for c in DATA["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in DATA["paths"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and body["published"][k] != body[k]
            assert not (k.endswith("_dim") or k.endswith("_rank")
                        or k in ("d_model", "d_ff", "num_heads",
                                 "num_kv_heads", "num_experts_per_tok"))
        assert c["name"] in used
    cells = [w["name"] for w in DATA["workloads"]]
    assert len(set(cells)) == len(cells) and 1 <= len(cells) <= 24
    pairs = {(w["config"], w["traffic"]) for w in DATA["workloads"]}
    assert len(pairs) == len(cells)
    four = sum(w["chips"] == 4 for w in DATA["workloads"])
    assert four <= max(1, len(cells) // 2)
    for w in DATA["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()


def test_metrics():
    cells = {w["name"] for w in DATA["workloads"]}
    e2e = {m["name"]: m for m in DATA["end_to_end"]}
    allm = DATA["end_to_end"] + DATA["per_layer"]
    assert len({m["name"] for m in allm}) == len(allm)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in DATA["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in allm:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in DATA["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in e2e[m["moves"]].get("workloads", cells)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    spec = Spec.load()
    for w in cells:
        reported = [m["name"] for m in spec.end_to_end(w)]
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.per_layer(w)


def test_names_resolve_to_files():
    spec = Spec.load()
    for w in DATA["workloads"]:
        run = cli.make_run(spec, argparse.Namespace(
            workload=w["name"], seed=1, seconds=1.0, trace=0))
        assert run.config["kind"] in cli.RUNNERS
        assert run.traffic["kind"] in ("lm", "cnn")
        for m in spec.per_layer(w["name"]):
            assert callable(spec.reader(m["name"]))
    with pytest.raises(KeyError):
        spec.workload("no-such.cell")
    with pytest.raises(KeyError):
        spec.reader("no_such_metric")


def test_a_new_config_mix_and_metric_are_files_and_entries(tmp_path):
    """Copy the benchmark, add one configuration, one mix and one
    metric as new files plus entries, and find them all by name."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((bench / "configs" / "binarynet-cifar10.json")
                     .read_text())
    cfg.update(name="wide-cnn", img_size=64)
    (bench / "configs" / "wide-cnn.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "batch64.json").write_text(json.dumps(
        {"kind": "cnn", "loop": "closed", "batch": 64,
         "distinct_batches": 2}))
    (bench / "metrics" / "images_in_window.py").write_text(
        "def read(ctx):\n    return None if ctx is None else "
        "float(ctx.batches)\n")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"].append({"name": "wide-cnn", "source": "x",
                            "file": "bench/configs/wide-cnn.json",
                            "reduced": [], "why": "x"})
    data["workloads"].append({"name": "wide-cnn.batch64",
                              "config": "wide-cnn", "traffic": "batch64",
                              "chips": 1, "why": "x"})
    for m in data["end_to_end"]:
        if m["name"] == "images_per_s":
            m["workloads"].append("wide-cnn.batch64")
    data["per_layer"].append({"name": "images_in_window", "unit": "images",
                              "better": "higher", "source": "host_clock",
                              "layer": "jitted steps",
                              "moves": "images_per_s",
                              "workloads": ["wide-cnn.batch64"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    spec = Spec.load(bench_dir=bench)
    run = cli.make_run(spec, argparse.Namespace(
        workload="wide-cnn.batch64", seed=3, seconds=1.0, trace=1))
    assert run.config["img_size"] == 64 and run.traffic["batch"] == 64
    assert [m["name"] for m in spec.end_to_end("wide-cnn.batch64")] == \
        ["images_per_s", "setup_s"]
    assert [m["name"] for m in spec.per_layer("wide-cnn.batch64")] == \
        ["images_in_window"]
    ctx = type("Ctx", (), {"batches": 7})()
    assert spec.reader("images_in_window")(ctx) == 7.0
    assert spec.reader("images_in_window")(None) is None
