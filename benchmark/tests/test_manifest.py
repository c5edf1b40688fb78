"""`BENCHMARK.json` and the data files it names, held to the contract's
letter before the driver holds them to it: PR 22 was refused for one string.
"""
import glob
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head_size|expansion")


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def strings(obj):
    if isinstance(obj, str):
        yield obj
    elif isinstance(obj, dict):
        for k, v in obj.items():
            yield k
            yield from strings(v)
    elif isinstance(obj, list):
        for v in obj:
            yield from strings(v)


def one_line(s, lo=1, hi=200):
    return lo <= len(s) <= hi and s.isascii() and s.isprintable()


def data_files():
    out = [os.path.join(ROOT, "BENCHMARK.json"), os.path.join(BENCH, "peaks.json")]
    for sub in ("configs", "traffic", "tests/configs"):
        out += sorted(glob.glob(os.path.join(BENCH, sub, "*.json")))
    return out


@pytest.mark.parametrize("path", data_files(), ids=lambda p: os.path.relpath(p, ROOT))
def test_every_string_is_printable_ascii(path):
    raw = open(path, "rb").read()
    assert raw.isascii(), f"{path}: bytes outside ASCII"
    for s in strings(load(path)):
        assert s.isascii() and s.isprintable(), f"{path}: {s!r}"


def test_layer_metric_readers_are_ascii():
    for path in glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True):
        assert open(path, "rb").read().isascii(), path


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(manifest["command"]) <= 32
    assert all(one_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    s = manifest["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    # the whole check has to fit with the full 24 cells
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    # the command names no file outside `paths`
    for word in manifest["command"][1:]:
        if "/" in word or word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in manifest["paths"]), word


def test_configs(manifest):
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    assert len({c["name"] for c in configs}) == len(configs)
    assert len({c["file"] for c in configs}) == len(configs)
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]), (c["name"], len(c["source"]))
        assert one_line(c["why"])
        assert PATH.match(c["file"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        on_disk = load(os.path.join(ROOT, c["file"]))
        assert on_disk["name"] == c["name"]
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]
        assert os.path.isfile(
            os.path.join(BENCH, "builders", on_disk["builder"] + ".py")
        )


def test_cells_resolve_to_files(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"]), w["name"]
        cfg = load(os.path.join(ROOT, configs[w["config"]]["file"]))
        assert cfg["chips"] == w["chips"]
        mix = load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert mix["name"] == w["traffic"]
        assert os.path.isfile(os.path.join(BENCH, "drivers", mix["driver"] + ".py"))
        assert mix["limits"], "a mix states the limits of what it compares"


def metric_cells(m, manifest):
    return m.get("workloads", [w["name"] for w in manifest["workloads"]])


def test_metrics(manifest):
    e2e, layer = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    reports = {c: {m["name"] for m in e2e if c in metric_cells(m, manifest)} for c in cells}
    for m in layer:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["source"] in SOURCES and one_line(m["layer"])
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        ), m["name"]
        for c in metric_cells(m, manifest):
            assert m["moves"] in reports[c], (m["name"], c)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(metric_cells(m, manifest)) <= cells
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
        assert any(c in metric_cells(m, manifest) for m in layer)


def test_files_under_paths_are_named_from_names(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    import subprocess

    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard", "benchmark"],
        cwd=ROOT, capture_output=True, text=True,
    ).stdout.split()
    assert listed
    for f in listed:
        assert ok.match(f), f
