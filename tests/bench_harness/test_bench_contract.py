"""``BENCHMARK.json`` against the contract's letter, and the data files
against ``BENCHMARK.json``: what the driver refuses before a single run."""
import copy
import importlib.util
import json
import os
import re

import pytest

import conftest
from conftest import BENCH, ROOT

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
# ``hidden_size``, not a bare ``hidden``: ``num_hidden_layers`` is a DEPTH,
# the contract's own example of a key that ``reduced`` may list
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate|latent|state|"
                   r"proj|head_size|head_dim|expansion|experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    files = [w for w in bench["command"] if "/" in w]
    assert all(any(w.startswith(p + "/") for p in bench["paths"])
               for w in files)


def test_names_units_and_entry_keys(bench):
    assert not WIDTH.search("num_hidden_layers")
    assert all(WIDTH.search(k) for k in (
        "hidden_size", "intermediate_size", "kv_lora_rank", "head_dim",
        "ssm_state_size", "num_experts_per_tok"))
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not WIDTH.search(k)
                   for k in c["reduced"])
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
        names.append(m["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        got = [e["name"] for e in bench[group]]
        assert len(got) == len(set(got))
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_cells_configs_and_metrics_hang_together(bench):
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2            # setup_s and one other
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_what_a_per_layer_metric_moves_is_reported_in_each_of_its_cells(
        bench):
    """As the harness finds them: a cell that reads the per-layer metric
    prints the end-to-end metric it should move in its ``--trace 0`` line."""
    files = harness.Files(ROOT)
    ends = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in ends and m["moves"] != "setup_s", m["name"]
        mine = [w["name"] for w in bench["workloads"]
                if m in files.metrics("per_layer", w["name"])]
        assert mine, m["name"]
        for cell in mine:
            reported = {e["name"]
                        for e in files.metrics("end_to_end", cell)}
            assert m["moves"] in reported, (m["name"], cell)


def test_a_cell_build_root_does_not_know_leaves_the_old_cells_standing(
        tmp_path, monkeypatch):
    """A later PR appends its cell to a metric's ``workloads`` list (it
    cannot report an end-to-end metric otherwise): the tiny root keeps the
    cells it stands in for and drops the stranger."""
    later = copy.deepcopy(harness.Files(ROOT).bench)
    later["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "a later PR's cell"})
    listed = [m for m in later["end_to_end"] + later["per_layer"]
              if "workloads" in m]
    for m in listed:
        m["workloads"].append("new-model.new-mix")
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    (checkout / "BENCHMARK.json").write_text(json.dumps(later))
    monkeypatch.setattr(conftest, "ROOT", str(checkout))
    root = tmp_path / "root"
    root.mkdir()
    tiny = harness.Files(conftest.build_root(str(root))).bench
    known = set(conftest.REAL_OF)
    assert {w["name"] for w in tiny["workloads"]} == known
    for m, was in zip(tiny["end_to_end"] + tiny["per_layer"],
                      later["end_to_end"] + later["per_layer"]):
        if "workloads" in was:
            assert set(m["workloads"]) == {
                t for t, real in conftest.REAL_OF.items()
                if real in was["workloads"]}


def test_every_named_file_is_there_and_says_the_same(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:    # what the run applies is not the source's
            assert all(cfg[key] != v for v in cfg["published"].values())
        for kind in ("system", "reference"):
            assert os.path.exists(os.path.join(
                BENCH, kind + "s" if kind == "system" else kind,
                cfg[kind] + ".py"))
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           mix["driver"] + ".py"))
        with open(os.path.join(BENCH, "limits", w["name"] + ".json")) as f:
            assert all(v >= 0 for v in json.load(f).values())


def test_every_per_layer_metric_has_its_reader(bench):
    for m in bench["per_layer"]:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read)
        assert mod.MOVES == m["moves"]
    on_disk = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if f.endswith(".py")}
    assert on_disk == {m["name"] for m in bench["per_layer"]}


def test_the_check_fits_the_contracts_budget(bench):
    runs = 2 + 14 * 24
    total = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
