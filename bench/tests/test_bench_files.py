"""Every configuration, traffic mix, metric reader and limit file that
BENCHMARK.json names loads by its name, and the file keeps the contract's
shape."""
import json
import os
import re

import pytest

import run
import traffic

SPEC = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.fullmatch(n), n


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_loads(conf):
    cfg = run.load_json(os.path.join(run.ROOT, conf["file"]))
    assert cfg["name"] == conf["name"]
    for key in conf["reduced"]:
        assert key in cfg and key in cfg["published"]
    assert os.path.exists(os.path.join(
        run.BENCH, "reference", f"{cfg['reference']}.py"))
    assert callable(run.load_family(cfg).inputs)


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_loads_by_name(cell):
    loaded = run.load_cell(cell["name"])
    assert loaded["traffic"] == traffic.load(cell["traffic"])
    assert loaded["chips"] in (1, 4)
    assert {"loss_gap", "change_gap"} <= set(loaded["limits"])
    assert loaded["end_to_end"] and loaded["per_layer"]
    assert "setup_s" in {m["name"] for m in loaded["end_to_end"]}
    if loaded["traffic"]["workers"] > 1:
        assert "stale_gap" in loaded["limits"]


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_loads_and_reads_nothing_from_nothing(metric):
    read = run.load_reader(metric["name"])
    empty = run._Ctx(reduced=None, steps=0, images=0, window_s=0.0,
                     feed_wait_s=[], cfg={}, traffic={}, chips=1,
                     peaks={}, family=None, scopes=None)
    assert read(empty) is None
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}


@pytest.mark.parametrize("cfg,named", [
    ({"name": "x"}, "names no family"),
    ({"name": "x", "family": "no_such_family"}, "'no_such_family'"),
])
def test_unknown_family_is_a_failure(cfg, named):
    with pytest.raises(run.Failure, match=named):
        run.load_family(cfg)


def test_every_traffic_file_loads():
    for f in os.listdir(os.path.join(run.BENCH, "traffic")):
        assert traffic.load(f[:-len(".json")])["batch"] > 0


def test_render_is_a_function_of_the_seed():
    cfg = run.load_json(os.path.join(run.BENCH, "configs",
                                     "chaos-large.json"))
    family = run.load_family(cfg)
    a = family.render(8, 2**31 + 5)
    b = family.render(8, 2**31 + 5)
    c = family.render(8, 6)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()
    assert a[0].shape == (8, 29, 29, 1) and a[1].dtype.name == "int32"
    d = family.inputs(dict(cfg, train_images=8), 2**31 + 5)
    assert all((x == y).all() for x, y in zip(a, d))
    assert json.dumps(SPEC)
