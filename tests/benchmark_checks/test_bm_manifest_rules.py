"""BENCHMARK.json against the driver's rules, and the files it names."""

import copy
import importlib
import json
import os

import pytest

from benchmark import manifest

BENCH = manifest.load()


def test_manifest_breaks_none_of_the_drivers_rules():
    assert manifest.problems(BENCH) == []


def test_exactly_one_cell_takes_four_chips_and_says_why():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == ["gpt2xl-train-zero3-4chip"]
    assert "only" in four[0]["why"] and "across chips" in four[0]["why"]


def test_the_full_check_fits_the_drivers_day_at_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_each_per_layer_metric_has_a_reader_that_declares_the_same(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    mod = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"],
        entry["source"])
    assert callable(mod.read)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_config_traffic_kind_and_family_by_name(cell):
    entry = manifest.cell_of(BENCH, cell)
    config = manifest.config_of(BENCH, entry)
    traffic = manifest.traffic_of(entry)
    assert traffic["name"] == cell and traffic["config"] == config["name"]
    assert callable(manifest.kind_module(traffic).run)
    family = manifest.family_module(config)
    assert family.sizes(config, False)["n_embd"] == config["n_embd"]
    e2e = [m["name"] for m in manifest.metrics_for(BENCH, entry, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_for(BENCH, entry, "per_layer")


def test_configurations_keep_the_published_widths():
    want = {"gpt2-large-774m": (1280, 36, 20), "gpt2-xl-1558m": (1600, 48, 25)}
    for c in BENCH["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            body = json.load(f)
        assert (body["n_embd"], body["n_layer"], body["n_head"]) == \
            want[c["name"]]
        assert body["n_embd"] // body["n_head"] == 64
        assert body["n_positions"] == 1024
        assert body["published"]["vocab_size"] == 50257
        assert not any(k.endswith(("_dim", "_rank")) or k in (
            "n_embd", "n_head", "n_inner") for k in c["reduced"])


@pytest.mark.parametrize("break_it,says", [
    (lambda b: b["workloads"][0].update(chips=2), "chips is not 1 or 4"),
    (lambda b: b["workloads"][0].update(name="has space"), "name rule"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda b: b["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda b: b["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda b: b["per_layer"][0].update(why="x"), "has keys"),
    (lambda b: [w.update(chips=4) for w in b["workloads"][:2]],
     "ask for 4 chips"),
    (lambda b: b.update(run_seconds=52), "run_seconds"),
    (lambda b: b["configs"].append(dict(b["configs"][0], name="unused",
                                        file="benchmark/configs/x.json")),
     "used by no cell"),
])
def test_the_checker_catches_what_the_driver_would_refuse(break_it, says):
    bench = copy.deepcopy(BENCH)
    break_it(bench)
    assert any(says in p for p in manifest.problems(bench)), \
        manifest.problems(bench)


READERS = sorted(f[:-3] for f in os.listdir(
    os.path.join(manifest.HERE, "layer_metrics"))
    if f.endswith(".py") and not f.startswith("_"))


@pytest.mark.parametrize("metric", READERS)
def test_every_reader_file_is_named_after_its_metric_and_is_well_formed(
        metric):
    """Readers of a cell that is not admitted yet (the closed loop's) are
    held to the same form, so admitting the cell is entries only."""
    mod = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    assert mod.NAME == metric and manifest.NAME.match(mod.NAME)
    assert manifest.UNIT.match(mod.UNIT)
    assert mod.SOURCE in manifest.SOURCES
    assert "\n" not in mod.LAYER and 1 <= len(mod.LAYER) <= 200
    assert callable(mod.read)


CANDIDATES = sorted(
    f[:-5] for f in os.listdir(os.path.join(manifest.HERE, "workloads"))
    if f[:-5] not in {w["name"] for w in BENCH["workloads"]})


def test_the_candidates_are_the_ones_perf_md_names():
    assert CANDIDATES == ["gpt2l-serve-decode-sat"]


@pytest.mark.parametrize("cell", CANDIDATES)
def test_a_candidate_is_admitted_by_pasting_the_entries_its_file_carries(
        cell):
    """BENCHMARK.json does not list it, so the driver never runs it; with
    its file's ``admit_with`` entries laid over the manifest it breaks none
    of the driver's rules and finds its files by name."""
    with pytest.raises(KeyError):
        manifest.cell_of(BENCH, cell)
    merged = manifest.with_candidate(BENCH, cell)
    assert manifest.problems(merged) == []
    assert manifest.load() == BENCH, "with_candidate edited its argument"
    entry = manifest.cell_of(merged, cell)
    traffic = manifest.traffic_of(entry)
    assert callable(manifest.kind_module(traffic).run)
    names = [m["name"] for m in manifest.metrics_for(merged, entry,
                                                     "per_layer")]
    assert names and all(
        importlib.import_module(f"benchmark.layer_metrics.{n}").NAME == n
        for n in names)
    e2e = [m["name"] for m in manifest.metrics_for(merged, entry,
                                                   "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    # an admitted cell is left as it is
    assert manifest.with_candidate(merged, cell) is merged
