"""BENCHMARK.json against the driver's rules, and the files it names.

Every rule here is held on whatever ``BENCHMARK.json`` and files the imported
``benchmark`` package sits beside: each configuration against its own file
and its family, each family file against ``benchmark/families/__init__.py``'s
list. ``test_bm_rehearsal_runs.py`` runs this file again on a copy of the
checkout to which a configuration of another family was added as files and
entries only. What is known of GPT-2 alone sits in the cases that name it.

Since PR 37 it also holds the CHECKS to what they ask of a later PR: an
addition goes at the end of a list and onto the ``workloads`` of a metric
that is there, so no file of this directory may hold the manifest to a
position or a count (the last two cases).
"""

import ast
import copy
import importlib
import json
import os
import shutil

import pytest

from benchmark import families, manifest

BENCH = manifest.load()


def _listed(directory, ending):
    return sorted(f[:-len(ending)] for f in os.listdir(
        os.path.join(manifest.HERE, directory))
        if f.endswith(ending) and not f.startswith("_"))


def _config_file(entry):
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        return json.load(f)


def test_manifest_breaks_none_of_the_drivers_rules():
    assert manifest.problems(BENCH) == []


def test_the_four_chip_cells_are_within_the_cap_and_each_says_why():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert "gpt2xl-train-zero3-4chip" in [w["name"] for w in four]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for w in four:
        assert "only" in w["why"] and "across chips" in w["why"], w["name"]


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
    sizes = family.sizes(config, False)
    assert sizes and all(config[k] == v for k, v in sizes.items())
    shapes = family.traffic_shapes(config, False)
    assert set(shapes) == set(families.TRAFFIC_SHAPES)
    assert shapes["seq_scale"] == 1 and shapes["vocab_size"] > 1
    assert 0 < family.traffic_shapes(config, True)["seq_scale"] <= 1
    if "seq_len" in traffic:
        assert traffic["seq_len"] <= shapes["max_positions"]
        assert family.train_flops_per_token(config, traffic["seq_len"]) > 0
    e2e = [m["name"] for m in manifest.metrics_for(BENCH, entry, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_for(BENCH, entry, "per_layer")


def _is_width(key, family):
    return key in family.WIDTH_KEYS or key.endswith(("_dim", "_rank"))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_a_configuration_keeps_its_published_widths(name):
    """Held to its own file and family: no width key (the family's
    ``WIDTH_KEYS``, anything ending ``_dim`` / ``_rank``) is listed under
    ``reduced``, and each equals ``published[key]`` where the file's
    ``published`` block (the source's value of every changed key) has it."""
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    body = _config_file(entry)
    family = manifest.family_module(body)
    assert body["name"] == name and body["source"] == entry["source"]
    widths = [k for k in body if _is_width(k, family)]
    assert widths, f"{name} states none of {family.WIDTH_KEYS}"
    assert not [k for k in entry["reduced"] if _is_width(k, family)]
    published = body.get("published", {})
    assert not [k for k in widths if k in published
                and published[k] != body[k]]
    assert set(entry["reduced"]) <= set(published), \
        "a reduced key without its published value"


@pytest.mark.parametrize("name,want", [
    ("gpt2-large-774m", (1280, 36, 20)), ("gpt2-xl-1558m", (1600, 48, 25))])
def test_the_gpt2_configurations_are_the_published_ones(name, want):
    body = _config_file(next(c for c in BENCH["configs"]
                             if c["name"] == name))
    assert body["family"] == "gpt2"
    assert (body["n_embd"], body["n_layer"], body["n_head"]) == want
    assert body["n_embd"] // body["n_head"] == 64
    assert body["n_positions"] == 1024
    assert body["published"]["vocab_size"] == 50257


@pytest.mark.parametrize("name", [f for f in _listed("families", ".py")
                                  if f not in families.HELPERS])
def test_each_family_file_provides_what_the_harness_asks_of_a_family(name):
    """``benchmark/families/__init__.py`` is the list; serving is all or
    nothing (a family without a serving block has none of it).
    ``families.HELPERS`` (the recipe they share) are no families."""
    family = importlib.import_module(f"benchmark.families.{name}")
    missing = [m for m in families.TRAINING
               if not callable(getattr(family, m, None))]
    assert not missing, f"families/{name}.py lacks {missing}"
    for tags in families.TAGS:
        value = getattr(family, tags)
        assert isinstance(value, tuple) and all(
            isinstance(t, str) and t for t in value), tags
    assert family.WIDTH_KEYS
    serving = [m for m in families.SERVING
               if callable(getattr(family, m, None))]
    assert serving in ([], list(families.SERVING))
    assert [c for c in BENCH["configs"]
            if _config_file(c)["family"] == name], \
        f"no configuration is of family {name}"


@pytest.mark.parametrize("break_it,says", [
    (lambda b: b["workloads"][0].update(chips=2), "chips is not 1 or 4"),
    (lambda b: b["workloads"][0].update(name="has space"), "name rule"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda b: b["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda b: b["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda b: b["per_layer"][0].update(why="x"), "has keys"),
    (lambda b: [w.update(chips=4) for w in b["workloads"]],
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


READERS = _listed("layer_metrics", ".py")


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


CANDIDATES = [f for f in _listed("workloads", ".json")
              if f not in {w["name"] for w in BENCH["workloads"]}]


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


# ------------------------------------------- the checks take additions (PR 37)

# each family's "the cell is the one its issue names", which takes the manifest
FAMILY_CHECKS = {"test_bm_olmoe": "the_cell_is_the_one_issue_27_names",
                 "test_bm_qwen3_next": "the_cell_is_the_one_issue_31_names",
                 "test_bm_laguna": "the_cell_is_the_one_issue_33_names"}
LATER_READER = '''"""later_steps: a later PR's metric, added as a file."""
NAME, UNIT, LAYER = "later_steps", "count", "train step program"
MOVES, SOURCE = "train_tokens_per_s", "program_counter"


def read(record):
    return record.extra.get("steps")
'''


def with_a_later_prs_addition(bench, root, like="laguna-train-1chip-s16384"):
    """``bench`` as a later ``model_config`` PR would leave it, its files
    under ``root``: a configuration, a cell and a per-layer metric appended
    at the END (copies of ``like``'s files under new names), and the cell's
    name appended to the lists of the metrics every training cell has."""
    here = os.path.join(root, "benchmark")
    shutil.copytree(manifest.HERE, here,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    later = copy.deepcopy(bench)
    old = manifest.cell_of(bench, like)
    entry = next(c for c in bench["configs"] if c["name"] == old["config"])
    cell = dict(old, name="later-train-1chip", config="later-model",
                traffic="pretrain-later")
    config = dict(_config_file(entry), name="later-model")
    traffic = dict(manifest.traffic_of(old),
                   **{k: cell[k] for k in ("name", "config", "traffic")})
    for path, text in (
            ("configs/later-model.json", json.dumps(config)),
            ("workloads/later-train-1chip.json", json.dumps(traffic)),
            ("layer_metrics/later_steps.py", LATER_READER)):
        with open(os.path.join(here, path), "w") as f:
            f.write(text)
    later["configs"].append(dict(
        entry, name="later-model", file="benchmark/configs/later-model.json"))
    later["workloads"].append(cell)
    training = set(next(m for m in bench["end_to_end"]
                        if m["name"] == "train_tokens_per_s")["workloads"])
    for m in later["end_to_end"] + later["per_layer"]:
        if training <= set(m.get("workloads", ())):
            m["workloads"].append(cell["name"])
    later["per_layer"].append({
        "name": "later_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train step program",
        "moves": "train_tokens_per_s", "workloads": [cell["name"]]})
    return later


def test_an_addition_at_the_end_breaks_no_rule_and_no_familys_check(tmp_path):
    """Rule (a) of ISSUE 37: a sixth cell, its configuration and a metric of
    its own, appended where the driver's check wants every addition, leave
    ``manifest.problems`` empty and every family's own check true."""
    later = with_a_later_prs_addition(BENCH, str(tmp_path))
    assert manifest.load() == BENCH, "the addition edited its argument"
    assert manifest.problems(later, root=str(tmp_path)) == []
    added = manifest.cell_of(later, "later-train-1chip")
    names = {m["name"] for m in manifest.metrics_for(later, added,
                                                     "per_layer")}
    assert {"later_steps", "train_program_hbm_gb"} <= names
    for module, check in FAMILY_CHECKS.items():
        getattr(importlib.import_module(module), check)(later)


SECTIONS = ("workloads", "configs", "per_layer", "end_to_end")


def _is_a_section(node):
    """``BENCH["<section>"]``, the loaded manifest under either name."""
    return isinstance(node, ast.Subscript) \
        and isinstance(node.value, ast.Name) \
        and node.value.id in ("BENCH", "bench") \
        and isinstance(node.slice, ast.Constant) \
        and node.slice.value in SECTIONS


def _holds(node, test):
    return any(test(n) for n in ast.walk(node))


def pins(source):
    """Lines of ``source`` that hold the manifest to a position or a count:
    a section of it indexed by a number or a slice; a ``workloads`` list
    compared with ``[CELL]``; the ``len`` of a section, or of a name made
    from one, compared with a number."""
    tree = ast.parse(source)
    made_from_it = {t.id for n in ast.walk(tree) if isinstance(n, ast.Assign)
                    and _holds(n.value, _is_a_section)
                    for t in n.targets if isinstance(t, ast.Name)}

    def a_count(n):
        return isinstance(n, ast.Call) and getattr(n.func, "id", "") == "len" \
            and (_holds(n.args[0], _is_a_section) or getattr(
                n.args[0], "id", None) in made_from_it)

    def a_number(n):
        return isinstance(n, ast.Constant) and isinstance(n.value, int)

    def only_the_cell(n):
        return isinstance(n, ast.List) and len(n.elts) == 1 \
            and getattr(n.elts[0], "id", "") == "CELL"

    def its_workloads(n):
        return isinstance(n, ast.Subscript) and isinstance(
            n.slice, ast.Constant) and n.slice.value == "workloads"

    out = []
    for n in ast.walk(tree):
        if isinstance(n, ast.Subscript) and _is_a_section(n.value) \
                and not isinstance(n.slice, ast.Name):
            out.append(n.lineno)
        if isinstance(n, ast.Compare):
            sides = [n.left] + n.comparators
            if any(map(a_count, sides)) and any(map(a_number, sides)):
                out.append(n.lineno)
            if _holds(n, only_the_cell) and _holds(n, its_workloads):
                out.append(n.lineno)
    return sorted(set(out))


@pytest.mark.parametrize("source", [
    'assert BENCH["workloads"][-1]["name"] == CELL',
    'assert BENCH["configs"][-1]["name"] == cell["config"]',
    'assert [m["name"] for m in BENCH["per_layer"][-4:]] == FOUR',
    'for m in BENCH["per_layer"]:\n    assert m["workloads"] == [CELL]',
    'cells = [w["name"] for w in BENCH["workloads"]]\n'
    'assert len(cells) == 5',
    'assert len(bench["end_to_end"]) == 2'],
    ids=["last_cell", "last_config", "last_four_metrics", "this_cell_alone",
         "five_cells", "two_metrics"])
def test_the_rule_finds_the_pins_issue_37_found(source):
    assert pins(source)


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(os.path.dirname(os.path.abspath(__file__)))
    if f.startswith("test_") and f.endswith(".py")))
def test_no_check_holds_the_manifest_to_a_position_or_a_count(name):
    """Rule (b) of ISSUE 37, one case a file of this directory: entries are
    held by NAME (``manifest.cell_of``, ``next(m for m in ... if m["name"]
    == ...)``, ``CELL in m["workloads"]``), so a later PR's appended entries
    fail no accepted check."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           name)) as f:
        assert pins(f.read()) == [], name
