"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one cell, one configuration, one traffic kind or
one per-layer metric sits in a file of its own, found by the name in
``BENCHMARK.json``:

    benchmark/configs/<config>.json        a configuration as it is run
    benchmark/workloads/<cell>.json        a cell's traffic: kind + parameters
    benchmark/kinds/<kind>.py              how a kind of traffic is driven
    benchmark/families/<family>.py         how a model family is built
    benchmark/layer_metrics/<metric>.py    one reader per per-layer metric

so a later PR adds a cell, a configuration, a metric or a model of another
family by adding files and entries, and edits nothing that is there. A
family file is the one place that knows a model: its classes, the names of
its configuration keys (``hidden_size`` or ``n_embd``), its widths, its
module and kernel scopes, its counts of operations, and how its programs
are lowered for ``tools/rehearse_compile.py``. The harness reads no model
key itself; what it asks of a family is listed in
``benchmark/families/__init__.py``. ``problems()`` holds the manifest to
the driver's rules; the tests run it.
"""

import copy
import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read(path):
    with open(path) as f:
        return json.load(f)


def cell_of(bench, name):
    """The cell's entry in BENCHMARK.json."""
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[c['name'] for c in bench['workloads']]}")


def with_candidate(bench, name, here=HERE):
    """``bench`` as it would read with the candidate cell ``name`` admitted.

    A candidate has its traffic file (and its kind and readers) in the tree
    but no entry in BENCHMARK.json: the driver never runs it. Its file
    carries under ``admit_with`` the very entries that admit it — the
    metrics it reports, each naming it under ``workloads`` — so admitting
    it is pasting them, and tools and CPU rehearsals run it through this
    function meanwhile. An entry BENCHMARK.json already has is kept, with
    the cell added to its ``workloads``."""
    if any(c["name"] == name for c in bench["workloads"]):
        return bench
    body = _read(os.path.join(here, "workloads", name + ".json"))
    out = copy.deepcopy(bench)
    out["workloads"].append({k: body[k] for k in ("name", "config",
                                                  "traffic", "chips", "why")})
    for section in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in out[section]}
        for m in body["admit_with"][section]:
            if m["name"] not in have:
                out[section].append(m)
            elif "workloads" in have[m["name"]]:
                have[m["name"]]["workloads"].append(name)
    return out


def config_of(bench, cell, root=ROOT):
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            return _read(os.path.join(root, c["file"]))
    raise KeyError(f"cell {cell['name']!r} names configuration "
                   f"{cell['config']!r}, which BENCHMARK.json does not list")


def traffic_of(cell, here=HERE):
    return _read(os.path.join(here, "workloads", cell["name"] + ".json"))


def kind_module(traffic):
    return importlib.import_module(f"benchmark.kinds.{traffic['kind']}")


def family_module(config):
    return importlib.import_module(f"benchmark.families.{config['family']}")


def metric_module(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def metrics_for(bench, cell, section):
    """The metrics of ``section`` that ``cell`` reports: those with no
    ``workloads`` list, and those whose list names the cell."""
    return [m for m in bench[section]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def problems(bench, root=ROOT):
    """Everything in the manifest the driver would refuse, as sentences."""
    out = []
    here = os.path.join(root, "benchmark")
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != want:
        out.append(f"keys {sorted(bench)} are not exactly {sorted(want)}")
        return out
    if not (isinstance(bench["run_seconds"], int)
            and 1 <= bench["run_seconds"] <= 51):
        out.append("run_seconds is not a whole number from 1 to 51")
    if not 1 <= len(bench["paths"]) <= 16 or not all(
            PATH.match(p) and not p.startswith("/") and ".." not in p
            for p in bench["paths"]):
        out.append("paths break the path rules")
    if not 1 <= len(bench["command"]) <= 32 or not all(
            _line(w) and not w.startswith("/") and ".." not in w
            for w in bench["command"]):
        out.append("command breaks the command rules")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in bench["paths"])

    names = {}

    def name_ok(kind, n):
        if not (isinstance(n, str) and NAME.match(n)):
            out.append(f"{kind} name {n!r} breaks the name rule")
        if n in names.setdefault(kind, set()):
            out.append(f"{kind} name {n!r} appears twice")
        names[kind].add(n)

    files = set()
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config {c.get('name')!r} has keys {sorted(c)}")
            continue
        name_ok("config", c["name"])
        if not (_line(c["source"]) and _line(c["why"])):
            out.append(f"config {c['name']}: source/why not one short line")
        if not (PATH.match(c["file"]) and under_paths(c["file"])):
            out.append(f"config {c['name']}: file {c['file']!r} not under paths")
        if c["file"] in files:
            out.append(f"config file {c['file']} is used twice")
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            out.append(f"config {c['name']}: reduced breaks the rules")
        path = os.path.join(root, c["file"])
        if not os.path.isfile(path):
            out.append(f"config {c['name']}: {c['file']} is not there")
            continue
        body = _read(path)
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            out.append(f"config {c['name']}: 'reduced' differs from its file")
        fam = os.path.join(here, "families", body.get("family", "?") + ".py")
        if not os.path.isfile(fam):
            out.append(f"config {c['name']}: no family file {fam}")
    if not 1 <= len(bench["configs"]) <= 24:
        out.append("configs are not 1 to 24")

    pairs, used = set(), set()
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w.get('name')!r} has keys {sorted(w)}")
            continue
        name_ok("workload", w["name"])
        if not NAME.match(w["traffic"]) or not NAME.match(w["config"]):
            out.append(f"workload {w['name']}: config/traffic break the name rule")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips is not 1 or 4")
        if not _line(w["why"]):
            out.append(f"workload {w['name']}: why is not one line of <= 200")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"pair {(w['config'], w['traffic'])} appears twice")
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        if w["config"] not in names.get("config", ()):
            out.append(f"workload {w['name']}: unknown config {w['config']}")
        tfile = os.path.join(here, "workloads", w["name"] + ".json")
        if not os.path.isfile(tfile):
            out.append(f"workload {w['name']}: no traffic file {tfile}")
            continue
        traffic = _read(tfile)
        for key in ("config", "chips", "traffic"):
            if traffic.get(key) != w[key]:
                out.append(f"workload {w['name']}: its file disagrees on {key}")
        kind = os.path.join(here, "kinds", str(traffic.get("kind")) + ".py")
        if not os.path.isfile(kind):
            out.append(f"workload {w['name']}: no kind file {kind}")
    n = len(bench["workloads"])
    if not 2 <= n <= 24:
        out.append("workloads are not 2 to 24")
    four = sum(w.get("chips") == 4 for w in bench["workloads"])
    if four > max(1, n // 4):
        out.append(f"{four} cells ask for 4 chips; at most {max(1, n // 4)} may")
    for c in names.get("config", ()):
        if c not in used:
            out.append(f"config {c} is used by no cell")

    e2e = {m.get("name") for m in bench["end_to_end"]}
    for section, keys in (("end_to_end",
                           {"name", "unit", "better", "bound", "source"}),
                          ("per_layer", {"name", "unit", "better", "source",
                                         "layer", "moves"})):
        limit = 16 if section == "end_to_end" else 128
        if not 1 <= len(bench[section]) <= limit:
            out.append(f"{section} has a wrong number of metrics")
        for m in bench[section]:
            if set(m) - {"workloads"} != keys:
                out.append(f"metric {m.get('name')!r} has keys {sorted(m)}")
                continue
            name_ok("metric", m["name"])
            if not UNIT.match(m["unit"]):
                out.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"metric {m['name']}: source {m['source']!r}")
            for cell in m.get("workloads", ()):
                if cell not in names.get("workload", ()):
                    out.append(f"metric {m['name']}: unknown cell {cell}")
            if section == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    out.append(f"metric {m['name']}: an end-to-end metric "
                               "takes host_clock or device_trace")
                if not 0.01 <= m["bound"] <= 0.1:
                    out.append(f"metric {m['name']}: bound {m['bound']}")
            else:
                if not _line(m["layer"]):
                    out.append(f"metric {m['name']}: layer is not one line")
                if m["moves"] not in e2e:
                    out.append(f"metric {m['name']}: moves {m['moves']!r} is "
                               "no end-to-end metric")
                mod = os.path.join(here, "layer_metrics", m["name"] + ".py")
                if not os.path.isfile(mod):
                    out.append(f"metric {m['name']}: no reader {mod}")
    if "setup_s" not in e2e:
        out.append("no setup_s among the end-to-end metrics")
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            continue
        mine = [m["name"] for m in metrics_for(bench, w, "end_to_end")]
        if "setup_s" not in mine or len(mine) < 2:
            out.append(f"cell {w['name']} reports {mine}: needs setup_s and "
                       "one more end-to-end metric")
        layer = metrics_for(bench, w, "per_layer")
        if not layer:
            out.append(f"cell {w['name']} reports no per-layer metric")
        for m in layer:
            if m["moves"] not in mine:
                out.append(f"cell {w['name']}: {m['name']} moves "
                           f"{m['moves']}, which the cell does not report")
    if len(json.dumps(bench)) > 64 * 1024:
        out.append("BENCHMARK.json is over 64 KiB")
    return out
