"""Observability fast gate (ISSUE 12 satellites, wired into
ci/telemetry_gate.sh):

- metric-name drift guard: every metric name documented in
  docs/observability.md's tables must still be emitted by the code,
  and every ``cluster/*`` name the code can emit must be documented —
  the docs stop rotting per PR;
- prometheus_text grammar round-trip: the exposition page (HELP/TYPE
  lines, escaped label values, histogram quantile gauges, the new
  cluster gauges) must parse under the openmetrics line grammar a real
  scraper applies;
- viewer import guard: ``import deepspeed_tpu.telemetry.view`` must
  succeed with jax IMPORT-POISONED — the viewer is documented as
  stdlib-only ("runs anywhere the dump landed") and the lazy package
  root (PEP 562) is what keeps that true; this test enforces it.

Everything here is fast and accelerator-free.
"""

import os
import pathlib
import re
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
DOCS = REPO / "docs" / "observability.md"
PKG = REPO / "deepspeed_tpu"

# metric-name shape: subsystem/metric[/...], possibly with one-or-more
# {a,b,c} alternation groups (the docs' compact row form)
_NAME_RE = re.compile(r"^[a-z0-9_]+(/[a-z0-9_{},]+)+$")


def _expand(name):
    """`a/{b,c}/d` -> [`a/b/d`, `a/c/d`] (repeatedly)."""
    m = re.search(r"\{([^{}]*)\}", name)
    if not m:
        return [name]
    out = []
    for alt in m.group(1).split(","):
        out.extend(_expand(name[:m.start()] + alt + name[m.end():]))
    return out


def documented_metric_names():
    """Backticked metric names from the first cell of every markdown
    table row in docs/observability.md, alternations expanded."""
    names = set()
    for line in DOCS.read_text().splitlines():
        if not line.startswith("| `"):
            continue
        first_cell = line.split("|")[1]
        for tok in re.findall(r"`([^`]+)`", first_cell):
            if _NAME_RE.match(tok):
                names.update(_expand(tok))
    assert names, "no metric tables found — did observability.md move?"
    return names


def _package_source():
    return "\n".join(p.read_text() for p in sorted(PKG.rglob("*.py")))


def test_documented_metric_names_are_emitted():
    """Every documented name must appear in the package source — either
    as the full literal, or (for the f-string-built families like
    ``span/<tag>`` and ``memory/<key>``) as the literal tail after the
    subsystem prefix. A doc row whose metric was renamed in code fails
    here instead of rotting."""
    src = _package_source()
    missing = []
    for name in sorted(documented_metric_names()):
        if name.startswith(("cluster/", "slo/")):
            continue   # pinned exactly (both directions) by the
            #            programmatic tests below — they are f-string
            #            built, so no literal to find here
        tail = name.split("/", 1)[1]
        if name in src or tail in src:
            continue
        missing.append(name)
    assert not missing, (
        "documented in docs/observability.md but not found in the "
        "code (renamed? removed?): " + ", ".join(missing))


def test_cluster_metric_names_documented_both_directions():
    """The ``cluster/*`` namespace is pinned EXACTLY: emitted ⊆
    documented (an undocumented new gauge fails) and documented ⊆
    emitted (a doc row for a dropped gauge fails). cluster.py is
    importable jax-free, so this runs without an accelerator."""
    from deepspeed_tpu.telemetry.cluster import cluster_metric_names
    emitted = set(cluster_metric_names())
    documented = {n for n in documented_metric_names()
                  if n.startswith("cluster/")}
    assert emitted - documented == set(), (
        "emitted but undocumented cluster/* names — add them to the "
        "docs/observability.md cluster table: "
        + ", ".join(sorted(emitted - documented)))
    assert documented - emitted == set(), (
        "documented but no longer emitted cluster/* names: "
        + ", ".join(sorted(documented - emitted)))


def test_slo_metric_names_documented_both_directions():
    """The ``slo/*`` namespace (ISSUE 19) is pinned EXACTLY like
    cluster/*: emitted ⊆ documented and documented ⊆ emitted, against
    ``telemetry.slo.slo_metric_names()``. slo.py is stdlib-only, so
    this runs anywhere."""
    from deepspeed_tpu.telemetry.slo import slo_metric_names
    emitted = set(slo_metric_names())
    documented = {n for n in documented_metric_names()
                  if n.startswith("slo/")}
    assert emitted - documented == set(), (
        "emitted but undocumented slo/* names — add them to the "
        "docs/observability.md slo table: "
        + ", ".join(sorted(emitted - documented)))
    assert documented - emitted == set(), (
        "documented but no longer emitted slo/* names: "
        + ", ".join(sorted(documented - emitted)))


def test_cluster_fences_counts_on_every_rank(monkeypatch):
    """The PR-12 asymmetry fix (ISSUE 19 satellite), pinned: the
    ``cluster/fences`` counter increments in ``exchange()`` on EVERY
    rank — a non-zero rank's registry must show its fences, not 0
    (the old behavior: only the rank-0 fold counted)."""
    import numpy as np
    from deepspeed_tpu.telemetry.cluster import (CLUSTER_METRICS,
                                                 ClusterAggregator)
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    world, me = 3, 1          # a NON-fold rank
    mat = np.zeros((world, len(CLUSTER_METRICS)), np.float32)
    monkeypatch.setattr(
        "deepspeed_tpu.utils.distributed.allgather_host_floats",
        lambda vec: (mat, me))
    reg = MetricsRegistry()
    agg = ClusterAggregator(registry=reg)
    for _ in range(4):
        agg.exchange({"step_time_s": 0.1})
    assert agg.rank == 1 and agg.fences == 4
    assert reg.counter("cluster/fences").value == 4
    # and the fold-side gauges did NOT appear on this rank
    assert reg.peek_gauge("cluster/step_time_s/max") is None


def test_router_metric_names_documented_both_directions():
    """The ``router/*`` namespace (ISSUE 14) is pinned EXACTLY like
    cluster/*: emitted ⊆ documented and documented ⊆ emitted, against
    ``serving.router.router_metric_names()``."""
    from deepspeed_tpu.serving.router import router_metric_names
    emitted = set(router_metric_names())
    documented = {n for n in documented_metric_names()
                  if n.startswith("router/")}
    assert emitted - documented == set(), (
        "emitted but undocumented router/* names — add them to the "
        "docs/observability.md router table: "
        + ", ".join(sorted(emitted - documented)))
    assert documented - emitted == set(), (
        "documented but no longer emitted router/* names: "
        + ", ".join(sorted(documented - emitted)))


def test_handoff_serving_metric_names_documented():
    """The handoff/TTFT-attribution additions to the serving/*
    namespace (ISSUE 14) must be documented — and stay emitted (the
    generic documented→source test covers the reverse direction)."""
    documented = documented_metric_names()
    for name in ("serving/ttft_queue_wait_s", "serving/ttft_prefill_s",
                 "serving/handoff_s", "serving/transport_s",
                 "serving/transport_encode_s",
                 "serving/transport_collective_s",
                 "serving/transport_decode_s",
                 "serving/first_decode_tick_s",
                 "serving/handoffs_out", "serving/handoffs_in"):
        assert name in documented, (
            f"{name} missing from the docs/observability.md serving "
            f"table")
        assert name in _package_source(), name


def test_o_direct_metric_names_documented():
    """The O_DIRECT swap-tier additions (ISSUE 20): the device-truth
    bandwidth gauges and the buffered-fallback breadcrumb counter must
    stay documented AND emitted."""
    documented = documented_metric_names()
    for name in ("swap/device_read_mb_s", "swap/device_write_mb_s",
                 "swap/o_direct_fallback"):
        assert name in documented, (
            f"{name} missing from the docs/observability.md swap table")
        assert name in _package_source(), name


def test_zero_gather_edge_metric_names_documented():
    """The ZeRO-3 gather edge's two gauges (ISSUE 25) stay documented
    AND emitted."""
    documented = documented_metric_names()
    for name in ("zero/gather_edge_leaves",
                 "zero/gather_edge_bytes_per_layer"):
        assert name in documented, (
            f"{name} missing from the docs/observability.md train table")
        assert name in _package_source(), name


@pytest.mark.parametrize("name", ["remat/kept_names", "remat/kept_mb",
                                  "remat/budget_mb", "remat/reserve_mb",
                                  "remat/scan_states_kept",
                                  "remat/fell_back_to_base"])
def test_remat_budget_metric_names_documented(name):
    """What a rematted block keeps beside its base names (ISSUE 61; the
    reserve and the scans' name: ISSUE 64): the five trace-time gauges and
    the fall-back's counter stay documented AND emitted."""
    assert name in documented_metric_names(), (
        f"{name} missing from the docs/observability.md train table")
    assert name in _package_source(), name


@pytest.mark.parametrize("name", ["linear_attn/gdn_lane_overcompute",
                                  "linear_attn/gdn_kernel_heads_per_step",
                                  "linear_attn/gdn_states_kept_every"])
def test_delta_rule_engagement_gauges_documented(name):
    """The gated delta rule's trace-time gauges (ISSUE 32: heads a grid
    step, 0 the XLA form; ISSUE 68: the lanes the kernels compute on over
    the published heads' lanes, which ``gdn_lane_overcompute`` reads) stay
    documented AND emitted."""
    assert name in documented_metric_names(), (
        f"{name} missing from the docs/observability.md train table")
    assert f'"{name}"' in _package_source(), name


@pytest.mark.parametrize("name", ["attention/flash_tile_overcompute",
                                  "attention/flash_heads_per_block",
                                  "attention/window_tile_overcompute",
                                  "attention/window_tiles_per_grid_step",
                                  "attention/window_bwd_tiles_per_grid_step",
                                  "attention/flash_residual_mb",
                                  "attention/flash_grid_steps_walked_share",
                                  "attention/flash_chunk_rows",
                                  "attention/flash_bwd_products_per_tile",
                                  "attention/flash_bwd_dq_slabs"])
def test_flash_engagement_gauges_documented(name):
    """The flash kernels' trace-time engagement gauges (ISSUE 28: the
    loops' overcompute; ISSUE 30: heads a column block, 0 head-major;
    ISSUE 34: MB of the residuals a differentiation names; ISSUE 39: the
    chunked kernels' grid steps over the rectangle's; ISSUE 43: the window
    kernels' score tiles a grid step; ISSUE 48: the chunked kernels' rows a
    grid step; ISSUE 49: the backward's products a score tile and its dq
    slabs; ISSUE 53: the window backward's heads x tiles a grid step) stay documented AND emitted."""
    assert name in documented_metric_names(), (
        f"{name} missing from the docs/observability.md train table")
    assert name in _package_source(), name


@pytest.mark.parametrize("name", [
    "span/startup/sharded_init", "span/startup/engine_init",
    "span/startup/state_init", "span/startup/build_fns",
    "compile/cache_misses", "compile/after_first_step"])
def test_startup_and_compile_names_documented(name):
    """Start-up's spans and the compile listener's two counters (ISSUE 35)
    stay documented AND emitted: a span's histogram is
    ``span/<tag>``, so the code holds the tag; the ``compile/*`` names are
    literals of ``telemetry/spans.py``. The ``compile`` event and the
    ``t0_mono`` field are rows of the flight recorder's table."""
    assert name in documented_metric_names(), (
        f"{name} missing from the docs/observability.md train table")
    literal = name[len("span/"):] if name.startswith("span/") else name
    assert f'"{literal}"' in _package_source(), name
    doc = DOCS.read_text()
    assert "| `compile` | `spans.watch_compiles()`" in doc
    assert "`t0_mono`" in doc.split("| `span` | `span()` exit")[1][:200]


def test_moe_metric_names_documented():
    """The dropless expert layer's four gauges (ISSUE 27) stay documented
    AND sown: the engine names a gauge after the model's ``stats`` variable
    (``moe_aux_loss`` -> ``moe/aux_loss``)."""
    documented = documented_metric_names()
    sown = (PKG / "moe" / "dropless.py").read_text()
    for name in ("moe/rows_max_over_mean", "moe/aux_loss", "moe/z_loss",
                 "moe/dropped_rows"):
        assert name in documented, (
            f"{name} missing from the docs/observability.md train table")
        assert '"' + name.replace("/", "_") + '"' in sown, name


# ------------------------------------------------------- prometheus page

# the exposition-format line grammar a real scraper applies
# (https://prometheus.io/docs/instrumenting/exposition_formats/):
_PROM_METRIC_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_PROM_LABELS = r'\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"' \
               r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*")*\}'
_PROM_VALUE = r"[-+]?(?:[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|" \
              r"[Nn]a[Nn]|[+-]?[Ii]nf)"
SAMPLE_LINE = re.compile(
    rf"^({_PROM_METRIC_NAME})(?:{_PROM_LABELS})? ({_PROM_VALUE})"
    rf"(?: [0-9]+)?$")
HELP_LINE = re.compile(rf"^# HELP ({_PROM_METRIC_NAME}) .*$")
TYPE_LINE = re.compile(
    rf"^# TYPE ({_PROM_METRIC_NAME}) "
    rf"(counter|gauge|summary|histogram|untyped)$")


def test_prometheus_text_roundtrips_the_openmetrics_grammar():
    from deepspeed_tpu.telemetry.registry import (MetricsRegistry,
                                                  prometheus_text)
    from deepspeed_tpu.telemetry.cluster import (ClusterAggregator,
                                                 cluster_metric_names)
    reg = MetricsRegistry()
    reg.counter("train/steps").inc(7)
    reg.gauge("serving/page_pool_occupancy").set(0.25)
    # histogram -> summary family with quantile label gauges
    h = reg.histogram("serving/ttft_s")
    for v in (0.1, 0.2, 0.4, 1.5):
        h.observe(v)
    # a name needing mangling + a digit-leading name
    reg.gauge("weird-metric.name/with spaces").set(1.0)
    reg.counter("0starts_with_digit/x").inc()
    # the new cluster gauges via a real fold (world of 3, one NaN rank)
    agg = ClusterAggregator(registry=reg)
    agg.world = 3
    agg.rank = 0
    import numpy as np
    mat = np.asarray(
        [[0.1, 0.0, 0.0, 2.0, 100.0, 1.0, 0.5],
         [0.3, 0.0, 0.0, 2.1, 110.0, 1.0, 0.5],
         [np.nan, np.nan, np.nan, np.nan, np.nan, np.nan, np.nan]],
        np.float32)
    agg._fold(mat, step=4)

    text = prometheus_text(reg)
    families = {}
    last_help = None
    for line in text.strip().splitlines():
        m = HELP_LINE.match(line)
        if m:
            last_help = m.group(1)
            continue
        m = TYPE_LINE.match(line)
        if m:
            # HELP must immediately precede TYPE for the same family
            assert m.group(1) == last_help, line
            families[m.group(1)] = m.group(2)
            continue
        m = SAMPLE_LINE.match(line)
        assert m, f"line fails the exposition grammar: {line!r}"
        base = re.sub(r"_(sum|count)$", "", m.group(1)) \
            if m.group(1).endswith(("_sum", "_count")) else m.group(1)
        assert base in families or m.group(1) in families, (
            f"sample before its # TYPE header: {line!r}")
    # quantile-labeled summary lines present and parseable
    assert 'serving_ttft_s{quantile="0.5"}' in text
    assert families["serving_ttft_s"] == "summary"
    # cluster gauges made it onto the page, mangled names intact
    assert "cluster_step_time_s_max" in families
    n_cluster = sum(1 for f in families if f.startswith("cluster_"))
    assert n_cluster >= len(cluster_metric_names()) - 1  # fences is a
    #         counter emitted by exchange(), not _fold — tolerate ±1


def test_prometheus_label_escaping_survives_a_scraper_regex():
    from deepspeed_tpu.telemetry.registry import (_prom_escape_label,
                                                  _prom_escape_help)
    nasty = 'a"b\\c\nd'
    esc = _prom_escape_label(nasty)
    line = f'metric{{rule="{esc}"}} 1.0'
    assert SAMPLE_LINE.match(line), line
    assert "\n" not in esc
    help_line = f"# HELP metric {_prom_escape_help(nasty)}"
    assert HELP_LINE.match(help_line), help_line


# ------------------------------------------------------ viewer jax-free

def test_viewer_import_chain_is_stdlib_only(tmp_path):
    """ISSUE 12 satellite: the dump viewer's documented stdlib-only
    contract, ENFORCED — `import deepspeed_tpu.telemetry.view` in a
    fresh interpreter with BOTH jax and numpy import-poisoned via
    stubs first on sys.path ("runs anywhere the dump landed" includes
    machines with neither). The package root AND telemetry/__init__
    resolve their public surfaces lazily (PEP 562) precisely so this
    passes; an eager jax/numpy import anywhere in the chain fails
    here. telemetry.serve (stdlib http.server) must ride along;
    telemetry.cluster legitimately needs numpy and is exempt."""
    for name in ("jax", "numpy"):
        (tmp_path / f"{name}.py").write_text(
            f"raise ImportError('poisoned: the viewer must not import "
            f"{name}')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{tmp_path}{os.pathsep}{REPO}" \
        + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run(
        [sys.executable, "-c",
         "import deepspeed_tpu.telemetry.view as v; "
         "import deepspeed_tpu.telemetry.serve; "
         "import deepspeed_tpu.telemetry.slo; "
         "import deepspeed_tpu.telemetry.perfetto as p; "
         "print('STDLIB_OK', callable(v.render) and "
         "callable(p.export))"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (
        f"viewer import chain pulled jax/numpy (or crashed):\n{r.stderr}")
    assert "STDLIB_OK True" in r.stdout


def test_viewer_render_accepts_a_single_pathlike(tmp_path):
    """The pre-ISSUE-12 render(path) signature keeps working for str
    AND PathLike single arguments next to the new list form."""
    import pathlib

    from deepspeed_tpu.telemetry import view
    p = tmp_path / "d.jsonl"
    p.write_text('{"kind": "loss", "step": 1, "loss": 2.0, "ts": 1.0, '
                 '"seq": 1}\n')
    for arg in (str(p), pathlib.Path(p), [str(p)]):
        out = "\n".join(view.render(arg))
        assert "per-step phase attribution" in out


def test_lazy_package_root_still_resolves_the_public_surface():
    """The PEP 562 root must behave exactly like the old eager imports
    for real users: attribute access resolves and caches."""
    import deepspeed_tpu as dstpu
    assert callable(dstpu.initialize)
    assert callable(dstpu.add_config_arguments)
    assert dstpu.DeepSpeedConfig is not None
    assert dstpu.MeshConfig is not None
    assert dstpu.zero is not None          # deepspeed.zero parity alias
    # subpackage attributes the eager root implicitly bound must stay
    # reachable (`d.parallel.mesh.make_mesh` was valid user code)
    assert dstpu.parallel.mesh.make_mesh is not None
    assert dstpu.config.config.DeepSpeedConfig is dstpu.DeepSpeedConfig
    assert "DeepSpeedEngine" in dir(dstpu)
    with pytest.raises(AttributeError):
        dstpu.no_such_symbol_anywhere
