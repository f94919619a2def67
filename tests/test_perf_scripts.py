"""The probes under tests/perf/ run only in chip sessions, and they cite the
package's kernels and helpers by name. Between sessions this holds them to the
tree: each script imports on the CPU backend, every ``from x import y``
anywhere in it and every ``module.name`` it reads off a module it bound at
import resolves, and a script with a command line parses ``--help``."""

import ast
import glob
import importlib
import os
import sys
import types

import pytest

import deepspeed_tpu  # noqa: F401  (before a script puts its own path first)

PERF = os.path.join(os.path.dirname(os.path.abspath(__file__)), "perf")
SCRIPTS = sorted(os.path.basename(p)[:-3]
                 for p in glob.glob(os.path.join(PERF, "*.py"))
                 if not p.endswith("__init__.py"))


def test_the_scripts_that_stay_are_the_nine():
    """A probe is added here by name, with the Finding or the comment that
    cites it: 22 one-off probes had piled up before PR 46. Ten since PR 47:
    ``mla_flash_bench`` (the chunked kernels at latent attention's two
    widths over (block, chunk) plans) is what ``_CHUNK_BYTES``'s comment and
    PERF.md's Findings PR 47 quote, beside ``flash_chunked_bench --plans``
    (the equal-width cells' shapes: Findings PR 48). Eleven since PR 58:
    ``mhc_stream_bench`` (the residual streams' passes at the Xing4.0 cell's
    shape, looped against unrolled bodies, a branch against the ``jnp``
    form) is what ``ROW_TILE``'s and ``SLABS_A_TURN``'s comments in
    ``ops/pallas/mhc_stream.py`` and PERF.md's Findings PR 58 quote. Twelve
    since PR 60: ``bd_attention_bench`` (the block-diffusion mask kernels at
    the SDAR cell's shape over (tile, chunk) plans) is what ``_CHUNK_ROWS``'s
    comment in ``ops/pallas/block_diffusion_attention.py`` and PERF.md's
    Findings PR 60 quote. Thirteen since PR 65: ``dsa_bench`` (the
    learned-sparse-attention kernels at the Keye-VL-2.0 cell's shape, each
    stage alone, after a check against the dense plain-XLA form) is what
    PERF.md's Findings PR 65 quote."""
    assert SCRIPTS == [
        "adam_test", "aio_bench", "bd_attention_bench", "blocksparse_sweep",
        "dsa_bench", "flash_chunked_bench",
        "gdn_scan_bench", "gmm_tile_bench", "mhc_stream_bench",
        "mixer_elementwise_bench", "mla_flash_bench", "rows_to_tokens_bench",
        "swa_bench"]


@pytest.mark.parametrize("name", SCRIPTS)
def test_perf_script_imports_and_names_what_exists(name, monkeypatch, capsys):
    script = os.path.join(PERF, name + ".py")
    monkeypatch.setattr("sys.argv", [script, "--help"])
    monkeypatch.setattr("sys.path", list(sys.path))     # scripts prepend
    mod = importlib.import_module("tests.perf." + name)
    tree = ast.parse(open(script).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            src = importlib.import_module(node.module)
            for alias in node.names:
                if not hasattr(src, alias.name):    # a submodule, or gone
                    importlib.import_module(f"{node.module}.{alias.name}")
        elif isinstance(node, ast.Attribute) and isinstance(node.value,
                                                            ast.Name):
            bound = getattr(mod, node.value.id, None)
            if isinstance(bound, types.ModuleType):
                assert hasattr(bound, node.attr), \
                    f"{name}: {bound.__name__} has no {node.attr}"
    if "argparse" in vars(mod):
        with pytest.raises(SystemExit) as done:
            mod.main()
        assert done.value.code == 0
        assert "usage:" in capsys.readouterr().out


@pytest.mark.parametrize("plans,kernels,skipped", [
    (None, [("64x128", 20)] * 2, []),           # the plan the script derives
    ("64x128,64x256,64x1024", [("64x128", 20)] * 2 + [("64x256", 12)] * 2,
     ["64x1024"]),                              # the sweep; one tiles no S
], ids=["default", "plans"])
def test_flash_chunked_bench_rehearses_its_plans_on_the_cpu(
        plans, kernels, skipped, monkeypatch, tmp_path, capsys):
    """``flash_chunked_bench --rehearse-cpu [--plans BxC,...]``: the sweep's
    control flow in the interpreter at S 512 — one line a kernel and a plan
    with the grid steps a head walks (``fwd``, the single-pass ``bwd``) and
    one, ``bwd_xla``, for the XLA passes round the backward's kernel (delta,
    the dq slabs' sum), a ``skipped`` line for a plan that does
    not tile S, no time read off the chip, the lines in
    ``chiprun_out/<out>.jsonl`` — and the plan ``flash_attention`` picks at
    a cell's shape, which is what the script times without ``--plans``."""
    import json
    script = os.path.join(PERF, "flash_chunked_bench.py")
    argv = [script, "--rehearse-cpu", "--shapes", "laguna", "--out", "swept"]
    monkeypatch.setattr("sys.argv", argv + (["--plans", plans] if plans
                                            else []))
    monkeypatch.setattr("sys.path", list(sys.path))
    monkeypatch.chdir(tmp_path)
    mod = importlib.import_module("tests.perf.flash_chunked_bench")
    mod.main()
    with open(tmp_path / "chiprun_out" / "swept.jsonl") as f:
        lines = [json.loads(ln) for ln in f]
    assert [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")] == lines
    assert lines[0]["dtype"] == "bfloat16" and lines[0]["platform"] == "cpu"
    timed = [ln for ln in lines[1:] if "kernel" in ln]
    assert [ln["kernel"] for ln in timed] == ["fwd", "bwd", "bwd_xla"] * (
        len(kernels) // 2)
    assert all("ms" not in ln and ln["S"] == 512 for ln in timed)
    timed = [ln for ln in timed if ln["kernel"] != "bwd_xla"]   # no grid
    assert [(ln["plan"], ln["grid_steps_a_head"]) for ln in timed] == kernels
    assert [ln["plan"] for ln in lines[1:] if "skipped" in ln] == skipped
    B, H, Hkv, S, D = mod.SHAPES["laguna"]
    assert mod.picked_plan(B, H, Hkv, S, D, "bfloat16") == (512, 4096)
