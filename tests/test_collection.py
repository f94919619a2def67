"""CI-style guards on suite collection.

1. The whole suite must COLLECT cleanly: a single bad import (e.g. the
   `from jax import shard_map` that broke tests/test_csr.py on the
   pinned jax 0.4.37) silently gates every test in the affected module;
   with `--continue-on-collection-errors` in the tier-1 runner the suite
   still "passes" while whole files never run.
2. Every test FILE that slow-marks anything must still collect at least
   one fast (non-slow) test: the tier-1 runner deselects `-m 'not
   slow'`, so a file whose tests all drift behind @pytest.mark.slow
   drops out of tier-1 entirely — coverage evaporating one decorator at
   a time, with the suite still green.

3. What tier-1's wall rests on (tests/conftest.py): the flash kernels'
   tests stay dealt over their ``test_flash_*.py`` files with no case lost
   or run twice, and no whole-step compile of a routed cell joins tier-1
   unnoticed (each is minutes of one compile that nothing can share).

All guards read ONE subprocess collection (`--collect-only -q -m 'not
slow'`): it fails loudly on any collection error, reports the total
collected count (before deselection), and lists the surviving fast node
ids per file.
"""

import collections
import functools
import os
import re
import subprocess
import sys

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)


@functools.lru_cache(maxsize=1)
def _collect_fast():
    """(total_collected, {file -> its fast tests' ids, file name cut off})
    from one subprocess collection — shared by every guard (a full
    re-collect costs ~35 s of suite imports, and the tier-1 wall is a real
    budget)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", "not slow", "-p", "no:cacheprovider", "-p", "no:xdist",
         "-p", "no:randomly", "tests/"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    out = proc.stdout + proc.stderr
    # without --continue-on-collection-errors any collection error → rc != 0
    assert proc.returncode == 0, \
        f"collection failed (rc={proc.returncode}):\n{out[-4000:]}"
    # "N/M tests collected (X deselected)" with -m; "M tests collected"
    # without any deselection
    m = re.search(r"(?:(\d+)/)?(\d+) tests collected", out)
    assert m, out[-2000:]
    total = int(m.group(2))
    fast_per_file = {}
    for line in proc.stdout.splitlines():
        if "::" in line:
            path, case = line.split("::", 1)
            fast_per_file.setdefault(path.split("/")[-1], []).append(case)
    return total, fast_per_file


def test_suite_collects_without_errors():
    total, _ = _collect_fast()
    assert total >= 438, total


def test_slow_marked_files_keep_fast_coverage():
    _, fast_per_file = _collect_fast()
    slow_files = []
    for name in sorted(os.listdir(TESTS_DIR)):
        if not (name.startswith("test_") and name.endswith(".py")):
            continue
        with open(os.path.join(TESTS_DIR, name)) as f:
            if "pytest.mark.slow" in f.read():
                slow_files.append(name)
    assert slow_files, "expected at least one slow-marked file in tests/"
    orphaned = [f for f in slow_files if not fast_per_file.get(f)]
    assert not orphaned, (
        f"these files slow-mark tests and no longer collect ANY fast "
        f"test — tier-1 lost them entirely: {orphaned}. Keep (or add) a "
        f"fast sibling test per file, or un-mark something.")


# the whole steps tier-1 compiles for a described chip: the dense cell's and
# the first routed cell's (under a minute each); every later cell's is
# slow-marked, its kernels compiled alone at the cell's shapes
WHOLE_STEP_COMPILES = [
    "test_train_step_compiles_for_one_chip_with_flash_and_fits",
    "test_olmoe_step_compiles_for_one_chip_with_its_scopes_and_fits",
]


def test_tier1_keeps_its_flash_cases_and_takes_no_new_whole_step_compile():
    _, fast_per_file = _collect_fast()
    flash = {f: cases for f, cases in fast_per_file.items()
             if f.startswith("test_flash_")}
    cases = collections.Counter(c for found in flash.values() for c in found)
    assert sum(cases.values()) == 256, {f: len(c) for f, c in flash.items()}
    twice = sorted(c for c, n in cases.items() if n > 1)
    assert not twice, f"under two of {sorted(flash)}: {twice}"
    joined = sorted({
        name for found in fast_per_file.values()
        for name in (c.split("[")[0] for c in found)
        if re.search(r"_step_compiles_for_one_chip_with_\w+_and_fits$", name)
    } - set(WHOLE_STEP_COMPILES))
    assert not joined, (
        f"a whole-step compile joined tier-1: {joined}; a routed cell's "
        f"takes minutes on one worker — slow-mark it beside its siblings in "
        f"tests/test_tpu_compile.py")
