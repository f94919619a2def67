"""One repair the rule file cannot take from a PR that adds a cell.

``test_bm_manifest_rules.py``'s breaker "ask for 4 chips" sets the FIRST TWO
cells to four chips and expects the checker to refuse that. A quarter of the
cells, rounded down, may ask for four: from eight cells on two may, and the
breaker breaks nothing — the nested check of ``test_bm_rehearsal_runs.py``
appends an eighth cell to the seven the manifest has since PR 40 and failed
there. A PR that adds a cell may edit no file the benchmark already has, so
the breaker's edit is replaced HERE, at collection, by one that sets EVERY
cell (over a quarter however many cells later PRs append); the rule file's
bytes are the accepted ones. A ``benchmark`` PR should move this into the
rule file (``b["workloads"][:2]`` -> ``b["workloads"]``) and delete this
file.
"""


def _every_cell_asks_for_four(bench):
    for cell in bench["workloads"]:
        cell.update(chips=4)


def pytest_collection_modifyitems(items):
    for item in items:
        params = getattr(getattr(item, "callspec", None), "params", {})
        if params.get("says") == "ask for 4 chips" and "break_it" in params:
            params["break_it"] = _every_cell_asks_for_four
