"""One repair the directory's rule ("nothing here is edited") leaves to a new
file.  ``test_swa_moe_family.py::test_the_cell_is_appended_and_the_manifest_
keeps_its_rules`` (PR 34) asserts that ITS cell, configuration and three
metrics are the manifest's LAST — true of the manifest of its day, false the
moment the next cell is appended, against ``benchmarks/README.md`` ("no test
reads the position of a cell or of a name").  Until a ``benchmark`` PR takes
those positions out of the test, it is handed the manifest as it was when its
cell was appended: everything up to and including its own entries.  What it
pins besides — the manifest's rules, the readers its cell lists — it still
checks, and the test that checks them on the WHOLE manifest is each later
family's (``test_mla_moe_family.py::test_the_cell_is_in_the_manifest_and_
the_manifest_keeps_its_rules``), which reads no position."""

import copy

import pytest


def manifest_when_appended(man: dict, cell: str) -> dict:
    """``man`` cut back to the moment ``cell`` was appended: the cells and
    configurations up to its own, the later cells' names taken off every
    metric's ``workloads``, and the metrics no remaining cell reports."""
    man = copy.deepcopy(man)
    names = [w["name"] for w in man["workloads"]]
    later = set(names[names.index(cell) + 1:])
    man["workloads"] = [w for w in man["workloads"]
                        if w["name"] not in later]
    used = {w["config"] for w in man["workloads"]}
    man["configs"] = [c for c in man["configs"] if c["name"] in used]
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if "workloads" in m:
                m["workloads"] = [n for n in m["workloads"]
                                  if n not in later]
        man[group] = [m for m in man[group] if m.get("workloads", True)]
    return man


@pytest.fixture(autouse=True)
def _the_manifest_of_its_day(request, monkeypatch):
    module = request.module
    if request.node.name == ("test_the_cell_is_appended_and_the_manifest_"
                             "keeps_its_rules") and hasattr(module, "CELL"):
        monkeypatch.setattr(module, "MAN",
                            manifest_when_appended(module.MAN, module.CELL))
