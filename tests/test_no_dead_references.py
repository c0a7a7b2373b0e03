"""One benchmark, and no kernel chosen by the environment: the old
single-headline script, its environment names, the update-kernel switch and
the plug-in era's notebook were deleted in PR 31.  No tracked text may name
them again as if they existed."""

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FORBIDDEN = ("bench.py", "BENCH_", "DISTLEARN_TPU_FUSED", "docs/PERF.md")
SUFFIXES = (".py", ".md", ".json", ".toml", ".yml", ".sh", ".gitignore")
#: the benchmark's own paths (a `benchmark` PR's to edit: ROADMAP D12) and the
#: records that tell the history
SKIP_DIRS = {".git", ".jax_cache", "_scratch", "chiprun_out",
             os.path.join("tests", "benchmark"), "benchmarks"}
SKIP_FILES = {"CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md",
              "PERF_LEDGER.jsonl",
              os.path.join("tests", "test_no_dead_references.py")}


def _text_files():
    for dirpath, dirnames, filenames in os.walk(ROOT):
        rel_dir = os.path.relpath(dirpath, ROOT)
        dirnames[:] = [d for d in dirnames
                       if os.path.normpath(os.path.join(rel_dir, d))
                       not in SKIP_DIRS and d != "__pycache__"]
        for name in filenames:
            rel = os.path.normpath(os.path.join(rel_dir, name))
            if name.endswith(SUFFIXES) and rel not in SKIP_FILES:
                yield rel


def test_nothing_names_what_pr_31_deleted():
    hits = []
    for rel in _text_files():
        with open(os.path.join(ROOT, rel), encoding="utf-8",
                  errors="replace") as f:
            for n, line in enumerate(f, 1):
                hits += [f"{rel}:{n}: {word}" for word in FORBIDDEN
                         if word in line]
    assert not hits, "\n".join(hits)
