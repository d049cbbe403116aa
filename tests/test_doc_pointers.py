"""Every measurement script or record a document names exists.

README.md and docs/*.md point readers at what measures this system
(``benchmark/run.py``, ``chip_smoke.py``, ``tools/flash_bench.py``,
``ci.sh``) and at records of it. A deletion that leaves such a pointer
behind leaves a claim nobody can check. The docs also name files of the
reference repo (``gloo_run.py``, ``spark/common/store.py``, ...), so
only the patterns below are held to this checkout, not every path.
"""

from __future__ import annotations

import re
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
DOCUMENTS = [REPO_ROOT / "README.md", *sorted((REPO_ROOT / "docs").glob("*.md"))]

# a path under the two directories that hold measurement code
IN_TREE = re.compile(r"(?<![\w/.-])(?:benchmark|tools)/[\w./-]*")
# bench.py, *_bench.py (the reference's *_benchmark.py are not ours),
# ci.sh and chip_smoke.py, with or without a directory in front
SCRIPT = re.compile(
    r"(?<![\w/.-])(?:[\w.-]+/)*(?:(?:\w+_)?bench\.py|ci\.sh|chip_smoke\.py)\b")
# record files by the names the old rigs gave them
RECORD = re.compile(r"(?<![\w/.-])(?:BENCH|SCALING|MULTICHIP)_\w+(?:\.json)?")


def pointers(text: str):
    for pattern in (IN_TREE, SCRIPT, RECORD):
        for match in pattern.finditer(text):
            yield match.group(0).rstrip(".").removeprefix("./")


def exists(pointer: str) -> bool:
    if "/" in pointer:
        return (REPO_ROOT / pointer).exists()
    if RECORD.fullmatch(pointer):
        return any((REPO_ROOT / name).exists()
                   for name in (pointer, pointer + ".json"))
    # a bare script name: at the root, or where measurement code lives
    return any((REPO_ROOT / where / pointer).exists()
               for where in (".", "tools", "benchmark"))


@pytest.mark.parametrize("document", DOCUMENTS,
                         ids=[d.relative_to(REPO_ROOT).as_posix()
                              for d in DOCUMENTS])
def test_measurement_pointers_exist(document):
    dead = sorted({p for p in pointers(document.read_text())
                   if not exists(p)})
    assert not dead, (
        f"{document.relative_to(REPO_ROOT)} names measurement scripts or "
        f"records that are not in this checkout: {dead}")


def test_the_patterns_catch_what_they_are_for():
    found = set(pointers(
        "see `bench.py --step-bench`, scaling_bench.py, "
        "examples/tensorflow2_synthetic_benchmark.py, tools/flash_bench.py, "
        "benchmark/run.py. (BENCH_r18.json), SCALING_resnet_r5, "
        "BENCHMARK.json, ./ci.sh step 1j"))
    assert found == {"bench.py", "scaling_bench.py", "tools/flash_bench.py",
                     "benchmark/run.py", "BENCH_r18.json",
                     "SCALING_resnet_r5", "ci.sh"}
