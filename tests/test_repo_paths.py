"""The documents name only what the tree holds.

A document that tells an operator to run a script, or sends a reader to a
module, is checked against the checkout: every repository path it names
in backticks or on a command line exists. ``PERF.md``, ``CHANGES.md`` and
``ROADMAP.md`` are history (they name what PRs deleted) and are no cases.
"""

from __future__ import annotations

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

# a path counts when it starts at one of this repository's own directories:
# PARITY.md also names the reference's files (``main3d.py``, ``communicator/``)
ROOT_DIRS = ("triton_client_tpu", "benchmarks", "perf", "tests", "docs", "examples", "data")
# the least number of paths a document's case must find, so that it cannot
# pass by finding nothing (docs/LINTING.md names five files in all)
DOCUMENTS = {
    "README.md": 10,
    "docs/OPERATIONS.md": 10,
    "docs/LINTING.md": 5,
    "PARITY.md": 10,
    "ci.sh": 10,
    ".claude/skills/verify/SKILL.md": 10,
}
PATH = re.compile(r"(?<![\w./<>-])(?:%s)/[\w./*<>{}$-]*" % "|".join(ROOT_DIRS))


def named_paths(text: str) -> list[str]:
    """The repository paths ``text`` names, each once. A test id's
    ``::case`` and ``[param]``, a ``:line`` and a sentence's full stop are
    no part of a path, and one with a placeholder (``<k>``, ``{name}``,
    ``$var``) names no one file."""
    found = []
    for match in PATH.finditer(text):
        path = match.group().rstrip(".")
        if not re.search(r"[<>{}$]", path) and path not in found:
            found.append(path)
    return found


def _held(path: str) -> bool:
    """A file or directory of the checkout, a glob that matches something,
    or a module spelled as one (``perf/_harness``,
    ``benchmarks/trace_reduce.reduce_dir``)."""
    if "*" in path:
        return any(ROOT.glob(path))
    directory, _, name = path.rpartition("/")
    return (ROOT / path).exists() or (ROOT / directory / (name.split(".")[0] + ".py")).exists()


def missing(paths) -> list[str]:
    return [p for p in paths if not _held(p)]


@pytest.mark.parametrize("document, least", DOCUMENTS.items())
def test_every_path_a_document_names_exists(document, least):
    paths = named_paths((ROOT / document).read_text())
    assert len(paths) >= least, paths
    assert missing(paths) == []


def test_every_perf_script_is_named_by_a_record():
    """A probe under ``perf/`` stays while ``PERF.md`` cites it as evidence,
    the README tells an operator to run it or ``ci.sh`` runs it; a script
    that only other scripts import is named beside its caller."""
    assert missing(named_paths("run `python perf/profile_slo.py --slo-ms 250`, then tests/test_slo.py::test_x.")) == [
        "perf/profile_slo.py"
    ]
    records = "\n".join((ROOT / name).read_text() for name in ("PERF.md", "README.md", "ci.sh"))
    scripts = sorted(str(p.relative_to(ROOT)) for p in (ROOT / "perf").glob("*.py"))
    assert len(scripts) >= 5
    assert [s for s in scripts if s not in records] == []
