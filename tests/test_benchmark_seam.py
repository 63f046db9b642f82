"""Makes the benchmark's own tests count in tier-1 ``pytest tests/``.

``benchmarks/tests/`` holds the tests of the yardstick (the check kinds
on made-up answers, the trace reduction on a recorded trace, the launch
readers, the memory statement, the loops, and CPU rehearsals of every
configuration through the real server, controls included). They live
beside what they test, outside ``tests/``; this module takes every test
function and fixture of every module there into its own namespace, each
test under ``test_<module>__<name>``, so that the tier-1 run collects
them here. A module added there is picked up by its file name.

The longest module (``test_rehearsal.py``: a dozen seeds and the
controls of every configuration, some seventeen minutes on one worker,
and longer with every configuration ``BENCHMARK.json`` takes) is
collected in SHARDS instead: every ``test_benchmark_seam_rehearsals_<k>.py``
beside this file adopts all of it and keeps every n-th case, counted
from k in the order of the cases' ids (``conftest.py`` drops the others
at collection), n being the number of those files. ``--dist loadfile``
gives a file to one worker, so n workers share the rehearsals, a new
configuration's cases land in every shard, and one more shard is one
more copy of such a file.
"""

from __future__ import annotations

import importlib
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def _is_fixture(obj) -> bool:
    return type(obj).__name__ == "FixtureFunctionDefinition" or hasattr(obj, "_pytestfixturefunction")


SPLIT_OFF = ("test_rehearsal",)
SHARD_FILES = "test_benchmark_seam_rehearsals_*.py"


def adopt(into: dict, only: tuple = (), skip: tuple = ()) -> list[str]:
    """Every test function and fixture of ``benchmarks/tests`` (of the
    modules ``only``, or of all but ``skip``) into the namespace ``into``."""
    names = []
    for path in sorted((ROOT / "benchmarks" / "tests").glob("test_*.py")):
        if (only and path.stem not in only) or path.stem in skip:
            continue
        module = importlib.import_module(f"benchmarks.tests.{path.stem}")
        for name, obj in vars(module).items():
            if name.startswith("test_") and callable(obj):
                adopted = f"test_{path.stem[5:]}__{name[5:]}"
                into[adopted] = obj
                names.append(adopted)
            elif _is_fixture(obj):
                if name in into and into[name] is not obj:
                    raise RuntimeError(f"two fixtures named {name!r} under benchmarks/tests")
                into[name] = obj
    return names


def adopt_shard(into: dict) -> tuple[list[str], tuple[int, int]]:
    """All of ``SPLIT_OFF`` into the shard module ``into``; the names,
    and the module's ``(k, n)``: k from its own file name, n the number
    of shard files. The module keeps both as ``ADOPTED`` and ``SHARD``,
    where ``conftest.py`` reads them."""
    files = sorted(p.stem for p in pathlib.Path(__file__).parent.glob(SHARD_FILES))
    return adopt(into, only=SPLIT_OFF), (files.index(into["__name__"].rpartition(".")[2]), len(files))


ADOPTED = adopt(globals(), skip=SPLIT_OFF)


def test_every_module_of_the_benchmarks_tests_is_here():
    modules = {name.split("__")[0] for name in ADOPTED} | set(SPLIT_OFF)
    files = {p.stem for p in (ROOT / "benchmarks" / "tests").glob("test_*.py")}
    assert modules == files and len(ADOPTED) >= 40


def _collected(*paths) -> list[str]:
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q", "-p", "no:cacheprovider", *paths],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    return [line for line in out.stdout.splitlines() if "::" in line]


def test_the_shards_hold_every_rehearsal_case_once():
    """The union of the shards is the whole of ``test_rehearsal.py``,
    and no case is collected twice."""
    shards = sorted(str(p.relative_to(ROOT)) for p in pathlib.Path(__file__).parent.glob(SHARD_FILES))
    assert len(shards) >= 2
    theirs, per_shard = [], {path: [] for path in shards}
    for line in _collected("benchmarks/tests/test_rehearsal.py", *shards):  # one process collects both
        path, name = line.split("::", 1)
        if path not in per_shard:
            theirs.append(name)
        elif name.startswith("test_rehearsal__"):
            per_shard[path].append("test_" + name[len("test_rehearsal__"):])
    ours = [name for names in per_shard.values() for name in names]
    assert len(theirs) >= 35 and sorted(ours) == sorted(theirs)
    assert len(set(ours)) == len(ours)
    # round robin over the sorted ids: no shard is more than one case ahead
    sizes = [len(names) for names in per_shard.values()]
    assert max(sizes) - min(sizes) <= 1, sizes
    # and each configuration's dozen seeds are spread over all of them
    def configs(names):
        return {n[n.index("[") + 1:].rsplit("-", 1)[0] for n in names if "correct_on_a_dozen_seeds" in n}

    assert all(configs(names) == configs(theirs) for names in per_shard.values())
