"""Makes the benchmark's own tests count in tier-1 ``pytest tests/``.

``benchmarks/tests/`` holds the tests of the yardstick (the check kinds
on made-up answers, the trace reduction on a recorded trace, the launch
readers, the memory statement, the loops, and CPU rehearsals of every
configuration through the real server, controls included). They live
beside what they test, outside ``tests/``; this module takes every test
function and fixture of every module there into its own namespace, each
test under ``test_<module>__<name>``, so that the tier-1 run collects
them here. A module added there is picked up by its file name. The
longest module (``test_rehearsal.py``: a dozen seeds and the controls of
every configuration, some six minutes) is collected by
``test_benchmark_seam_rehearsals.py`` instead, so that two workers share
the ten minutes.
"""

from __future__ import annotations

import importlib
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def _is_fixture(obj) -> bool:
    return type(obj).__name__ == "FixtureFunctionDefinition" or hasattr(obj, "_pytestfixturefunction")


SPLIT_OFF = ("test_rehearsal",)


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


ADOPTED = adopt(globals(), skip=SPLIT_OFF)


def test_every_module_of_the_benchmarks_tests_is_here():
    modules = {name.split("__")[0] for name in ADOPTED} | set(SPLIT_OFF)
    files = {p.stem for p in (ROOT / "benchmarks" / "tests").glob("test_*.py")}
    assert modules == files and len(ADOPTED) >= 40
