"""The closed-loop cases of ``benchmark/tests/test_closed_loop.py`` and
the tiny cells they run on, collected here so that tier 1 counts them:
the feeder every closed-loop cell depends on is then guarded by the
count.  The cases stay where the benchmark keeps them; this file loads
them (and the fixtures of ``benchmark/tests/conftest.py``) by path."""
import importlib.util
import os
import sys

_HERE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")


def _load(name, file):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, file))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_fixtures = _load("benchmark_tests_conftest", "conftest.py")
# the cases import ``conftest`` for the checkout's root: theirs, while
# they load
_ours = sys.modules.get("conftest")
sys.modules["conftest"] = _fixtures
try:
    _cases = _load("benchmark_tests_closed_loop", "test_closed_loop.py")
finally:
    if _ours is None:
        del sys.modules["conftest"]
    else:
        sys.modules["conftest"] = _ours

tiny_closed_cell = _fixtures.tiny_closed_cell
drive = _fixtures.drive
globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
