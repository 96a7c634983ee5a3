"""The benchmark's own tests, under tier-1.

Every verdict in ``PERF_LEDGER.jsonl`` is computed by ``benchmarks/``
from what the product emits (span names and categories, the xplane's
layout, the compiled block's text), so a change to either side has to
meet ``benchmarks/tests/`` in the same run as the product's tests.
The driver's command collects ``tests/`` only; this module hands it
every ``test_*`` function of every ``benchmarks/tests/test_*.py``, one
class a module (two of them share function names), each function a case
of its own with its marks.  No body is copied: the modules are loaded
from their own paths, so they find their data by their own ``__file__``.
"""

import glob
import importlib.util
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "tests")

for _path in sorted(glob.glob(os.path.join(_DIR, "test_*.py"))):
    _stem = os.path.splitext(os.path.basename(_path))[0]
    _spec = importlib.util.spec_from_file_location(
        "benchmarks_tests_" + _stem, _path)
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    _cases = {n: staticmethod(f) for n, f in vars(_mod).items()
              if n.startswith("test_") and callable(f)}
    assert _cases, _path
    _name = "Test" + _stem[len("test_"):].title().replace("_", "")
    globals()[_name] = type(_name, (), _cases)
