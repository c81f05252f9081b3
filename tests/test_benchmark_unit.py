"""The benchmark's own unit cases in tier-1 (ROADMAP C10): the readers,
the trace reducer and the comparison arithmetic that every ledger row
rests on are guarded by the run the driver makes after each PR.

Collected, not copied: every case of ``benchmark/tests/test_benchmark.py``
that is not marked ``slow`` (those start ``benchmark/run.py
--rehearse-tiny`` as a child process for minutes) is imported here under
its own name.  Which cases exist is decided by that file alone, the same
in every worker."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests", "test_benchmark.py")
_spec = importlib.util.spec_from_file_location("benchmark_unit_cases", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

for _name, _fn in vars(_cases).items():
    if _name.startswith("test_") and callable(_fn) and not any(
            m.name == "slow" for m in getattr(_fn, "pytestmark", ())):
        globals()[_name] = _fn
