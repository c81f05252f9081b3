"""The ``gmflow_base`` configuration's share of the benchmark's own unit
cases in tier-1: collected, not copied, from
``benchmark/tests/test_benchmark_gmflow.py`` as
``tests/test_benchmark_unit_searaft.py`` collects its file's (every case not
marked ``slow``; the one child process it starts is the ``--rehearse-tiny``
of ``train_gmflow_chairs``).  A file of its own so that the rehearsals do
not queue on one worker."""

import importlib.util
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")

_spec = importlib.util.spec_from_file_location(
    "benchmark_unit_cases_test_benchmark_gmflow",
    os.path.join(_DIR, "test_benchmark_gmflow.py"))
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)
for _name, _fn in vars(_cases).items():
    if (_name.startswith("test_") and callable(_fn)
            and _fn.__module__ == _cases.__name__ and not any(
                m.name == "slow" for m in getattr(_fn, "pytestmark", ()))):
        globals()[_name] = _fn
