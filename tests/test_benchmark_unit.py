"""The benchmark's own unit cases in tier-1 (ROADMAP C10): the readers,
the trace reducer and the comparison arithmetic that every ledger row
rests on are guarded by the run the driver makes after each PR.

Collected, not copied: every case of ``benchmark/tests/test_benchmark.py``
and ``test_benchmark_gma.py`` that is not marked ``slow`` is imported here
under its own name (the slow ones start ``benchmark/run.py
--rehearse-tiny`` as a child process for minutes; the one such run tier-1
does make is ``train_gma_chairs``'s).  Which cases exist is decided by
those files alone, the same in every worker."""

import importlib.util
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")

for _file in ("test_benchmark", "test_benchmark_gma"):
    _spec = importlib.util.spec_from_file_location(
        f"benchmark_unit_cases_{_file}", os.path.join(_DIR, _file + ".py"))
    _cases = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_cases)
    for _name, _fn in vars(_cases).items():
        if (_name.startswith("test_") and callable(_fn)
                and _fn.__module__ == _cases.__name__ and not any(
                    m.name == "slow"
                    for m in getattr(_fn, "pytestmark", ()))):
            globals()[_name] = _fn
