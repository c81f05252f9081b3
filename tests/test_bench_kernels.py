"""scripts/bench_kernels.py --tiny: the tier-1 CPU interpret smoke.

Runs both fused kernels' microbench arms (fused vs unfused) once in
interpret mode and checks the one-line JSON record.
"""

import importlib.util
import json
import os.path as osp

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, osp.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_kernels_tiny_smoke(capsys):
    mod = _load_script("bench_kernels")
    mod.main(["--tiny"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    assert rec["metric"] == "kernel_fused_speedup_min"
    assert rec["unit"] == "x" and rec["value"] > 0
    cfg = rec["config"]
    assert cfg["tiny"] is True and cfg["interpret"] is True
    kers = cfg["kernels"]
    assert set(kers) == {"lookup_encoder", "gru"}
    for k in kers.values():
        assert k["fused_ms"] > 0 and k["unfused_ms"] > 0
        assert k["speedup"] > 0


def test_bench_kernels_rejects_unknown_kernel():
    import pytest

    mod = _load_script("bench_kernels")
    with pytest.raises(SystemExit):
        mod.main(["--tiny", "--kernels", "nope"])
