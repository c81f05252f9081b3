"""One GMFlow window attention alone on the chip: the ``jnp`` body of
``models/gmflow.py window_attention`` (what XLA makes of it) against the
Mosaic kernels of ``ops/pallas_attention.py``, in one process.

For each of forward and backward (``jax.vjp`` at a given cotangent; the
``jnp`` body under ``jax.checkpoint`` as ``--remat save_corr`` runs it),
plain and shifted: device milliseconds a call, read from a profiler trace
of 10 calls (the union of the device's operations, so the rolls and
XLA's relayouts count), the wall clock's figure beside it, and the
longest operations by name.  The kernels' results are held against the
``jnp`` body's.  One JSON line on stdout::

    python scripts/bench_window_attention.py                # 32x48x64 maps
    python scripts/bench_window_attention.py --maps 2x56x128     # Sintel
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--maps", default="32x48x64",
                   help="maps x H/8 x W/8 (both images of 16 pairs at the "
                        "chairs crop)")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--calls", type=int, default=10)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace as btrace
    from raft_tpu.models import gmflow
    from raft_tpu.ops import pallas_attention

    B, h, w = (int(x) for x in args.maps.split("x"))
    C, dt = gmflow.CHANNELS, jnp.dtype(args.dtype)
    q, k, v, g = (jax.random.normal(key, (B, h * w, C), jnp.float32
                                    ).astype(dt)
                  for key in jax.random.split(jax.random.PRNGKey(0), 4))
    chosen = gmflow.window_attention_path(h, w, C, dt)
    real_path = gmflow.window_attention_path

    def timed(fn, *operands):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        wall = (time.perf_counter() - t0) / args.calls
        d = tempfile.mkdtemp(prefix="wa_trace_")
        jax.profiler.start_trace(d)
        for _ in range(args.calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        r = btrace.reduce_trace(d, 1, top=4)
        shutil.rmtree(d, ignore_errors=True)
        if r is None:      # no device plane: off the chip, no device time
            return out, {"wall_ms": wall * 1e3}
        return out, {"device_ms": r["busy_s"] / args.calls * 1e3,
                     "wall_ms": wall * 1e3,
                     "ops_ms": [[name, s / args.calls * 1e3]
                                for name, s in r["device_ops"]]}

    result = {"maps": [B, h, w], "dtype": dt.name, "path_chosen": chosen,
              "block_rows": pallas_attention.window_block_rows(
                  h // gmflow.SPLITS, w // gmflow.SPLITS, C, dt.itemsize),
              "device": jax.devices()[0].device_kind}
    for shift in (False, True):
        outs = {}
        for body in ("xla", "mosaic"):
            gmflow.window_attention_path = (
                real_path if body == "mosaic" else lambda *a: "xla")
            if body == "mosaic" and chosen != "mosaic":
                continue

            def attend(q, k, v):
                return gmflow.window_attention(q, k, v, h, w, shift, dt,
                                               True)

            def backward(q, k, v, g):
                return jax.vjp(attend, q, k, v)[1](g)

            fwd, t_f = timed(attend, q, k, v)
            bwd, t_b = timed(backward, q, k, v, g)
            outs[body] = (fwd,) + tuple(bwd)
            tag = "shifted" if shift else "plain"
            result[f"{body}_forward_{tag}"] = t_f
            result[f"{body}_backward_{tag}"] = t_b
        if len(outs) == 2:
            result[f"gap_{'shifted' if shift else 'plain'}"] = [
                float(np.abs(np.asarray(a, np.float32)
                             - np.asarray(b, np.float32)).max()
                      / np.abs(np.asarray(b, np.float32)).max())
                for a, b in zip(outs["mosaic"], outs["xla"])]
    gmflow.window_attention_path = real_path
    print(json.dumps(result))


if __name__ == "__main__":
    main()
