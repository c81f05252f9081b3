"""Which figure of ``memory_stats()`` covers a program's temporaries?

    python3 benchmark/tools/memory_probe.py      (on the chip)

Holds 1 GiB live, then runs a program whose buffer assignment
(``memory_analysis()``) needs GiB-sized temporaries and lasts about a second,
while a thread samples the allocator's figures: ``bytes_in_use`` either rises
by the temporaries while the program runs or it does not, and
``bytes_reserved`` likewise.
"""
import json
import threading
import time

import jax
import jax.numpy as jnp

KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved")


def stats(tag, d):
    s = d.memory_stats() or {}
    print(json.dumps({"at": tag, **{k: s.get(k) for k in KEYS}}), flush=True)


def main():
    d = jax.devices()[0]
    print(json.dumps({"device": d.device_kind, "platform": d.platform}))
    stats("start", d)
    x = jnp.ones((16384, 16384), jnp.float32) * 1e-3        # 1 GiB live
    x.block_until_ready()
    stats("1 GiB live", d)

    def f(x):
        def body(_, y):
            return jnp.tanh(y @ x)          # a 1 GiB product every turn
        return jnp.sum(jax.lax.fori_loop(0, 8, body, x))

    c = jax.jit(f).lower(x).compile()
    m = c.memory_analysis()
    print(json.dumps({"memory_analysis": {
        "temp": m.temp_size_in_bytes, "argument": m.argument_size_in_bytes,
        "output": m.output_size_in_bytes,
        "code": m.generated_code_size_in_bytes}}), flush=True)
    stats("compiled", d)
    seen, done = [], threading.Event()

    def sample():
        while not done.is_set():
            s = d.memory_stats() or {}
            seen.append((s.get("bytes_in_use", 0), s.get("bytes_reserved", 0)))
            time.sleep(0.02)

    t = threading.Thread(target=sample)
    t.start()
    t0 = time.perf_counter()
    c(x).block_until_ready()
    seconds = time.perf_counter() - t0
    done.set()
    t.join()
    print(json.dumps({"program_s": seconds, "samples": len(seen),
                      "max_in_use_while_running": max(a for a, _ in seen),
                      "max_reserved_while_running": max(b for _, b in seen),
                      "max_sum_while_running": max(a + b for a, b in seen)}))
    stats("program ran", d)
    del c
    stats("executable dropped", d)


if __name__ == "__main__":
    main()
