"""From a profiler trace (``.xplane.pb``) to numbers.

One reduction for every cell and every later PR: device busy time as the
union of the intervals in which an operation ran, time per operation name,
the longest idle gaps with what the host was doing in them.  Reads the file
with ``jax.profiler.ProfileData`` and nothing else.

The reduction works on plain tuples so that it can be checked on a
hand-built trace: ``reduce_events(device_events, host_events, window)``
with events as ``(name, start_ns, duration_ns)``.
"""

from __future__ import annotations

import glob
import os
import re

# Lines of a TPU device plane that hold one event per executed operation.
OP_LINES = ("XLA Ops",)
# Host events that only say "the profiler/executor is alive": useless as the
# answer to "what was the host doing".
_DULL = re.compile(r"^(\$|Thread|PjRt|Xla|TfrtCpu|tsl::|ThreadPool|"
                   r"EventMgr|ProcessBatch|__|<)")


_HLO = re.compile(r"^(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\(")
# Operations that only contain others (their events span their bodies'
# events on the same line): they count towards busy time, which is a union,
# and are left out of the list of operations that took most time.
CONTAINERS = ("while", "conditional", "call")


def label(text):
    """A device event is named by its whole HLO instruction; keep the
    instruction's name, its opcode and (where short) its result shape:
    ``%refine.72 custom-call bf16[16,324,2944]``."""
    m = _HLO.match(text)
    if not m:
        return text[:120]
    name, shape, op = m.groups()
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{name} {op}" + (f" {shape}" if len(shape) <= 64 else "")


def is_container(name):
    parts = name.split(" ")
    return len(parts) > 1 and parts[1] in CONTAINERS


def find_xplane(directory):
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def read_xplane(path):
    """-> (device_events by plane name, host_events).  A device plane is
    one named ``/device:TPU:<n>``; on a CPU rehearsal there is none and the
    host's XLA op events stand in (named as such by the caller)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name in OP_LINES:
                device.setdefault(plane.name, []).extend(
                    (label(e.name), int(e.start_ns), int(e.duration_ns))
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
    return device, host


def union_ns(intervals):
    total, end = 0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, window):
    """Idle intervals inside ``window`` = (start_ns, end_ns)."""
    out, cur = [], window[0]
    for s, d in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, window[1]) - cur))
        cur = max(cur, s + d)
        if cur >= window[1]:
            break
    if cur < window[1]:
        out.append((cur, window[1] - cur))
    return [(s, d) for s, d in out if d > 0]


def host_doing(host_events, t_ns):
    """Name of the shortest informative host event that covers ``t_ns``."""
    best = None
    for name, s, d in host_events:
        if s <= t_ns < s + d and not _DULL.match(name):
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "host idle or untraced"


def reduce_events(device_events, host_events=(), window=None, top=10):
    """``device_events``: (name, start_ns, dur_ns) of ONE device.

    Returns busy_s, window_s, per-name seconds, the ``top`` operations and
    the ``top`` longest idle gaps named by what the host was doing at the
    gap's middle."""
    ev = [e for e in device_events if e[2] > 0]
    if not ev:
        return None
    if window is None:
        window = (min(e[1] for e in ev), max(e[1] + e[2] for e in ev))
    ev = [e for e in ev if e[1] + e[2] > window[0] and e[1] < window[1]]
    iv = [(max(s, window[0]), min(s + d, window[1]) - max(s, window[0]))
          for _, s, d in ev]
    by_name, by_n = {}, {}
    for (name, _, _), (_, d) in zip(ev, iv):
        by_name[name] = by_name.get(name, 0) + d
        by_n[name] = by_n.get(name, 0) + 1
    idle = sorted(gaps(iv, window), key=lambda g: -g[1])[:top]
    return {
        "busy_s": union_ns(iv) / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "by_name_s": {k: v / 1e9 for k, v in by_name.items()},
        "by_name_n": by_n,
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(((k, v) for k, v in by_name.items()
                               if not is_container(k)),
                              key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_doing(host_events, s + d // 2), d / 1e9]
                      for s, d in idle],
    }


def reduce_trace(directory, chips, top=10):
    """Reduce the newest trace under ``directory``; busy and window are
    averaged over the ``chips`` device planes, names summed over them and
    the breakdown taken from the first."""
    device, host = read_xplane(find_xplane(directory))
    planes = sorted(device)[:chips]
    if not planes:
        return None
    per = [reduce_events(device[p], host, top=top) for p in planes]
    per = [p for p in per if p]
    if not per:
        return None
    out = dict(per[0])
    out["busy_s"] = sum(p["busy_s"] for p in per) / len(per)
    out["window_s"] = sum(p["window_s"] for p in per) / len(per)
    names, counts = {}, {}
    for p in per:
        for k, v in p["by_name_s"].items():
            names[k] = names.get(k, 0.0) + v
            counts[k] = counts.get(k, 0) + p["by_name_n"][k]
    out["by_name_s"], out["by_name_n"] = names, counts
    out["planes"] = planes
    return out


def seconds_matching(summary, pattern):
    """Total device seconds of operations whose name matches ``pattern``
    (and how many names matched): None when nothing matched."""
    rx = re.compile(pattern)
    hit = {k: v for k, v in summary["by_name_s"].items() if rx.search(k)}
    if not hit:
        return None
    return sum(hit.values()), sorted(hit)


def describe(directory, top=80):
    """What a trace holds, for reading one by hand: planes, their lines with
    event counts, and the ``top`` device operations with seconds and calls."""
    from jax.profiler import ProfileData

    path = find_xplane(directory)
    data = ProfileData.from_file(path)
    planes = [{"plane": p.name,
               "lines": [[ln.name, sum(1 for _ in ln.events)]
                         for ln in p.lines]} for p in data.planes]
    device, _ = read_xplane(path)
    ops, custom = [], []
    for name in sorted(device)[:1]:
        r = reduce_events(device[name], top=top)
        ops = [[k, v, r["by_name_n"][k]] for k, v in r["device_ops"]]
        custom = sorted([k, v, r["by_name_n"][k]]
                        for k, v in r["by_name_s"].items()
                        if "custom-call" in k)
    return {"file": path, "bytes": os.path.getsize(path), "planes": planes,
            "device_ops": ops, "custom_calls": custom}
