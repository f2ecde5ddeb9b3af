"""Reduce a JAX profiler trace of the window to device metrics.

The trace (`<dir>/plugins/profile/<time>/*.xplane.pb`, read with
`jax.profiler.ProfileData`) holds, per TPU, an "XLA Ops" line whose
events are the HLO instructions as they ran, each named by its full
HLO text, nested (a `while` holds the ops of its body), and host lines
with the Python calls and the benchmark's `TraceAnnotation`s, on the
same clock. From it:

  busy_s      union of the device's op intervals, averaged over chips
  window_s    the traced window: the benchmark's `bench.window` host
              annotation, opened once the trace runs and closed before it
              stops
  device_ops  device self time by op (a Pallas kernel by its name, any
              other op by its HLO opcode), largest first
  kernels     per Pallas kernel: calls, device seconds, and the least
              time its operations and bytes allow (flops.py against
              peaks.json), summed over calls
  idle_gaps   idle device time in the window, by the innermost span of
              one host thread over the middle of each gap (the thread
              that drives the server), largest first
"""
import glob
import os
import re

import flops

_INST = re.compile(r"^%?([\w\-]+?)(?:\.\d+)?\s*=")
_KERNEL = 'custom_call_target="tpu_custom_call"'
WINDOW = "bench.window"          # host annotation over the traced window


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def label(name: str) -> str:
    """A Pallas kernel's name, or the HLO opcode of any other op."""
    m = _INST.match(name)
    return m.group(1) if m else name.split(" ", 1)[0]


def load(path: str):
    """({device plane: [(name, start_ns, end_ns)]}, [(name, start, end)]
    of the host thread that drives the window, whose spans label idle
    gaps, and the window: the span of the benchmark's `bench.window`
    annotation on that thread, or None)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                for ev in evs:
                    if ev[0] == WINDOW:
                        window = (float(ev[1]), float(ev[2]))
                        host = evs
    return devices, host, window


def union(intervals):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """{label: self seconds}: each event's duration less the part of it
    its nested events cover."""
    acc = {}
    stack = []                       # [name, start, end, child_ns]

    def close(ev):
        self_ns = (ev[2] - ev[1]) - ev[3]
        key = label(ev[0])
        acc[key] = acc.get(key, 0.0) + max(self_ns, 0.0) / 1e9

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][2]:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return acc


def kernels(events, peak: dict):
    out = {}
    for name, s, e in events:
        if _KERNEL not in name:
            continue
        _, _, res, ops = flops.parse_custom_call(name)
        k = label(name)
        fl, by = flops.cost(k, res, ops)
        d = out.setdefault(k, {"calls": 0, "seconds": 0.0, "bound_s": 0.0,
                               "flops": 0.0, "bytes": 0.0})
        d["calls"] += 1
        d["seconds"] += (e - s) / 1e9
        d["flops"] += fl
        d["bytes"] += by
        d["bound_s"] += max(fl / peak["flops_per_s"],
                            by / peak["hbm_bytes_per_s"])
    for d in out.values():
        d["bound"] = ("compute" if d["flops"] / peak["flops_per_s"]
                      > d["bytes"] / peak["hbm_bytes_per_s"] else "memory")
    return out


def idle_gaps(busy, host, lo: float, hi: float):
    """[(host span, idle seconds)]: the gaps between busy intervals in
    [lo, hi], each put down to the innermost host span over its
    middle."""
    spans = sorted(host, key=lambda x: x[1])
    acc = {}
    t = lo
    for s, e in busy + [[hi, hi]]:
        s, e = max(s, lo), min(e, hi)
        if s > t:
            mid = (t + s) / 2
            inner = [h for h in spans if h[1] <= mid <= h[2]]
            who = min(inner, key=lambda h: h[2] - h[1])[0] if inner \
                else "no host span"
            acc[who] = acc.get(who, 0.0) + (s - t) / 1e9
        t = max(t, e)
    return sorted(acc.items(), key=lambda kv: -kv[1])


def reduce(devices, host, window, peak: dict) -> dict:
    if not devices:
        raise ValueError("the trace holds no TPU ops")
    all_ev = [ev for evs in devices.values() for ev in evs]
    if window is None:
        window = (min(e[1] for e in all_ev), max(e[2] for e in all_ev))
    lo, hi = window
    busy_each = {}
    for dev, evs in devices.items():
        busy_each[dev] = union((max(s, lo), min(e, hi)) for _, s, e in evs
                               if e > lo and s < hi)
    busy_s = sum(sum(e - s for s, e in b) for b in busy_each.values()) \
        / len(busy_each) / 1e9
    first = sorted(devices)[0]
    ops = {}
    for evs in devices.values():
        for k, v in self_times(evs).items():
            ops[k] = ops.get(k, 0.0) + v / len(devices)
    ks = kernels(all_ev, peak)
    for d in ks.values():
        d["seconds"] /= len(devices)
        d["bound_s"] /= len(devices)
    return {"busy_s": busy_s, "window_s": (hi - lo) / 1e9,
            "device_ops": [[k, v] for k, v in
                           sorted(ops.items(), key=lambda kv: -kv[1])],
            "kernels": ks,
            "idle_gaps": [[k, v] for k, v in
                          idle_gaps(busy_each[first], host, lo, hi)]}


def reduce_dir(trace_dir: str, peak: dict) -> dict:
    return reduce(*load(find(trace_dir)), peak)
