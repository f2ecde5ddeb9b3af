"""Split the device's idle time in a traced window by what the host did.

`trace_reduce.py` gives the window's busy and idle time and labels each
idle gap by the innermost span of the thread that drives the server.
That says where the thread waits, not what for. This module reads the
same trace for two more splits:

  idle_by_phase    idle seconds by the innermost of the program's flush
                   spans (`flush`, `flush.stack`, `flush.dispatch`,
                   `flush.fetch`, `flush.complete`; the server writes
                   them into the profiler's trace as annotations) on the
                   driving thread over the middle of each gap; a gap
                   under none of them counts as `between_flushes`
  idle_transfer_s  idle seconds in which any host thread runs the
                   runtime's relayout or transfer work (TRANSFER, the
                   event names of a v5e trace)
  flushes          `flush` spans that start in the window
  window_s         as trace_reduce.py computes it

A trace of a program without the flush annotations has `flushes` 0 and
all its idle time `between_flushes`.

The per-layer metrics read this through `read(rec)`: it takes the
newest trace where run.py writes traces and uses it only if its window
is the one the record's `trace` was reduced from.
"""
import glob
import os

import bench
import trace_reduce

TRACE_DIR = os.path.join(bench.REPO, ".bench", "trace")   # run.py's
FLUSH = "flush"
IN_FLUSH = ("flush", "flush.stack", "flush.dispatch", "flush.fetch")
BETWEEN = "between_flushes"
TRANSFER = frozenset({
    "XlaLinearize", "Transpose", "H2D Dispatch", "D2H Dispatch",
    "tpu::System::TransferToDevice", "tpu::System::TransferFromDevice"})


def base(name: str) -> str:
    """An annotation's name without the args the profiler appends."""
    return name.split("#", 1)[0]


def host_events(path: str):
    """[(name, start_ns, end_ns)] of every host thread."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def gaps(busy, lo: float, hi: float):
    """[(start, end)]: the idle intervals of [lo, hi] between the merged
    busy intervals, as trace_reduce.idle_gaps walks them."""
    out, t = [], lo
    for s, e in busy + [[hi, hi]]:
        s, e = max(s, lo), min(e, hi)
        if s > t:
            out.append((t, s))
        t = max(t, e)
    return out


def idle_by_phase(busy, host, lo: float, hi: float) -> dict:
    """{phase: idle seconds}: each gap put down to the innermost flush
    span of `host` over its middle, else to `between_flushes`."""
    spans = sorted(((n, s, e) for n, s, e in ((base(n), s, e)
                                              for n, s, e in host)
                    if n == FLUSH or n.startswith(FLUSH + ".")),
                   key=lambda h: h[1])
    acc, active, i = {}, [], 0
    for s, e in gaps(busy, lo, hi):             # middles only increase
        mid = (s + e) / 2
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [h for h in active if h[2] >= mid]
        who = min(active, key=lambda h: h[2] - h[1])[0] if active \
            else BETWEEN
        acc[who] = acc.get(who, 0.0) + (e - s) / 1e9
    return acc


def idle_transfer_s(busy, threads, lo: float, hi: float) -> float:
    """Idle seconds of [lo, hi] covered by TRANSFER work on any of
    `threads`' events."""
    work = trace_reduce.union((s, e) for n, s, e in threads
                              if base(n) in TRANSFER)
    total, j = 0.0, 0
    for s, e in gaps(busy, lo, hi):
        while j < len(work) and work[j][1] <= s:
            j += 1
        k = j
        while k < len(work) and work[k][0] < e:
            total += min(e, work[k][1]) - max(s, work[k][0])
            k += 1
    return total / 1e9


def reduce_file(path: str) -> dict:
    devices, host, window = trace_reduce.load(path)
    if not devices:
        raise ValueError("the trace holds no TPU ops")
    if window is None:
        all_ev = [ev for evs in devices.values() for ev in evs]
        window = (min(e[1] for e in all_ev), max(e[2] for e in all_ev))
    lo, hi = window
    busy = trace_reduce.union(
        (max(s, lo), min(e, hi)) for _, s, e in devices[sorted(devices)[0]]
        if e > lo and s < hi)
    return {"window_s": (hi - lo) / 1e9,
            "idle_by_phase": idle_by_phase(busy, host, lo, hi),
            "idle_transfer_s": idle_transfer_s(busy, host_events(path),
                                               lo, hi),
            "flushes": sum(1 for n, s, _ in host
                           if base(n) == FLUSH and lo <= s <= hi)}


_MEMO = {}


def read(rec):
    """This run's reduction, or None for an untraced record or when no
    trace of the record's window is found."""
    t = rec.get("trace")
    paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not t or not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = reduce_file(path)
    out = _MEMO[key]
    return out if out["window_s"] == t["window_s"] else None
