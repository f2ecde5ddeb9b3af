"""Spans in a bounded ring buffer, Chrome-trace dumpable.

Two ways in:

  * `add_span` — a completed span with *explicit* timestamps. One solve
    request's lifecycle crosses several pump iterations (submit → queue
    wait → flush/solve → reward → Q-update), so request spans are
    stamped from the server's injectable clock: the server knows
    `submitted_at`, the batcher stamps solve start/end on each
    `FlushResult`, and `_complete` emits the whole request tree at once.
  * `span` — an inline span over a code block, timed by the tracer's
    clock. Inline spans nest through a `contextvars` stack of open
    spans: each gets an id (`sid`) and its enclosing span's id
    (`parent`), and inherits that span's row (`tid`) and category. An
    optional `annotate` hook — a callable from a span name and its args
    to a context manager, `jax.profiler.TraceAnnotation` in the server —
    is entered around the same block, so every inline span is also a
    host event on the profiler's clock, the one device ops use.

Code below the server records without a tracer argument: the server
makes its tracer the context's `current()` one around `step()` and AOT
warmup (`use`), and the module-level `span()` records into it, or does
nothing when no tracer is set. `note()` adds args to an open span once
they are known (they reach the ring, not the profiler event, whose args
are fixed when it opens).

The buffer is a `deque(maxlen=capacity)` — a long-running server keeps
the most recent spans and never grows without bound (same policy as the
telemetry latency reservoir). `chrome_trace()` renders the standard
Chrome trace-event JSON (``chrome://tracing`` / Perfetto): complete
("ph": "X") events, microsecond timestamps, one `tid` per request (or
flush) id so the viewer lays concurrent requests on separate rows.

Recording is cheap (one dataclass + deque append under a lock) and
fail-open (DESIGN.md §8.1): `add_span` callers wrap it in the
`fail_open` guard, and `span` guards its own hook and recording,
counting a fault in `registry` (``repro_obs_errors_total``) — a broken
tracer or hook can never break `submit()`/`step()`.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import json
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from repro.obs.metrics import fail_open


@dataclasses.dataclass(frozen=True)
class Span:
    name: str                 # phase: submit / queue_wait / solve / ...
    t0: float                 # [seconds] start, in the recording clock
    t1: float                 # [seconds] end
    tid: int = 0              # request or flush id (Chrome row)
    cat: str = "request"
    args: Optional[Dict[str, object]] = None
    sid: int = 0              # inline span id (0: not an inline span)
    parent: Optional[int] = None   # sid of the enclosing span

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


class _Open:
    """An inline span while its block runs (the context's stack)."""
    __slots__ = ("tracer", "name", "sid", "tid", "cat", "args")

    def __init__(self, tracer, name, sid, tid, cat, args):
        self.tracer, self.name, self.sid = tracer, name, sid
        self.tid, self.cat, self.args = tid, cat, args


_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_tracer", default=None)
_OPEN: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_open_spans", default=())


class Tracer:
    def __init__(self, capacity: int = 4096,
                 clock=time.perf_counter,
                 annotate: Optional[Callable] = None):
        self.capacity = int(capacity)
        self.clock = clock
        self.annotate = annotate
        self.registry = None    # where `span` counts faults (Observability)
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=self.capacity)
        self._sids = itertools.count(1)

    # -- recording ---------------------------------------------------------
    def add_span(self, name: str, t0: float, t1: float, tid: int = 0,
                 cat: str = "request", sid: int = 0,
                 parent: Optional[int] = None, **args) -> Span:
        """Record a completed span with caller-supplied timestamps."""
        span = Span(str(name), float(t0), float(t1), int(tid), str(cat),
                    dict(args) or None, int(sid), parent)
        with self._lock:
            self._spans.append(span)
        return span

    @contextlib.contextmanager
    def span(self, name: str, tid: Optional[int] = None,
             cat: Optional[str] = None, parent: Optional[int] = None,
             **args):
        """Inline span over a code block, timed by the tracer's clock;
        yields its id. `parent`, `tid` and `cat` default to the
        innermost open span of this tracer (else None, 0, "request")."""
        stack = _OPEN.get()
        outer = stack[-1] if stack and stack[-1].tracer is self else None
        if parent is None and outer is not None:
            parent = outer.sid
        if tid is None:
            tid = outer.tid if outer is not None else 0
        if cat is None:
            cat = outer.cat if outer is not None else "request"
        op = _Open(self, name, next(self._sids), tid, cat, args)
        token = _OPEN.set(stack + (op,))
        hook = self._enter(name, args)
        t0 = self.clock()
        try:
            yield op.sid
        finally:
            t1 = self.clock()
            if hook is not None:
                self._exit(hook)
            _OPEN.reset(token)
            self._record(name, t0, t1, tid, cat, op.sid, parent, **op.args)

    @fail_open
    def _enter(self, name: str, args: dict):
        if self.annotate is None:
            return None
        cm = self.annotate(name, **args)
        cm.__enter__()
        return cm

    @fail_open
    def _exit(self, hook) -> None:
        hook.__exit__(None, None, None)

    @fail_open
    def _record(self, *a, **kw) -> None:
        self.add_span(*a, **kw)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    # -- reading -----------------------------------------------------------
    def spans(self, tid: Optional[int] = None,
              cat: Optional[str] = None) -> List[Span]:
        """Recorded spans, oldest first. A `tid` is a row within a
        category, so filtering by it reads the request rows unless
        `cat` names another category."""
        with self._lock:
            out = list(self._spans)
        if tid is not None:
            cat = "request" if cat is None else cat
            out = [s for s in out if s.tid == tid]
        if cat is not None:
            out = [s for s in out if s.cat == cat]
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    # -- export ------------------------------------------------------------
    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object ({"traceEvents": [...]}). Each
        category is a process of its own (its `tid`s are its rows), and
        an inline span carries its `sid` and `parent` among its args."""
        events = []
        pids = {"request": 0}
        for s in self.spans():
            ev = {"name": s.name, "cat": s.cat, "ph": "X",
                  "ts": s.t0 * 1e6, "dur": max(s.duration, 0.0) * 1e6,
                  "pid": pids.setdefault(s.cat, len(pids)), "tid": s.tid}
            args = dict(s.args or {})
            if s.sid:
                args["sid"] = s.sid
            if s.parent is not None:
                args["parent"] = s.parent
            if args:
                ev["args"] = {k: _jsonable(v) for k, v in args.items()}
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump(self, path: str) -> None:
        """Write `chrome_trace()` to `path` (open in chrome://tracing)."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


# -- the context's tracer ----------------------------------------------------

def current() -> Optional[Tracer]:
    """The tracer code below the server records into (None: off)."""
    return _CURRENT.get()


@contextlib.contextmanager
def use(tracer: Optional[Tracer]):
    """Make `tracer` the context's `current()` one over a block."""
    token = _CURRENT.set(tracer)
    try:
        yield tracer
    finally:
        _CURRENT.reset(token)


def span(name: str, **kw):
    """`current().span(name, ...)`, or a no-op when no tracer is set."""
    tracer = _CURRENT.get()
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, **kw)


def note(name: str, **args) -> None:
    """Add `args` to the innermost open span called `name` (no-op when
    none is open)."""
    for op in reversed(_OPEN.get()):
        if op.name == name:
            op.args.update(args)
            return


def _jsonable(v):
    if isinstance(v, (str, int, bool)) or v is None:
        return v
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)
