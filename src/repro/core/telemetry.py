"""Host-side metrics registry for the wire stack: counters, gauges,
histograms and host spans.

PR 6 proved the zero-cost-when-idle counter pattern (fault containment,
DESIGN.md §8); this module generalises it into the online half of the
``repro.obs`` observability subsystem (DESIGN.md §9).  Counters emitted
*inside* jitted/shard_map regions reach the host through
``jax.debug.callback`` into process-global stores.

Metric kinds (all keyed by dotted tags — ``wire.hop_bytes``,
``kernel.calls.matmul.t8``, ``step.grad_norm``, ...):

* **counter** — float sum (:func:`emit` / :func:`record`).
* **gauge**   — last value wins (:func:`emit_gauge` / :func:`record_gauge`).
* **histogram** — running count/sum/min/max plus a bounded, deterministic
  stride-decimated sample for quantiles (:func:`emit_hist` /
  :func:`record_hist`).
* **host span** — :func:`host_span` measures a host-side region with real
  wall clock (train-loop steps, bench reps) and always enters a
  ``jax.profiler.TraceAnnotation`` of the same name, so the region lands in
  a profiler trace beside the device ops.

Device time has no recorder here.  Traced code names its regions with
``jax.named_scope`` (``embed``, ``layers``, ``mamba.ssd``, ``optimizer``,
``kernel.<op>.<fmt>``, ``wire.hop.<fmt>``, ...): pure metadata on the HLO
ops, present in every run, which a profiler trace of the device attributes
time to (DESIGN.md §9).

Usage::

    with telemetry.capture() as counters:
        fn = jax.jit(step)          # trace INSIDE the capture scope
        fn(...)
    counters["wire.escalations"]    # accumulated across all calls
    telemetry.spans()               # list of recorded host span dicts
    telemetry.snapshot()            # everything, export-ready

Two gates keep the cost at zero when nobody is listening:

* every ``emit*`` is a **trace-time** no-op unless a capture scope is
  active when the emitting code is *traced* — a jitted function traced
  outside ``capture()`` carries no callbacks (and no extra ops at all: the
  zero-op property is asserted on the jaxpr in tests/test_obs.py).
  Conversely, one traced inside keeps emitting for its cached lifetime;
  tests that need isolation run in fresh subprocesses.
* at runtime, values arriving while no capture is active are dropped.

Under shard_map every device emits, so per-device quantities arrive
``N``-fold: counters sum N devices' values and histograms take N samples
per logical event.  Emit pre-reduced values or document the multiplicity
at the tag (DESIGN.md §9 lists the rule per tag namespace).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import threading
import time

import jax

_LOCK = threading.Lock()
_COUNTERS: collections.Counter = collections.Counter()
_GAUGES: dict = {}
_HISTS: dict = {}
_SPANS: list = []
_DEPTH = 0  # capture scopes may nest; any active scope enables recording


def enabled() -> bool:
    """True while at least one :func:`capture` scope is active."""
    return _DEPTH > 0


# ---------------------------------------------------------------------------
# host-side recorders (the callback targets; also callable directly)
# ---------------------------------------------------------------------------


def record(tag: str, value) -> None:
    """Counter accumulate."""
    if _DEPTH > 0:
        with _LOCK:
            _COUNTERS[tag] += float(value)


def record_gauge(tag: str, value) -> None:
    """Gauge: last value wins."""
    if _DEPTH > 0:
        with _LOCK:
            _GAUGES[tag] = float(value)


class _Hist:
    """count/sum/min/max + a bounded deterministic sample.

    When the sample buffer fills it is decimated to every other element and
    the keep-stride doubles — no RNG (reproducible runs), bounded memory,
    and the surviving sample stays spread over the whole recording window
    instead of privileging the first CAP values.
    """

    CAP = 4096
    __slots__ = ("count", "total", "vmin", "vmax", "sample", "_stride", "_skip")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.sample: list = []
        self._stride = 1
        self._skip = 0

    def add(self, v: float) -> None:
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)
        self._skip += 1
        if self._skip >= self._stride:
            self._skip = 0
            self.sample.append(v)
            if len(self.sample) >= self.CAP:
                self.sample = self.sample[::2]
                self._stride *= 2

    def summary(self) -> dict:
        s = sorted(self.sample)
        q = lambda p: s[min(len(s) - 1, int(p * len(s)))] if s else float("nan")
        return {
            "count": self.count,
            "sum": self.total,
            "min": self.vmin if self.count else float("nan"),
            "max": self.vmax if self.count else float("nan"),
            "mean": self.total / self.count if self.count else float("nan"),
            "p50": q(0.50), "p90": q(0.90), "p99": q(0.99),
        }


def record_hist(tag: str, value) -> None:
    """Histogram sample accumulate."""
    if _DEPTH > 0:
        with _LOCK:
            h = _HISTS.get(tag)
            if h is None:
                h = _HISTS[tag] = _Hist()
            h.add(float(value))


def _record_span(name: str, cat: str, t0: float, t1: float, args=None) -> None:
    if _DEPTH > 0:
        with _LOCK:
            _SPANS.append({
                "name": name, "cat": cat, "t0": t0, "t1": t1,
                "tid": threading.get_ident(), **({"args": args} if args else {}),
            })


# ---------------------------------------------------------------------------
# trace-safe emitters (double-gated: trace-time no-op without a capture)
# ---------------------------------------------------------------------------


def emit(tag: str, value) -> None:
    """Counter emission: inside jit/shard_map this schedules an unordered
    debug callback; outside it records immediately.  A no-op (zero ops in
    the trace) unless a capture scope is active at trace time."""
    if _DEPTH > 0:
        # the tag is static (a python string, not a jax type): close over it
        jax.debug.callback(functools.partial(record, tag), value, ordered=False)


def emit_gauge(tag: str, value) -> None:
    if _DEPTH > 0:
        jax.debug.callback(
            functools.partial(record_gauge, tag), value, ordered=False
        )


def emit_hist(tag: str, value) -> None:
    if _DEPTH > 0:
        jax.debug.callback(
            functools.partial(record_hist, tag), value, ordered=False
        )


@contextlib.contextmanager
def host_span(name: str, cat: str = "host", **args):
    """Wall-clock span over a host-side region (no tracing involved): the
    train loop's per-step timing, bench repetitions, export passes.  The
    region always enters a ``jax.profiler.TraceAnnotation`` named ``name``
    (a no-op unless a profiler trace is running), and is recorded only under
    :func:`capture` — host code has no trace time — so it is safe (and
    free) to leave in place permanently."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        _record_span(name, cat, t0, time.perf_counter(), args or None)


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------


def counters() -> dict:
    with _LOCK:
        return dict(_COUNTERS)


def gauges() -> dict:
    with _LOCK:
        return dict(_GAUGES)


def hists() -> dict:
    """tag -> summary dict (count/sum/min/max/mean/p50/p90/p99)."""
    with _LOCK:
        return {tag: h.summary() for tag, h in _HISTS.items()}


def spans() -> list:
    with _LOCK:
        return list(_SPANS)


def snapshot() -> dict:
    """Everything the exporters consume, in one consistent view."""
    with _LOCK:
        return {
            "counters": dict(_COUNTERS),
            "gauges": dict(_GAUGES),
            "hists": {tag: h.summary() for tag, h in _HISTS.items()},
            "spans": list(_SPANS),
        }


def reset() -> None:
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTS.clear()
        _SPANS.clear()


@contextlib.contextmanager
def capture(fresh: bool = True):
    """Enable metric recording; yields the live counter store (the
    historical API — gauges/hists/spans are read via :func:`gauges` /
    :func:`hists` / :func:`spans` / :func:`snapshot`).  ``fresh`` resets
    accumulated state on entry of the *outermost* scope only: nested scopes
    share one store and never clear it (asserted in tests/test_obs.py).

    Exit blocks on :func:`jax.effects_barrier`: the debug callbacks are
    unordered and asynchronous, so without a flush an emission from a
    just-finished computation can land after the scope closes — and be
    dropped by the runtime gate.  Flushing before the depth decrement makes
    the exited store complete for everything launched inside the scope.
    """
    global _DEPTH
    if fresh and _DEPTH == 0:
        reset()
    _DEPTH += 1
    try:
        yield _COUNTERS
    finally:
        try:
            jax.effects_barrier()
        finally:
            _DEPTH -= 1
