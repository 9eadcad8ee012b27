"""``repro.obs`` — the unified observability subsystem (DESIGN.md §9).

Two halves over one statistics core:

* **online** — the metrics registry in :mod:`repro.core.telemetry`
  (counters, gauges and histograms via the double-gated
  ``jax.debug.callback`` pattern, and host spans), wired into every wire
  surface: the four Pallas kernel dispatch paths (``kernel.*``), the
  compressed/guarded collective rings (``wire.*``), pipeline hops
  (``pipe.*``), error feedback (``ef.*``), the train step (``step.*``),
  quantise and KV-cache appends (``quant.*`` / ``kv.*``), and the host
  train loop (``loop.*``); exported as JSONL and Perfetto/Chrome trace
  JSON (:mod:`repro.obs.trace_export`).  Device time is not recorded
  here: traced code names its regions with ``jax.named_scope``, and a
  profiler trace of the device attributes time to those names.
* **offline** — the statistically honest perf harness: interleaved
  round-robin repetitions in ``benchmarks/kernel_bench``, median-of-k with
  bootstrap CIs (:mod:`repro.obs.stats`), and the CI-overlap
  minimum-effect-size regression gate in ``benchmarks/compare``.

Everything here is re-exported so call sites read ``obs.capture()`` /
``obs.host_span(...)`` / ``obs.summarize(...)`` without caring which half
a symbol lives in.  The re-exports are *lazy* (PEP 562): importing
``repro.obs.stats`` alone stays numpy-only — ``benchmarks/compare`` is a
CI regression gate and must not pay (or risk) a jax import — while the
telemetry/trace symbols pull in jax only on first attribute access.
"""

from __future__ import annotations

_TELEMETRY = frozenset((
    "capture", "counters", "emit", "emit_gauge", "emit_hist", "enabled",
    "gauges", "hists", "host_span", "record", "record_gauge", "record_hist",
    "reset", "snapshot", "spans",
))
_STATS = frozenset(("MIN_EFFECT", "bootstrap_ci", "ci_gate", "summarize"))
_TRACE = frozenset((
    "chrome_trace", "export_chrome_trace", "export_jsonl",
    "load_chrome_trace", "load_jsonl", "validate_chrome_trace",
))

__all__ = sorted(_TELEMETRY | _STATS | _TRACE)


def __getattr__(name: str):
    if name in _TELEMETRY:
        from repro.core import telemetry as mod
    elif name in _STATS:
        from . import stats as mod
    elif name in _TRACE:
        from . import trace_export as mod
    else:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(mod, name)


def __dir__():
    return __all__
