"""Observability subsystem (DESIGN.md §14).

One process-wide *active recorder* — :class:`NullRecorder` by default,
every operation a no-op measured in nanoseconds — that launchers swap for
a real :class:`Recorder` (``--metrics-dir``). Instrumented code holds
whatever recorder was active when it was built and never checks a flag
twice: the disabled path is the pre-obs code path, bit for bit (no
``jax.debug.callback`` is ever staged into a jit graph unless metrics
are on, so jitted round bodies are untouched).

Spans are the one exception to "no-op": under either recorder a span is a
``jax.profiler.TraceAnnotation`` of the same name, which records only while
a profiler trace runs. Then the program's spans (``bank/gather``,
``serve/decode`` …) lie on the device trace's clock, with nothing to switch
on, and the benchmark's trace reduction reads them (PERF.md §3).

Three layers:

* **recorder** — counters / gauges / events + wall-clock spans (also
  profiler annotations), buffered
  to a JSONL event sink with a per-run ``manifest.json`` (config hash,
  git SHA, backend, jax version). ``emit_from_jit`` is the
  ``jax.debug.callback`` emit path for values produced inside jitted
  round bodies.
* **ledger** — the traffic ledger: actual bits crossing each protocol
  boundary (uplink smashed data, labels, downlink gradients, model sync,
  migration payloads), counted by callbacks the ``ProtocolEngine``
  stages next to the real transport ops. Reconciled per round against
  ``sysmodel.traffic`` predictions — any divergence is a pricing bug.
* **report** — ``python -m repro.obs.report RUN_DIR`` renders round
  timelines, the traffic-reconciliation table and cohort/DDQN summaries
  from the JSONL, and exits non-zero on any reconciliation mismatch
  (the CI contract).

``obs.log(msg)`` is the uniform stderr text sink replacing ad-hoc
``print()`` progress lines: it honors ``--quiet``, keeps benchmark
stdout parseable, and (when metrics are on) mirrors the line into the
event stream.
"""
from __future__ import annotations

from repro.obs.ledger import LEDGER_CATEGORIES, TrafficLedger, reconcile
from repro.obs.recorder import NullRecorder, Recorder, null_recorder
from repro.obs.stats import percentile

_active = null_recorder


def get_recorder():
    """The process-wide active recorder (NullRecorder unless a launcher
    or test installed a real one)."""
    return _active


def set_recorder(rec) -> None:
    global _active
    _active = rec if rec is not None else null_recorder


class use_recorder:
    """Context manager installing ``rec`` as the active recorder (tests)."""

    def __init__(self, rec):
        self.rec = rec

    def __enter__(self):
        self._prev = get_recorder()
        set_recorder(self.rec)
        return self.rec

    def __exit__(self, *exc):
        set_recorder(self._prev)
        return False


def set_quiet(quiet: bool = True) -> None:
    """Silence (or re-enable) the stderr text sink on every recorder —
    including the Null default, so ``--quiet`` works without metrics."""
    null_recorder.quiet = bool(quiet)
    _active.quiet = bool(quiet)


def log(msg: str) -> None:
    """Progress line → stderr (honoring ``--quiet``) and, when metrics
    are enabled, the event stream. The replacement for ad-hoc print()."""
    _active.log(msg)


__all__ = [
    "LEDGER_CATEGORIES", "NullRecorder", "Recorder", "TrafficLedger",
    "get_recorder", "log", "null_recorder", "percentile", "reconcile",
    "set_quiet", "set_recorder", "use_recorder",
]
