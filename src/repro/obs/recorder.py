"""Metrics core: counters / gauges / spans → buffered JSONL + manifest.

Event schema (``repro.obs.v1`` — one JSON object per line, DESIGN.md
§14): every record carries ``seq`` (monotonic, total order even within
one wall-clock tick), ``ts`` (unix seconds), ``kind``, ``name`` and
``round`` (the recorder's current round scope, ``None`` outside one),
plus kind-specific fields:

=========  ==============================================================
kind       fields
=========  ==============================================================
counter    ``value`` (the increment) — totals land in the ``summary``
gauge      ``value`` (float, or {mean,min,max,n} for array emits)
span       ``dur_s``, ``depth``, ``parent`` (closing-time emission:
           children precede their parent in the file, Chrome-trace style)
event      free-form payload (``traffic``, ``migration``, ``cohort``,
           ``ddqn_episode``, ``serve_token``, ``round`` … — see report)
log        ``msg`` (the stderr text sink's mirror)
summary    final counter totals, written on close
=========  ==============================================================

The recorder is deliberately host-side and lock-protected: the
``jax.debug.callback`` emit path (``emit_from_jit``, plus the traffic
ledger's taps) runs on the runtime's callback thread.

Every span, from the real recorder and from :class:`NullRecorder` alike,
is also a ``jax.profiler.TraceAnnotation`` under the span's own name: while
a profiler trace runs (``--profile N``, the benchmark's ``--trace 1``), the
span lies on the host plane on the device trace's clock, so device idle time
can be laid against it. With no trace active the annotation costs about what
a ``contextlib.nullcontext`` does. Spans measure host time only; nothing is
staged into a jitted function.
"""
from __future__ import annotations

import hashlib
import json
import math
import os
import subprocess
import sys
import threading
import time
from typing import Any, Dict, Optional

from jax.profiler import TraceAnnotation

from repro.obs.ledger import TrafficLedger

SCHEMA = "repro.obs.v1"
EVENTS_FILE = "events.jsonl"
MANIFEST_FILE = "manifest.json"


def _json_safe(v: Any):
    """JSON has no inf/nan; don't let one non-finite latency corrupt a
    line for every downstream reader."""
    if isinstance(v, float):
        return v if math.isfinite(v) else repr(v)
    if isinstance(v, dict):
        return {k: _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    if hasattr(v, "item") and getattr(v, "ndim", None) == 0:  # np scalars
        return _json_safe(v.item())
    return v


def git_sha() -> Optional[str]:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=5,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None


def config_hash(config: Dict) -> str:
    blob = json.dumps(_json_safe(config), sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def build_manifest(config: Optional[Dict] = None) -> Dict:
    """The per-run provenance header: enough to compare two runs'
    JSONLs without guessing what produced them."""
    man = {
        "schema": SCHEMA,
        "started": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "argv": sys.argv,
        "git_sha": git_sha(),
        "python": sys.version.split()[0],
        "platform": sys.platform,
    }
    import jax

    man["jax_version"] = jax.__version__
    man["backend"] = jax.default_backend()
    man["device_count"] = jax.device_count()
    if config is not None:
        man["config"] = _json_safe(config)
        man["config_hash"] = config_hash(config)
    return man


class NullRecorder:
    """The disabled default: every method a no-op (the hot path pays one
    attribute load + truthiness check at most), except that a span is still
    a profiler annotation, which records only while a trace runs. Keeps the
    stderr text sink so ``obs.log`` works metrics-off too."""

    enabled = False
    ledger = None

    def __init__(self):
        self.quiet = False

    # -- no-op metric surface -------------------------------------------
    def set_round(self, t):
        pass

    def span(self, name, **attrs):
        return TraceAnnotation(name, **attrs)

    def counter(self, name, value=1, **attrs):
        pass

    def gauge(self, name, value, **attrs):
        pass

    def event(self, kind, name=None, **fields):
        pass

    def emit_from_jit(self, name, value):
        pass

    def flush(self):
        pass

    def close(self):
        pass

    # -- text sink -------------------------------------------------------
    def log(self, msg: str) -> None:
        if not self.quiet:
            print(msg, file=sys.stderr, flush=True)


null_recorder = NullRecorder()


class _Span:
    """A JSONL span record and, for its lifetime, a profiler annotation."""

    __slots__ = ("rec", "name", "attrs", "t0", "parent", "depth", "ann")

    def __init__(self, rec, name, attrs):
        self.rec, self.name, self.attrs = rec, name, attrs
        self.ann = TraceAnnotation(name, **attrs)

    def __enter__(self):
        st = self.rec._span_stack()
        self.parent = st[-1] if st else None
        self.depth = len(st)
        st.append(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        self.rec._span_stack().pop()
        self.rec._emit(dict(kind="span", name=self.name, dur_s=dur,
                            depth=self.depth, parent=self.parent,
                            **self.attrs))
        return False


class Recorder:
    """The enabled recorder: JSONL event sink + manifest + traffic ledger.

    ``metrics_dir=None`` keeps everything in memory (``self.events``) —
    what the tests use; with a directory, events stream to
    ``events.jsonl`` (``append=True`` continues a resumed run's file
    and keeps its manifest, so round indices continue instead of
    restarting).
    """

    enabled = True

    def __init__(self, metrics_dir: Optional[str] = None, *,
                 config: Optional[Dict] = None, quiet: bool = False,
                 append: bool = False, flush_every: int = 256,
                 keep_events: Optional[bool] = None):
        self.metrics_dir = metrics_dir
        self.quiet = quiet
        self.ledger = TrafficLedger()
        self.events = []  # in-memory mirror (always on when no dir)
        self._keep = keep_events if keep_events is not None \
            else metrics_dir is None
        self._lock = threading.Lock()
        self._buf = []
        self._flush_every = max(1, flush_every)
        self._seq = 0
        self._round = None
        self._spans = threading.local()  # open spans, per thread
        self._counters: Dict[str, float] = {}
        self._fh = None
        self.manifest = build_manifest(config)
        if metrics_dir is not None:
            os.makedirs(metrics_dir, exist_ok=True)
            man_path = os.path.join(metrics_dir, MANIFEST_FILE)
            if not (append and os.path.exists(man_path)):
                with open(man_path, "w") as f:
                    json.dump(self.manifest, f, indent=2, sort_keys=True)
            self._fh = open(os.path.join(metrics_dir, EVENTS_FILE),
                            "a" if append else "w")

    # ------------------------------------------------------------------
    def set_round(self, t: Optional[int]) -> None:
        """Round scope: every event until the next call is tagged with
        ``round = t`` (None leaves events unscoped)."""
        self._round = None if t is None else int(t)

    @property
    def round(self) -> Optional[int]:
        return self._round

    def _span_stack(self) -> list:
        """Names of the spans open on the calling thread (a bank worker's
        spans nest under nothing of the main thread's)."""
        st = getattr(self._spans, "stack", None)
        if st is None:
            st = self._spans.stack = []
        return st

    # ------------------------------------------------------------------
    def _emit(self, rec: Dict) -> None:
        with self._lock:
            rec.setdefault("round", self._round)
            rec["seq"] = self._seq
            self._seq += 1
            rec["ts"] = time.time()
            rec = _json_safe(rec)
            if self._keep:
                self.events.append(rec)
            if self._fh is not None:
                self._buf.append(json.dumps(rec))
                if len(self._buf) >= self._flush_every:
                    self._flush_locked()

    def _flush_locked(self) -> None:
        if self._fh is not None and self._buf:
            self._fh.write("\n".join(self._buf) + "\n")
            self._fh.flush()
        self._buf = []

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def close(self) -> None:
        with self._lock:
            if self._counters:
                self._buf.append(json.dumps(_json_safe(
                    {"kind": "summary", "seq": self._seq,
                     "ts": time.time(), "round": None,
                     "counters": dict(self._counters)})))
                if self._keep:
                    self.events.append(json.loads(self._buf[-1]))
                self._seq += 1
            self._flush_locked()
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    # ------------------------------------------------------------------
    def counter(self, name: str, value=1, **attrs) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value
        self._emit(dict(kind="counter", name=name, value=value, **attrs))

    def gauge(self, name: str, value, **attrs) -> None:
        self._emit(dict(kind="gauge", name=name, value=value, **attrs))

    def event(self, kind: str, name: Optional[str] = None, **fields) -> None:
        self._emit(dict(kind=kind, name=name, **fields))

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def log(self, msg: str) -> None:
        if not self.quiet:
            print(msg, file=sys.stderr, flush=True)
        self._emit({"kind": "log", "name": "log", "msg": msg})

    # ------------------------------------------------------------------
    def emit_from_jit(self, name: str, value) -> None:
        """The ``jax.debug.callback`` emit path: call INSIDE a traced
        function to surface a device value as a gauge each time the
        compiled computation actually runs. Scalars become floats;
        arrays a {mean,min,max,n} summary (plus values when tiny).
        Disabled recorders stage nothing — the jit graph is unchanged."""
        import jax
        import numpy as np

        def _cb(v):
            v = np.asarray(v)
            if v.ndim == 0:
                self.gauge(name, float(v))
            else:
                summary = {"mean": float(v.mean()), "min": float(v.min()),
                           "max": float(v.max()), "n": int(v.size)}
                if v.size <= 16:
                    summary["values"] = [float(x) for x in v.reshape(-1)]
                self.gauge(name, summary)

        jax.debug.callback(_cb, value)

    def tap_bits(self, category: str, bits: int) -> None:
        """Stage a ledger increment inside a traced function: ``bits``
        must be a static (trace-time) int — shapes and codec wire
        formats are static under jit, which is what makes the ledger's
        counts exact. Executes once per real execution of the
        surrounding computation (so τ-scans count τ times)."""
        import jax

        bits = int(bits)
        if bits <= 0:
            return
        ledger = self.ledger
        jax.debug.callback(lambda: ledger.add(category, bits))


def read_events(metrics_dir: str):
    """Decode ``events.jsonl`` (skipping blank/corrupt lines) → list."""
    path = os.path.join(metrics_dir, EVENTS_FILE)
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def read_manifest(metrics_dir: str) -> Optional[Dict]:
    path = os.path.join(metrics_dir, MANIFEST_FILE)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)
