"""The program's own host spans in a trace, read for the client bank.

``repro.obs`` spans are ``jax.profiler.TraceAnnotation``s, so in a traced
run they lie on the host plane beside the harness's spans, on the device
trace's clock. The client bank's are named ``bank/<what>``: ``bank/gather``
and ``bank/scatter`` around the cohort gather and scatter, and for the host
bank ``bank/wait``, ``bank/prefetch`` and ``bank/drain``. Calls nest
(``gather_cohort_opt`` calls ``gather_cohort``), so each reading takes the
union of their intervals, clipped to the window.

Each reading is per execution of the training step in the window, the
denominator ``bank_device_ms.train`` uses, and is None when the trace holds
no ``bank/*`` span (a program that records none) or no step.
"""
from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from chipbench import trace

BANK = "bank/"
STEP = "train_step"
# the host event of one program launch, on the thread that launched it
EXECUTE = "PJRT_LoadedExecutable_Execute"


def bank_union(tr: trace.Trace) -> List[Tuple[float, float]]:
    """The union of the bank's span intervals inside the window."""
    return trace.union([h for h in trace._clip(tr.host, *tr.window)
                        if h[0].startswith(BANK)])


def _steps(tr: trace.Trace) -> Optional[int]:
    return trace.module_counts(tr).get(STEP)


def _overlap(a, b) -> float:
    """Seconds common to two sorted lists of disjoint intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def host_ms(tr: trace.Trace) -> Optional[float]:
    """Milliseconds per step in which the host was inside the bank."""
    bank, n = bank_union(tr), _steps(tr)
    if not bank or not n:
        return None
    return 1e3 * sum(e - s for s, e in bank) / n


def dispatches(tr: trace.Trace) -> Optional[float]:
    """Programs launched per step from inside the bank's spans."""
    bank, n = bank_union(tr), _steps(tr)
    if not bank or not n:
        return None
    starts = [s for s, _ in bank]
    count = 0
    for name, s, _ in tr.host:
        if name == EXECUTE:
            k = bisect.bisect_right(starts, s) - 1
            count += k >= 0 and s < bank[k][1]
    return count / n


def idle_ms(tr: trace.Trace) -> Optional[float]:
    """Milliseconds per step in which the host was inside the bank and no
    operation ran on the device (averaged over devices)."""
    bank, n = bank_union(tr), _steps(tr)
    if not bank or not n or not tr.devices:
        return None
    inside = sum(e - s for s, e in bank)
    busy = [_overlap(bank, trace.union(trace._clip(d.ops, *tr.window)))
            for d in tr.devices.values()]
    return 1e3 * (inside - sum(busy) / len(busy)) / n
