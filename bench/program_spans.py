"""The program's own host spans in the traced window.

The service marks its release path with profiler annotations
(`repro.obs.trace.annotate`: ``serve/...``, ``mwem/...``). They land on
the profile's host plane, on the device trace's clock, and the reduced
trace keeps them by name, start and end (`trace_reduce.Trace.host`). A
program that opens no span of a name leaves its reader nothing to read.
"""

from __future__ import annotations

import statistics
from typing import List, Optional

from bench import trace_reduce


def in_window(trace: trace_reduce.Trace, name: str) -> List[trace_reduce.Event]:
    """Spans named ``name`` that start inside the window."""
    t0, t1 = trace.window
    return [e for e in trace.host if e.name == name and t0 <= e.start <= t1]


def median_ms(ctx, name: str) -> Optional[float]:
    """Median duration of the window's ``name`` spans in ms, or None."""
    if ctx.trace is None:
        return None
    spans = in_window(ctx.trace, name)
    return 1e3 * statistics.median(e.dur for e in spans) if spans else None
