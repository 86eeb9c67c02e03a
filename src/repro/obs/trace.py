"""The `obs.enabled` switch and profiler annotation wrappers.

Two annotation flavors, matching where the code runs:

* `scope(name)` — **in-graph**: `jax.named_scope`, legal inside jitted
  functions / scan bodies. Attaches the name to the emitted HLO ops so
  XLA profiler timelines line up with logical phases (kernel call sites
  in `kernels/*/ops.py`). Pure metadata: cannot change numerics.
* `annotate(name, **ids)` — **host-side**: `jax.profiler.TraceAnnotation`
  alone, for the release path's layer boundaries (wave launch, wait,
  final error, delivery, ledger, WAL). Its events land on the profile's
  host plane, on the device trace's clock, when a profiler session is
  active; otherwise it costs one `TraceMe` construction. ``ids`` are
  integers (``wave``, ``ticket``, ``lanes``), kept as the event's stats:
  the profiler cuts a string value at its first comma, so never pass
  one. It enters no `jax.named_scope`, which would stamp its name onto
  whatever is traced or compiled under the span.

Both collapse to `nullcontext()` when obs is disabled. Neither path
touches the key chain or any traced value, so enabled-vs-disabled
results are bitwise identical (asserted in tests/test_obs.py).

jit-cache caveat: `enabled()` is read at *trace* time, so flipping the
switch after a shape is compiled will not re-trace — the cached
executable keeps (or keeps lacking) its scope names. Harmless: names
are metadata, and the bitwise-parity contract holds either way.
"""

from __future__ import annotations

import contextlib
from typing import ContextManager

import jax

try:  # host-side profiler annotation; absent on some minimal builds
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except ImportError:  # pragma: no cover - jax always ships it in CI
    _TraceAnnotation = None

_enabled = True


def enabled() -> bool:
    return _enabled


def set_enabled(flag: bool) -> None:
    global _enabled
    _enabled = bool(flag)


@contextlib.contextmanager
def disabled():
    """Temporarily switch obs off (parity tests; silent bench lanes)."""
    prev = _enabled
    set_enabled(False)
    try:
        yield
    finally:
        set_enabled(prev)


def scope(name: str) -> ContextManager:
    """In-graph named scope; safe inside jit/scan bodies."""
    if not _enabled:
        return contextlib.nullcontext()
    return jax.named_scope(name)


def annotate(name: str, **ids: int) -> ContextManager:
    """Host-side span: a profiler TraceAnnotation with integer ``ids``."""
    if not _enabled or _TraceAnnotation is None:
        return contextlib.nullcontext()
    return _TraceAnnotation(name, **ids)
