"""Which route each kernel call took: compiled, interpret, or its XLA ref.

Every kernel wrapper resolves its route here and records it, at trace
time, under the kernel's name. A Pallas kernel compiles only for a TPU
backend; anywhere else it runs in interpret mode, which executes the same
kernel body through XLA. The record makes that choice observable, so a
run that must have used the chip (``chip_smoke.py``) can assert that no
call took the interpret route or the shape gate's ref fallback.
"""

from __future__ import annotations

from typing import Dict, Optional, Set

import jax

COMPILED = "compiled"
INTERPRET = "interpret"
REF = "ref"

_taken: Dict[str, Set[str]] = {}


def default_interpret() -> bool:
    """Compiled on a TPU backend, interpret mode on any other."""
    return jax.default_backend() != "tpu"


def pallas_route(name: str, interpret: Optional[bool] = None) -> bool:
    """Record and return the interpret flag for one Pallas call
    (``None``: `default_interpret`; an explicit flag is kept as given)."""
    if interpret is None:
        interpret = default_interpret()
    note(name, INTERPRET if interpret else COMPILED)
    return interpret


def note(name: str, route: str) -> None:
    """Record that ``name`` was traced on ``route``."""
    _taken.setdefault(name, set()).add(route)


def taken() -> Dict[str, Set[str]]:
    """Routes traced since the last `reset`, by kernel name."""
    return {k: set(v) for k, v in _taken.items()}


def reset() -> None:
    _taken.clear()
