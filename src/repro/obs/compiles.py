"""Per-thread count of the programs JAX compiles or loads from its
persistent compilation cache.

JAX compiles on the thread that dispatches, so two readings of
:func:`compiles_so_far` around a dispatch, on the same thread, give that
dispatch's own compiles (the ``compiles`` attr of the stencil sweep's and
the LM grid's dispatch spans). The listener is registered with
``jax.monitoring`` by :func:`listen_for_compiles`, once per process; until
then every reading is 0. The module imports nothing beyond the standard
library until that call.
"""

from __future__ import annotations

import threading

#: JAX's monitoring event around each backend compile, a load from the
#: persistent compilation cache included.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _ThreadCompiles(threading.local):
    n = 0


_COUNT = _ThreadCompiles()
_LOCK = threading.Lock()
_LISTENING = False


def _on_event(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        _COUNT.n += 1


def listen_for_compiles() -> None:
    """Register the compile listener with JAX, if it is not yet."""
    global _LISTENING
    with _LOCK:
        if not _LISTENING:
            import jax

            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _LISTENING = True


def compiles_so_far() -> int:
    """Compiles counted on the calling thread since the listener was
    registered."""
    return _COUNT.n
