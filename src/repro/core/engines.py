"""The sweep-engine choice, in one place.

Every caller that takes an ``engine=`` -- the stencil driver, the LM
driver, the store's content key, the servers and the CLIs -- reads the
accepted values and the ``"auto"`` rule from here. Two questions are
asked of an engine name:

* its **family** (:func:`engine_family`): ``"jax"`` (the float32 compiled
  sweep) or ``"numpy"`` (the float64 oracle). The family is what a content
  key records, so it never touches the jax backend: a warm path computes
  keys without initializing a device;
* its **dispatch** (:func:`dispatch_engine`): the family, plus the
  sharded engine when ``"auto"`` lands on jax and more than one device is
  attached. Only :func:`repro.core.codesign.codesign` asks it, just before
  it sweeps. The sharded engine is bit-identical to the jax engine, so it
  shares the jax family and its content keys.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["ENGINES", "engine_family", "dispatch_engine"]

#: the values every ``engine=`` accepts.
ENGINES = ("auto", "jax", "numpy")

#: below this many hardware points the jit compile cannot pay for itself;
#: ``engine="auto"`` takes the NumPy reference solver there.
_AUTO_MIN_HW = 64


def engine_family(engine: str, n_hw: Optional[int] = None) -> str:
    """``"jax"`` or ``"numpy"``: the matrix family ``engine`` builds over
    ``n_hw`` hardware points. ``n_hw=None`` applies no floor (the LM grid,
    whose hardware axis is always small, takes jax under ``"auto"``)."""
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r} (want {'|'.join(ENGINES)})")
    if engine != "auto":
        return engine
    return "numpy" if n_hw is not None and n_hw < _AUTO_MIN_HW else "jax"


def dispatch_engine(engine: str, n_hw: int) -> str:
    """``"numpy"``, ``"jax"`` or ``"sharded"``: the engine a stencil sweep
    of ``n_hw`` points runs on. ``"auto"`` takes the sharded engine when
    more than one device is attached; an explicit ``"jax"`` stays on one."""
    family = engine_family(engine, n_hw)
    if family == "numpy" or engine != "auto":
        return family
    from . import sweep  # loads jax: only a sweep about to run asks this

    return "sharded" if sweep.device_count() > 1 else "jax"
