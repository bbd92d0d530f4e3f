"""Compiled-HLO cost accounting.

XLA's own cost model for a compiled program, via
``jitted.lower(...).compile().cost_analysis()`` (a dict with ``"flops"`` and
``"bytes accessed"``): what the compile watches record beside a program's
memory (``introspect.CompileWatch``). A callable
that is not jitted, a program that does not compile and a backend that
reports no count (``-1``) give None rather than an exception: the callers
are observers (CompileWatch, reports) that must not sink what they observe.
"""

from __future__ import annotations

from typing import Any, Optional


def hlo_cost(jitted_fn, *args, **kwargs) -> Optional[dict]:
    """Cost analysis of the compiled program for ``jitted_fn(*args)``.

    Returns ``{"flops": float, "bytes_accessed": float | None}`` or None
    when any link of the lower→compile→cost_analysis chain is unavailable
    (module docstring). Arguments may be real pytrees or
    ``jax.ShapeDtypeStruct``s. NOTE: compiles the program if it isn't
    already — call where a compile is acceptable (report time), not on a
    hot path.
    """
    lower = getattr(jitted_fn, "lower", None)
    if lower is None:
        return None                       # not a jitted callable
    try:
        compiled = lower(*args, **kwargs).compile()
    except Exception:
        return None
    return compiled_cost(compiled)


def compiled_cost(compiled) -> Optional[dict]:
    """``hlo_cost`` for an ALREADY-compiled program — the shared half of
    the guard, split out so CompileWatch can pay ONE lower→compile and
    feed both this cost model and ``memory.compiled_memory``."""
    try:
        analysis = compiled.cost_analysis()
    except Exception:
        return None
    return _normalize(analysis)


def _normalize(analysis: Any) -> Optional[dict]:
    """``cost_analysis()``'s dict → ``{"flops", "bytes_accessed"}``."""
    if not isinstance(analysis, dict):
        return None
    flops = analysis.get("flops")
    if flops is None or float(flops) < 0:  # some backends report -1
        return None
    bytes_accessed = analysis.get("bytes accessed")
    return {"flops": float(flops),
            "bytes_accessed": (float(bytes_accessed)
                               if bytes_accessed is not None else None)}
