"""Device ops resolved to named scopes — ``repro.obs``'s bridge to XLA.

A profile names device ops by their HLO instruction (``%fusion.124``),
which says nothing of the code that made them and changes with any edit.
The programs say it themselves: every ``jax.named_scope`` open while an op
was traced lands in that op's ``op_name`` metadata, and the compiled HLO
keeps it, fusions inside a ``while`` body included.  This module reads it
back, in two steps that keep the measured region clean:

- :func:`note` — called by an instrumented dispatch site while tracing is
  on.  It records, once per distinct program, which jitted function ran
  and the abstract arguments (shape, dtype, sharding) it ran on.  It
  lowers and compiles nothing, so it costs a dictionary lookup.
- :func:`op_scopes` — called after the measured region.  It lowers and
  compiles each noted program (JAX's own caches serve what already ran),
  parses the compiled HLO text and maps each instruction name, bare of
  the leading ``%``, to the innermost scope on its ``op_name`` that starts
  with one of ``prefixes``.  Instructions under no such scope, and those
  the compiler made without an ``op_name``, are left out.  :func:`bare`
  puts a profile's op name in the map's form.

Like the rest of ``repro.obs`` it imports jax only when used.
"""
from __future__ import annotations

import re
import threading
from typing import Any, Callable

__all__ = ["note", "op_scopes", "scopes_from_hlo", "bare", "clear"]

_LOCK = threading.Lock()
_NOTES: dict[tuple, tuple[Callable, tuple, dict]] = {}
_TEXTS: dict[tuple, str] = {}  # note key -> compiled HLO text

# One instruction of the HLO text that has an op_name: its name and op_name.
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="([^"]*)"', re.M)


def bare(name: str) -> str:
    """An op's name as :func:`op_scopes` keys it: ``%fusion.124`` and
    ``fusion.124`` both read ``fusion.124``."""
    return name[1:] if name.startswith("%") else name


def _abstract(x: Any) -> Any:
    """An array's shape and dtype, with its sharding only where it spans
    devices: a one-device input lowers as it was dispatched, to the
    program that ran, which JAX's caches then serve."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        import jax

        sharding = getattr(x, "sharding", None) if isinstance(x, jax.Array) else None
        if sharding is not None and len(sharding.device_set) == 1:
            sharding = None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return x


def note(fn: Callable, *args, **kwargs) -> None:
    """Record that the jitted ``fn`` ran on these arguments.  Arrays are
    kept as their shape, dtype and sharding; every other argument (the
    static ones) as it is, and it must be hashable.  Does nothing where an
    argument is a tracer: a call traced into an outer program is no
    program of its own."""
    import jax

    values = (*args, *kwargs.values())
    if any(isinstance(x, jax.core.Tracer) for x in values):
        return
    a_args = tuple(_abstract(x) for x in args)
    a_kwargs = {k: _abstract(v) for k, v in kwargs.items()}
    key = (fn, a_args, tuple(sorted(a_kwargs.items())))
    with _LOCK:
        _NOTES.setdefault(key, (fn, a_args, a_kwargs))


def clear() -> None:
    """Forget every noted program."""
    with _LOCK:
        _NOTES.clear()
        _TEXTS.clear()


def scopes_from_hlo(text: str, prefixes: tuple[str, ...]) -> dict[str, str]:
    """``{instruction: scope}`` from an HLO module's text: the innermost
    component of each instruction's ``op_name`` that starts with one of
    ``prefixes``.  Instructions with no such component, among them those
    the compiler made and gave no ``op_name`` (layout copies, a loop's
    tuples), are left out."""
    scope = re.compile(r"(?:^|[/(])(" + "|".join(re.escape(p) for p in prefixes)
                       + r")([^/()]*)")
    out: dict[str, str] = {}
    for name, op_name in _INSTR.findall(text):
        hits = scope.findall(op_name)
        if hits:
            out[name] = "".join(hits[-1])
    return out


def op_scopes(prefixes: tuple[str, ...] = ("prohd.",)) -> dict[str, str]:
    """``{instruction: scope}`` over every noted program (see the module
    docstring).  Lowers and compiles: call it after the measured region.
    Instruction names are unique within one program; where two noted
    programs reuse a name, the one noted later wins."""
    with _LOCK:
        notes = list(_NOTES.items())
    out: dict[str, str] = {}
    for key, (fn, args, kwargs) in notes:
        text = _TEXTS.get(key)
        if text is None:
            text = fn.lower(*args, **kwargs).compile().as_text()
            with _LOCK:
                _TEXTS[key] = text
        out.update(scopes_from_hlo(text, tuple(prefixes)))
    return out
