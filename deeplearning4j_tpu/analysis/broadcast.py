"""Symbolic NumPy-style broadcasting and jax dtype promotion.

Soundness contract (shared with every rule in ``rules.py``): a broadcast
*error* is reported only when two aligned entries are both concrete ints,
neither is 1, and they differ. Symbolic/unknown entries degrade the result
dim, never produce an error — a ``(None, 128)`` batch against a concrete
``(4, 128)`` activation must check clean.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.analysis.values import (
    Dim, DimEntry, Shape, fmt_shape)


class BroadcastError(Exception):
    """Provable broadcast failure; ``.detail`` names the offending axis."""

    def __init__(self, detail: str):
        super().__init__(detail)
        self.detail = detail


def broadcast_dim(a: DimEntry, b: DimEntry) -> DimEntry:
    """One aligned axis pair → result entry (raises on provable failure)."""
    if isinstance(a, int) and isinstance(b, int):
        if a == b:
            return a
        if a == 1:
            return b
        if b == 1:
            return a
        raise BroadcastError(f"{a} vs {b}")
    if a is None or b is None:
        # unknown vs concrete>1 → the concrete dim (any valid execution
        # yields it); unknown vs 1 or unknown vs symbol → unknown
        other = a if b is None else b
        if isinstance(other, int) and other > 1:
            return other
        return None
    # at least one symbolic Dim
    if isinstance(a, Dim) and isinstance(b, Dim):
        return a if a == b else None
    sym, conc = (a, b) if isinstance(a, Dim) else (b, a)
    if isinstance(conc, int):
        if conc == 1:
            return sym
        return conc  # symbol must equal the concrete dim in a valid run
    return None


def broadcast_shapes(shapes: Sequence[Shape]) -> Shape:
    """NumPy-style broadcast of N symbolic shapes (right-aligned).

    Raises :class:`BroadcastError` only on a provable mismatch; any shape
    with unknown rank makes the whole result unknown."""
    known = [s for s in shapes if s is not None]
    if len(known) != len(shapes) or not known:
        return None
    rank = max(len(s) for s in known)
    out: List[DimEntry] = []
    for axis in range(rank):
        entry: DimEntry = 1
        for s in known:
            idx = len(s) - rank + axis
            d = s[idx] if idx >= 0 else 1
            try:
                entry = broadcast_dim(entry, d)
            except BroadcastError:
                raise BroadcastError(
                    f"axis {axis - rank}: "
                    + " vs ".join(fmt_shape(s) for s in known))
        out.append(entry)
    return tuple(out)


def promote_dtypes(dtypes: Sequence[Optional[np.dtype]]) -> Optional[np.dtype]:
    """jax promotion lattice over known dtypes; None if any is unknown."""
    if any(dt is None for dt in dtypes) or not dtypes:
        return None
    import jax.numpy as jnp

    out = dtypes[0]
    for dt in dtypes[1:]:
        out = np.dtype(jnp.promote_types(out, dt))
    return out


def is_float_dtype(dt: Optional[np.dtype]) -> bool:
    """Floating-point including the ml_dtypes extended types (bfloat16,
    float8_*) that numpy classifies as kind 'V', not inexact."""
    return dt is not None and (np.issubdtype(dt, np.inexact)
                               or dt.name.startswith(("bfloat", "float8")))


def promotion_surprise(dtypes: Sequence[Optional[np.dtype]]
                       ) -> Optional[str]:
    """The GC003 predicate: mixed float widths (bf16+f32, f32+f64 — the
    silent up/downcast class the optimizer's strip guard exists for), a
    promotion to a dtype wider than every input (int8+uint8→int16), or a
    signed result too narrow for an unsigned input. The last is what
    int32+uint32 means as programs here run, with x64 off: jax's lattice
    asks for int64, the installed ``promote_types`` answers int32, and
    every uint32 value above 2^31-1 silently wraps negative. (With x64 on
    the same pair widens to int64 and is caught by the previous clause.)
    Returns a human-readable reason, or None when unsurprising."""
    known = [dt for dt in dtypes if dt is not None]
    if len(known) < 2:
        return None
    inexact = [dt for dt in known if is_float_dtype(dt)]
    if len(inexact) >= 2 and len(set(inexact)) > 1:
        names = sorted({dt.name for dt in inexact})
        return f"mixed float widths {' vs '.join(names)}"
    promoted = promote_dtypes(known)
    names = " + ".join(dt.name for dt in known)
    if promoted is not None and all(promoted != dt for dt in known):
        return f"{names} promotes to {promoted.name} (wider than every input)"
    if promoted is not None and promoted.kind == "i":
        for dt in known:
            if dt.kind == "u" and dt.itemsize >= promoted.itemsize:
                return (f"{names} stays {promoted.name}: {dt.name} values "
                        f"above {np.iinfo(promoted).max} wrap negative")
    return None
