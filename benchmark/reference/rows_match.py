"""The comparison that decides ``correct``.

A copy of ``tests/oracle.assert_rows_match`` (the original stays for the
repo's own tests; PERF.md lists it under open questions).  Tolerances:
relative 1e-9, absolute 1e-6, or half a unit of a decimal answer's
declared scale, because ``avg(decimal(p, s))`` rounds HALF_UP at scale
``s`` in the engine (the reference's semantics) while the float-based
reference keeps full precision.  Everything that is not a float or a
decimal must be equal.
"""

from __future__ import annotations

import math
from decimal import Decimal
from typing import List, Optional


def _key(row: tuple):
    """Sort key that orders None first and rounds floats, so two row
    sets that agree within the tolerance sort alike."""
    out = []
    for v in row:
        if v is None:
            out.append((0, 0, ""))
        elif isinstance(v, (int, float, Decimal)):
            out.append((1, round(float(v), 4), ""))
        else:
            out.append((2, 0, str(v)))
    return tuple(out)


def mismatch(actual: List[tuple], expected: List[tuple],
             ordered: bool) -> Optional[str]:
    """None where ``actual`` equals ``expected`` under the tolerances,
    else one line saying where they part."""
    if len(actual) != len(expected):
        return f"row count: got {len(actual)}, want {len(expected)}"
    a = actual if ordered else sorted(actual, key=_key)
    e = expected if ordered else sorted(expected, key=_key)
    for i, (ra, re_) in enumerate(zip(a, e)):
        if len(ra) != len(re_):
            return f"row {i}: arity {len(ra)} against {len(re_)}"
        for j, (va, ve) in enumerate(zip(ra, re_)):
            if isinstance(va, (float, Decimal)) or isinstance(ve, (float, Decimal)):
                if va is None or ve is None:
                    if va is not None or ve is not None:
                        return f"row {i} col {j}: {va} against {ve}"
                    continue
                abs_tol = 1e-6
                if isinstance(va, Decimal):
                    exp = va.as_tuple().exponent
                    if isinstance(exp, int) and exp < 0:
                        abs_tol = max(abs_tol, 0.5000001 * 10.0 ** exp)
                if not math.isclose(float(va), float(ve), rel_tol=1e-9,
                                    abs_tol=abs_tol):
                    return f"row {i} col {j}: got {va}, want {ve}"
            elif va != ve:
                return f"row {i} col {j}: got {va!r}, want {ve!r}"
    return None


def decode_rows(columns: List[dict], rows: List[tuple]) -> List[tuple]:
    """The statement protocol ships decimals as strings; make them
    Decimals again so they compare by value (copied from
    ``chip_smoke.py``)."""
    is_decimal = [c["type"].startswith("decimal") for c in columns]
    return [tuple(Decimal(v) if dec and v is not None else v
                  for v, dec in zip(row, is_decimal)) for row in rows]
