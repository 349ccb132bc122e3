"""Exact counts from the text of a compiled step (`compiled.as_text()`)."""

from __future__ import annotations

import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def collective_counts(text: str) -> dict:
    """Instructions by kind: `= <shape> <kind>(` or, where the compiler made
    the collective asynchronous, `<kind>-start(`; its `-done` is not another
    one."""
    return {kind: len(re.findall(
        r"^\s*(?:ROOT )?\S+ = .*? %s(?:-start)?\(" % re.escape(kind), text, re.M))
        for kind in COLLECTIVES}
