"""The engine's own record of each request the serving window answered.

The serve driver leaves its tickets and which of them were answered in
``run.state`` (``tickets``, ``done``); each ticket's ``RequestContext``
holds the engine's stamps and what is derived from them.  A program
without a stamp, or a driver without tickets, gives nothing to read.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def answered(run, field: str) -> Optional[np.ndarray]:
    """``field`` of every answered request's context, or None where the
    driver left no tickets or a context lacks the field."""
    tickets, done = run.state.get("tickets"), run.state.get("done")
    if tickets is None or done is None:
        return None
    values = [getattr(t.context, field, None) for t, d in zip(tickets, done) if d]
    if not values or any(v is None for v in values):
        return None
    return np.asarray(values, dtype=float)
