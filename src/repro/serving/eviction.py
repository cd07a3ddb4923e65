"""HBM-budgeted eviction over device-resident serving plans.

Admission stages a plan's tiles to the device once; with thousands of
resident matrices the staged bytes are the scarce resource, not the host
copies.  :class:`LRUEvictor` keeps the **device** footprint under a byte
budget: every admission (and every transparent re-stage) charges the
plan's device bytes, and when the budget overflows the least-recently-
*used* plans are unstaged — their device arrays dropped, their host tiles
and autotuned geometry kept, so a later request against an evicted plan
re-stages in one ``device_tiles`` call with zero re-preprocessing (the
partition config is still in the plan, and a full re-admission would hit
the ``.hbp_autotune/`` disk cache by content hash anyway).

Transpose pairs linked by ``admit_pair`` are evicted as a unit — a
forward plan without its backward partner would silently re-stage the
partner on the first training step, defeating the budget accounting.

A plan with work queued or on the device is **pinned** (:meth:`LRUEvictor.pin`,
counted per request by the serving engine): unstaging it would drop the
registry's reference while the runtime still holds its buffers for the
launched batches, so the charged bytes would understate what HBM holds.
Victims come only from unpinned units; when every other unit is pinned the
admission goes ahead over the budget and :meth:`LRUEvictor.over_budget`
reports by how much.

The policy is pure bookkeeping (names and byte counts); the registry owns
the actual staging/unstaging side effects.
"""
from __future__ import annotations

from typing import Dict, List, Optional

__all__ = ["plan_device_bytes", "LRUEvictor"]


def plan_device_bytes(tiles) -> int:
    """Device bytes one plan's staged tiles occupy.

    Computed from the host :class:`~repro.core.tile.HBPTiles` mirror —
    the staged pytree holds the same arrays (data f32, cols/rowgroup/
    colblock i32, perm) at the dtypes ``device_tiles`` casts to, and one
    i32 width per tile.
    """
    return int(
        tiles.data.size * 4  # f32 payloads
        + tiles.cols.size * 4  # i32 local columns
        + tiles.rowgroup.size * 4 * 3  # i32 rowgroup, colblock, width
        + tiles.perm.size * 4  # staged as i32
    )


class LRUEvictor:
    """Least-recently-used byte-budget policy over resident plan names.

    ``admit(name, nbytes)`` registers (or re-registers) a plan as the
    most recently used and returns the names that must be unstaged to get
    back under ``budget_bytes`` — oldest first, never the plan just
    admitted (a single plan larger than the whole budget stays resident
    and the evictor reports the overshoot via :meth:`over_budget`).
    ``touch(name)`` refreshes recency on every registry ``get``;
    ``drop(name)`` removes a plan the registry unstaged or fully evicted
    for its own reasons (pair partners, explicit evicts); ``pin(name)`` /
    ``unpin(name)`` count the work that holds a plan's unit resident.
    """

    def __init__(self, budget_bytes: int):
        """Create a policy holding device residency under ``budget_bytes``."""
        if budget_bytes <= 0:
            raise ValueError(f"budget_bytes must be > 0, got {budget_bytes}")
        self.budget_bytes = int(budget_bytes)
        # insertion order == recency order (Python dicts preserve it);
        # values are the charged device bytes
        self._resident: Dict[str, int] = {}
        self._pair: Dict[str, str] = {}
        self._pins: Dict[str, int] = {}  # name -> requests queued or in flight

    # --- bookkeeping -------------------------------------------------------

    @property
    def resident_bytes(self) -> int:
        """Total device bytes currently charged."""
        return sum(self._resident.values())

    def resident(self) -> List[str]:
        """Resident plan names, least recently used first."""
        return list(self._resident)

    def over_budget(self) -> int:
        """Bytes past the budget, 0 when under.

        Positive only when what could not be unstaged exceeds the budget:
        a single unit larger than the whole budget (it stays resident
        rather than thrashing), or the admitted unit beside pinned ones.
        """
        return max(0, self.resident_bytes - self.budget_bytes)

    def link(self, a: str, b: str) -> None:
        """Mark ``a`` and ``b`` as a transpose pair evicted as one unit."""
        if a != b:
            self._pair[a] = b
            self._pair[b] = a

    def touch(self, name: str) -> None:
        """Refresh ``name`` (and its pair partner) as most recently used."""
        for n in self._unit(name):
            nbytes = self._resident.pop(n, None)
            if nbytes is not None:
                self._resident[n] = nbytes

    def drop(self, name: str) -> None:
        """Forget ``name`` (registry unstaged or evicted it out of band)."""
        self._resident.pop(name, None)

    def pin(self, name: str, count: int = 1) -> None:
        """Hold ``name``'s unit resident for ``count`` more pieces of work."""
        self._pins[name] = self._pins.get(name, 0) + count

    def unpin(self, name: str, count: int = 1) -> None:
        """Release ``count`` pieces of work pinned by :meth:`pin`."""
        left = self._pins.get(name, 0) - count
        if left < 0:
            raise ValueError(f"{name!r} unpinned {-left} more times than pinned")
        if left:
            self._pins[name] = left
        else:
            del self._pins[name]

    def pinned(self, name: str) -> bool:
        """Whether ``name`` or its pair partner has work pinned."""
        return any(n in self._pins for n in self._unit(name))

    def unlink(self, name: str) -> None:
        """Dissolve ``name``'s pair link (full eviction of one side)."""
        partner = self._pair.pop(name, None)
        if partner is not None:
            self._pair.pop(partner, None)

    # --- the policy --------------------------------------------------------

    def admit(self, name: str, nbytes: int) -> List[str]:
        """Charge ``name`` at ``nbytes`` and return the victims to unstage.

        The admitted plan (and its pair partner, if resident) is held
        for this decision, as is every pinned unit; victims come least
        recently used first, each expanded to its full pair unit, until
        the total fits the budget or nothing evictable remains.
        """
        self._resident.pop(name, None)
        self._resident[name] = int(nbytes)
        held = set(self._unit(name))
        victims: List[str] = []
        while self.resident_bytes > self.budget_bytes:
            candidate = next(
                (n for n in self._resident if n not in held and not self.pinned(n)),
                None,
            )
            if candidate is None:
                break  # only held units remain: allow the overshoot
            for n in self._unit(candidate):
                if n in self._resident:
                    del self._resident[n]
                    victims.append(n)
        return victims

    def _unit(self, name: str) -> List[str]:
        """``name`` plus its pair partner — the unit evictions operate on."""
        partner: Optional[str] = self._pair.get(name)
        return [name] if partner is None else [name, partner]

    def snapshot(self) -> dict:
        """Bookkeeping view for stats/tests (bytes, order, budget)."""
        return {
            "budget_bytes": self.budget_bytes,
            "resident_bytes": self.resident_bytes,
            "resident": list(self._resident),
            "over_budget": self.over_budget(),
            "pinned": dict(self._pins),
        }
