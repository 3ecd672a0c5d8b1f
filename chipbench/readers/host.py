"""Per-layer metrics read from what the harness counted and clocked on the
host. A reader takes the run's facts and returns the number, or ``None``
where this cell gives it nothing to read."""

from __future__ import annotations

from typing import Any, Dict, Optional


def first_batch_s(facts: Dict[str, Any]) -> Optional[float]:
    """Dataset construction (pool spawn, shuffle launch) to the first
    batch or chunk on the device: part of ``setup_s``."""
    return facts.get("first_batch_s")


def input_wait_pct(facts: Dict[str, Any], kind: str = "train"
                   ) -> Optional[float]:
    """Share of the window's wall the consumer spent inside
    ``next(batch)``."""
    if facts.get("kind") != kind:
        return None
    return 100.0 * facts["input_wait_s"] / facts["window_elapsed_s"]


def step_compiles(facts: Dict[str, Any]) -> Optional[float]:
    """Compilations of the train step inside the window (there should be
    none): ``step_fn._cache_size()`` after minus before."""
    value = facts.get("step_compiles")
    return None if value is None else float(value)


def peak_hbm_gb(facts: Dict[str, Any], kind: str) -> Optional[float]:
    """The run's ``memory_peak_bytes`` on the fullest chip, read after the
    window and before the reference runs: the allocator's
    ``peak_bytes_in_use`` and, in a train cell, the compiled step's
    temporaries on top (the runtime's reading leaves them out; an earlier
    line of every run prints the two apart)."""
    if facts.get("kind") != kind:
        return None
    return facts["device"]["memory_peak_bytes"] / 1e9
