"""Profiler trace of a steady slice: the time in which an all-reduce,
reduce-scatter, all-gather, all-to-all or permute ran on a chip (overlapped
halves on the async line included: the union) over the slice, averaged over
the chips, in percent. Listed for the cells that have more than one chip."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    t = record.get("trace")
    return None if not t else 100.0 * t["collective_share"]
