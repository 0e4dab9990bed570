"""Profiler trace of a steady slice: 1 - union of device-operation intervals /
slice, averaged over the chips, in percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    t = record.get("trace")
    return None if not t else 100.0 * t["idle_share"]
