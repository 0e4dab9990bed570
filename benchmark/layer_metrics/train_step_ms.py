"""Median interval between completed optimizer steps, each step blocked (the
traced run, after the traced slice)."""

from typing import Any, Dict, Optional

from common import median


def read(record: Dict[str, Any]) -> Optional[float]:
    med = median(record.get("step_intervals_s") or [])
    return None if med is None else 1e3 * med
