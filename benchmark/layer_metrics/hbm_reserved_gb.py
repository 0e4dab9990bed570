"""``memory_stats()["peak_bytes_reserved"]`` after the window, on the fullest
chip, in GB: what the runtime set aside while a program ran, the step's
temporaries included. Held against ``step_temp_gb``."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    peak = (record.get("memory_peaks") or {}).get("reserved")
    return peak / 1e9 if peak else None
