"""``memory_stats()["peak_bytes_in_use"]`` after the window, on the fullest
chip, in GB: the buffers JAX holds (parameters, optimizer state, batches). The
step's temporaries are not in it: ``hbm_reserved_gb`` and ``step_temp_gb``."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    peak = (record.get("memory_peaks") or {}).get("in_use")
    return peak / 1e9 if peak else None
