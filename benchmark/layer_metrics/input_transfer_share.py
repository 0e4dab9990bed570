"""PipelineStats transfer seconds (device_put of tokens and targets) between
the window's edges over the window."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    if not w or "transfer" not in w["stage_seconds"]:
        return None
    return w["stage_seconds"]["transfer"] / w["seconds"]
