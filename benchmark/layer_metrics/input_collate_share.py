"""PipelineStats collate seconds between the window's edges over the window
(summed over collate threads: a share of work, not of wall)."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    if not w or "collate" not in w["stage_seconds"]:
        return None
    return w["stage_seconds"]["collate"] / w["seconds"]
