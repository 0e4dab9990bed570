"""PipelineStats read seconds (corpus + batcher: one update's raw batches)
between the window's edges over the window."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    if not w or "read" not in w["stage_seconds"]:
        return None
    return w["stage_seconds"]["read"] / w["seconds"]
