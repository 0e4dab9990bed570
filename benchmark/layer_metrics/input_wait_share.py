"""PipelineStats queue_wait seconds (the loop waiting for its next batch)
between the edges over the window, in percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    if not w or "queue_wait" not in w["stage_seconds"]:
        return None
    return 100.0 * w["stage_seconds"]["queue_wait"] / w["seconds"]
