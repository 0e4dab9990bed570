"""PipelineStats loop_host seconds (the loop thread's own work per dispatch:
not waiting for input, not evaluating, not checkpointing) between the window's
edges over the window, in percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    if not w or "loop_host" not in w["stage_seconds"]:
        return None
    return 100.0 * w["stage_seconds"]["loop_host"] / w["seconds"]
