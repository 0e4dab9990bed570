"""PipelineStats loop_host/dispatch seconds (rng split and the call into the
update: the enqueue, never the device time) between the window's edges over
the window's optimizer steps, in milliseconds."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    if not w or "loop_host/dispatch" not in w["stage_seconds"] or not w["steps"]:
        return None
    return 1e3 * w["stage_seconds"]["loop_host/dispatch"] / w["steps"]
