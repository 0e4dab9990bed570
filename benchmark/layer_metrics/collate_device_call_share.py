"""Of the collate seconds between the window's edges, the share spent in
calls that leave the host (every key ending in `/device_call`: an eager jnp
operation and the copy back, which queue behind the step on the chip). A part
of the head's share it lies in, not beside it. In percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    stages = (w or {}).get("stage_seconds", {})
    calls = [v for k, v in stages.items() if k.endswith("/device_call")]
    if not calls or not stages.get("collate"):
        return None
    return 100.0 * sum(calls) / stages["collate"]
