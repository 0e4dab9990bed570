"""Of the collate seconds between the window's edges, the share spent in the
parser's make_targets (`collate/targets/parser`: projectivisation and the
arc-eager oracle). In percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    stages = (w or {}).get("stage_seconds", {})
    if "collate/targets/parser" not in stages or not stages.get("collate"):
        return None
    return 100.0 * stages["collate/targets/parser"] / stages["collate"]
