"""Of the collate seconds between the window's edges, what no child span
covers: collate - collate/features - collate/targets - collate/stack (cache
lookups, the word count, the loop over micro-batches). In percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    stages = (w or {}).get("stage_seconds", {})
    if "collate/targets" not in stages or not stages.get("collate"):
        return None
    covered = sum(stages.get(k, 0.0)
                  for k in ("collate/features", "collate/targets", "collate/stack"))
    return 100.0 * (stages["collate"] - covered) / stages["collate"]
