"""Of the collate seconds between the window's edges, the share spent in the
NER head's make_targets (`collate/targets/ner`), its device call included. In
percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    stages = (w or {}).get("stage_seconds", {})
    if "collate/targets/ner" not in stages or not stages.get("collate"):
        return None
    return 100.0 * stages["collate/targets/ner"] / stages["collate"]
