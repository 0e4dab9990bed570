"""Of the collate seconds between the window's edges, the share spent in
`collate/features`: hashing the uncached words (vocab.featurize) and filling
attr_keys / mask / vector_rows. In percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    stages = (w or {}).get("stage_seconds", {})
    if "collate/features" not in stages or not stages.get("collate"):
        return None
    return 100.0 * stages["collate/features"] / stages["collate"]
