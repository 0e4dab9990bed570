"""1 - real words / sum of B x T of the batches the step was given, over the
window, in percent (a count)."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    if not w or not w.get("cells"):
        return None
    return 100.0 * (1.0 - w["words"] / w["cells"])
