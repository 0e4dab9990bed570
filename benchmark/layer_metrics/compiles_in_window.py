"""Rise of the compile hook's count in update calls inside the window; each was
blocked on and cut out."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    w = record.get("window")
    return None if not w else float(w["compiles"])
