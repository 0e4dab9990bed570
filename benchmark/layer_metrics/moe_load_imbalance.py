"""Rows of the fullest held expert over rows of the mean held expert, a step
and layer, from the program's own counters summed over the run
(``record["runtime"]["moe"]``). 1.0 is even; the grouped product's time
follows the sum, the sort and the tail follow the fullest. Left out where the
record has no ``moe`` block."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    moe = (record.get("runtime") or {}).get("moe")
    if not isinstance(moe, dict) or not moe.get("mean_expert_load"):
        return None
    return moe["max_expert_load"] / moe["mean_expert_load"]
