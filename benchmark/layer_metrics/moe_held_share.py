"""Of the (word, choice) assignments the router made, the percentage that
landed on an expert held here: the program's own counters, summed over the
run (``record["runtime"]["moe"]``, spacy_ray_tpu/names.py). 12.5 under even
routing with 16 of 128 experts held; a program without the counters has no
``moe`` block and the metric is left out."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    moe = (record.get("runtime") or {}).get("moe")
    if not isinstance(moe, dict) or not moe.get("assignments"):
        return None
    return 100.0 * moe["assignments_held"] / moe["assignments"]
