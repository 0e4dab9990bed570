"""Of the rows the routed experts' grouped products ran on, the percentage
that were live: the (word, choice) pairs that landed on an expert held here
over the rows of the buffers the expert-layer calls took (the quarter tier,
the bound, or every pair; the rest are noughts the products multiply all the
same). The program's own counters, summed over the run
(``record["runtime"]["moe"]``, spacy_ray_tpu/names.py). A program without the
counter (no ``buffer_rows`` in its ``moe`` block, or no block) leaves the
metric out."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    moe = (record.get("runtime") or {}).get("moe")
    if not isinstance(moe, dict) or not moe.get("buffer_rows"):
        return None
    return 100.0 * moe["assignments_held"] / moe["buffer_rows"]
