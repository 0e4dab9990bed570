"""Of the expert-layer calls of the run, the percentage whose live (word,
choice) pairs fitted the bounded buffer (twice this rank's even share of the
pairs) and so moved only that many rows; the rest took the full path, which
moves every pair: the program's own counters, summed over the run
(``record["runtime"]["moe"]``, spacy_ray_tpu/names.py). A program without the
counter (no ``bounded_calls`` in its ``moe`` block, or no block) leaves the
metric out."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    moe = (record.get("runtime") or {}).get("moe")
    if not isinstance(moe, dict) or "bounded_calls" not in moe or not moe.get("layer_calls"):
        return None
    return 100.0 * moe["bounded_calls"] / moe["layer_calls"]
