"""Of the parameters' elements, the percentage whose leaf the fused Adam
kernel updated where it lay, in the layout and dtype the leaf already had (no
copy to another shape round the kernel): the program's own tally, worked out
from the leaves' shapes when the step is traced
(``record["runtime"]["fused_update_in_place"]``, spacy_ray_tpu/ops/fused_update.py).
A program without the tally (the parent commit; a mesh of several chips, where
the kernel gives way to XLA) leaves the metric out."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    tally = (record.get("runtime") or {}).get("fused_update_in_place")
    if not isinstance(tally, dict) or "share" not in tally:
        return None
    return 100.0 * tally["share"]
