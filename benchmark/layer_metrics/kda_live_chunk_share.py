"""Of the (row, chunk) blocks the delta-rule layers' chunked recurrence ran,
the percentage that held at least one real word: the program's own counters,
summed over the run (``record["runtime"]["kda"]``: ``live_chunks`` /
``chunks``, from the batches' masks; spacy_ray_tpu/names.py). The rest is the
recurrence's own waste: whole chunks of padding, solved and carried like any
other. A program without the block (the parent commit, a trunk with no
delta-rule layer) leaves the metric out."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    kda = (record.get("runtime") or {}).get("kda")
    if not isinstance(kda, dict) or not kda.get("chunks") or "live_chunks" not in kda:
        return None
    return 100.0 * kda["live_chunks"] / kda["chunks"]
