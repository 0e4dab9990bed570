"""Of the (row, chunk) blocks the state-space layers' chunked scan ran, the
percentage that held at least one real word: the program's own counters,
summed over the run (``record["runtime"]["ssm"]``: ``live_chunks`` /
``chunks``, from the batches' masks; spacy_ray_tpu/names.py). The rest is
the scan's own waste: whole chunks of padding, computed like any other. A
program without the block (the parent commit, a trunk with no state-space
layer) leaves the metric out."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    ssm = (record.get("runtime") or {}).get("ssm")
    if not isinstance(ssm, dict) or not ssm.get("chunks") or "live_chunks" not in ssm:
        return None
    return 100.0 * ssm["live_chunks"] / ssm["chunks"]
