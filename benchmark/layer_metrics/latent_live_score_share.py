"""Of the (query, key) score entries the latent-attention layers computed, the
percentage whose query and key were both real words with the key at or
before the query: the program's own counters, summed over the run
(``record["runtime"]["mla"]``: ``live_scores`` / ``scores``, from the
batches' masks; spacy_ray_tpu/names.py). The rest is the attention's own
waste: padding, and the half of every row past the diagonal that the causal
mask throws away after computing it. A path that skips dead blocks reports
what it computes, so the share moves with it. A program without the block
(the parent commit, a trunk with no latent-attention layer) leaves the metric
out."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    mla = (record.get("runtime") or {}).get("mla")
    if not isinstance(mla, dict) or not mla.get("scores") or "live_scores" not in mla:
        return None
    return 100.0 * mla["live_scores"] / mla["scores"]
