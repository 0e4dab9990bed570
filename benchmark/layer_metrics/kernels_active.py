"""Main-path kernels the runtime report names "active (pallas...)" after the
window. A drop is a silent fallback."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    runtime = record.get("runtime")
    if not runtime:
        return None
    names = ("flash_attention", "hash_embed_kernel", "fused_update")
    return float(sum(1 for n in names if str(runtime.get(n, "")).startswith("active (pallas")))
