"""Operations the forward and backward need per REAL word (benchmark/flops.py,
from the configuration's shapes and the window's word-weighted mean document
length; remat and padding not counted) x words/s/chip over the chip's bf16
peak, in percent."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    if record.get("kind") != "train" or not record.get("train_wps_chip"):
        return None
    import flops
    from common import peaks_for

    per_word = flops.train_flops_per_word(
        record["config"], record["window"]["attention_context_words"])
    peak = peaks_for(record["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_word * record["train_wps_chip"] / peak
