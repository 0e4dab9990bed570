"""Temporaries of the largest step the run made, by the compiler's own memory
analysis of that program (``train_cell.step_memory``), in GB."""

from typing import Any, Dict, Optional


def read(record: Dict[str, Any]) -> Optional[float]:
    memory = record.get("step_memory")
    return memory["temp"] / 1e9 if memory else None
