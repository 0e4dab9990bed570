"""The readers of the program's own spans (``spacy_ray_tpu/names.py``) on a
hand-made window, and on the window of a program that has no such span (the
parent commit): the metric is left out, nothing is raised. Runs on a CPU:
``pytest benchmark/tests``."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

from common import load_module  # noqa: E402

# 10 s, 4 steps; the collate thread worked 8 s of them
WINDOW = {
    "seconds": 10.0,
    "steps": 4,
    "stage_seconds": {
        "read": 0.5, "collate": 8.0, "transfer": 0.25, "queue_wait": 7.0,
        "collate/features": 0.8,
        "collate/targets": 6.4,
        "collate/targets/tagger": 0.4,
        "collate/targets/parser": 4.0,
        "collate/targets/ner": 2.0,
        "collate/targets/ner/device_call": 1.2,
        "collate/targets/spancat/device_call": 0.4,
        "collate/stack": 0.4,
        "loop_host": 0.3,
        "loop_host/dispatch": 0.1,
    },
}

# (reader, the one key without which it has nothing to read, value on WINDOW)
READERS = [
    ("input_read_share", "read", 0.05),
    ("input_transfer_share", "transfer", 0.025),
    ("collate_features_share", "collate/features", 10.0),
    ("collate_parser_share", "collate/targets/parser", 50.0),
    ("collate_ner_share", "collate/targets/ner", 25.0),
    ("collate_device_call_share", "/device_call", 20.0),
    ("collate_self_share", "collate/targets", 5.0),
    ("loop_host_share", "loop_host", 3.0),
    ("loop_dispatch_ms", "loop_host/dispatch", 25.0),
]


@pytest.mark.parametrize("name,key,expected", READERS)
def test_reader_on_a_hand_made_window_and_without_its_key(name, key, expected):
    read = load_module("layer_metrics", name).read
    assert read({"window": WINDOW}) == pytest.approx(expected)
    without = dict(WINDOW, stage_seconds={
        k: v for k, v in WINDOW["stage_seconds"].items() if not k.endswith(key)})
    assert read({"window": without}) is None
    # the parent commit's window: the four stage keys and nothing else
    parent = dict(WINDOW, stage_seconds={
        k: v for k, v in WINDOW["stage_seconds"].items()
        if k in ("read", "collate", "transfer", "queue_wait") and k != key})
    assert read({"window": parent}) is None
    assert read({"kind": "serve", "window": None}) is None


def test_sm_window_without_a_stack_span():
    """``accumulate_gradient`` = 1 stacks nothing: the self share counts the
    missing key as zero seconds."""
    stages = {k: v for k, v in WINDOW["stage_seconds"].items() if k != "collate/stack"}
    read = load_module("layer_metrics", "collate_self_share").read
    assert read({"window": dict(WINDOW, stage_seconds=stages)}) == pytest.approx(10.0)
