"""The readers of the program's own spans (``spacy_ray_tpu/names.py``) on a
hand-made window, and on the window of a program that has no such span (the
parent commit): the metric is left out, nothing is raised. The readers of the
trace's summary on a hand-made record, one of them added as a file in a
temporary directory. Runs on a CPU: ``pytest benchmark/tests``."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
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


# what trace_reduce gives a four-chip run: 0.25 s of a 10 s slice in collectives
TRACE = {"chips": 4, "window_s": 10.0, "busy_s": 2.0, "idle_share": 0.8,
         "collective_s": 0.25, "collective_share": 0.025, "steps": 4,
         "kernels_s": {"srt_flash_fwd": 0.3, "srt_flash_bwd": 0.5, "srt_fused_adam": 0.002}}


def test_collective_share_on_a_hand_made_record():
    read = load_module("layer_metrics", "collective_share").read
    assert read({"kind": "train", "trace": TRACE}) == pytest.approx(2.5)
    assert read({"kind": "train", "trace": None}) is None  # no device operation ran


KERNEL_READER = '''
def read(record):
    t = record.get("trace") or {}
    seconds = t.get("kernels_s", {}).get("srt_flash_bwd")
    if not seconds or not t.get("steps"):
        return None
    return 1e3 * seconds / t["steps"]
'''


def test_a_reader_of_kernels_s_is_an_added_file(tmp_path, monkeypatch):
    """A kernel's milliseconds a step, from ``kernels_s`` and ``steps``: a new
    file under ``layer_metrics`` and no edit to any file that is there."""
    readers = tmp_path / "benchmark" / "layer_metrics"
    readers.mkdir(parents=True)
    (readers / "flash_bwd_ms.py").write_text(KERNEL_READER)
    monkeypatch.setattr(common, "ROOT", tmp_path)
    monkeypatch.setattr(common, "BENCH", tmp_path / "benchmark")
    read = load_module("layer_metrics", "flash_bwd_ms").read
    assert read({"trace": TRACE}) == pytest.approx(125.0)
    assert read({"trace": dict(TRACE, kernels_s={})}) is None  # the kernel left the path
    assert read({"trace": None}) is None
