"""From the profiler's ``.xplane.pb`` to numbers: device busy time and idle
share, time in collectives, the device operations that took most time, and the
idle gaps by what the host was doing in them. Needs nothing but JAX
(``jax.profiler.ProfileData``). Checked on a recorded trace and on a
hand-made one in ``benchmark/tests``.

Conventions, fixed here so that every PR computes the same number:

* a device plane is one named ``/device:TPU:<n>`` (one per chip; planes with
  a longer name, such as a chip's SparseCore planes, are left out);
* its operations are the events of the line named ``XLA Ops``; where a plane
  has no such line, every line but the step, module, annotation and async
  lines. An operation's name is its HLO instruction's name and output shape;
* the window is the harness's ``bench:slice`` annotation where the trace has
  one, else it runs from the first to the last event read; events are clipped
  to it. Intervals the harness cut out (``cuts_s``, seconds from the slice's
  start: update calls that compiled) count neither as window nor as busy nor
  as a gap;
* busy is the union of the operations' intervals, per chip, averaged over the
  chips;
* a collective is an operation whose name starts with one of ``COLLECTIVES``,
  on the operation line or on ``Async XLA Ops`` (where the TPU puts the
  ``-start``/``-done`` halves of an overlapped one): the union of both;
* a kernel of the program is an operation whose instruction name starts with
  ``srt_`` (the ``name=`` of its ``pallas_call``): ``kernels_s`` gives the
  seconds of EVERY such kernel on the first chip, by name without the ``.N``
  suffix, however far down the list of operations it comes. A kernel's
  operation is a leaf, so its duration is its self time. ``steps`` counts the
  events of the ``XLA Modules`` line of that chip whose name starts with
  ``jit_srt_train_step`` and whose middle lies inside the window (the host's
  and the device's clocks differ by microseconds, and the slice's first step
  begins with the slice): what a reader divides a kernel's seconds by;
* an idle gap on the first chip belongs to the harness annotation
  (``bench:...``) that covers most of it, if that is at least half, else to
  ``host: other``.

``python benchmark/trace_reduce.py <dir-or-file>`` prints the summary;
``--dump`` lists planes, lines and a few events, for looking at a new trace.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
                "Framework Ops", "Source code")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
MODULE_LINE = "XLA Modules"
STEP_MODULE = "jit_srt_train_step"
KERNEL_PREFIX = "srt_"
ANNOTATION_PREFIX = "bench:"
TOP = 10

Interval = Tuple[int, int]


def find_xplane(trace_dir: Path) -> Optional[Path]:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return files[-1] if files else None


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if a < hi and b > lo]


def subtract(intervals: List[Interval], holes: List[Interval]) -> List[Interval]:
    """Disjoint sorted ``intervals`` without the disjoint sorted ``holes``."""
    out: List[Interval] = []
    for a, b in intervals:
        for x, y in holes:
            if y <= a or x >= b:
                continue
            if x > a:
                out.append((a, x))
            a = max(a, y)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def op_name(text: str) -> str:
    """The profiler names a TPU operation by its whole HLO instruction,
    ``%fusion.5 = f32[16384,96]{1,0:T(8,128)} fusion(...)``: keep the
    instruction's name and the shape it produces, ``fusion.5 f32[16384,96]``.
    A loop (``while.202``: a scanned layer stack) is an event of its own that
    spans its body's events, so the breakdown's rows overlap; busy does not
    (it is a union)."""
    head, _, rest = text.lstrip("%").partition(" = ")
    shape = re.match(r"\w+\[[\d,]*\]", rest)  # none for a tuple, e.g. a while loop's
    return f"{head} {shape.group(0) if shape else ''}".strip()[:120]


def kernel_name(text: str) -> Optional[str]:
    """``srt_flash_fwd`` for ``%srt_flash_fwd.16 = (bf16[...], ...) custom-call(...)``,
    None for an operation that is no kernel of the program."""
    head = text.lstrip("%").partition(" = ")[0]
    if not head.startswith(KERNEL_PREFIX):
        return None
    return re.sub(r"\.\d+$", "", head)


def _op_lines(plane: Any) -> List[Any]:
    lines = list(plane.lines)
    named = [l for l in lines if l.name == OP_LINE]
    return named or [l for l in lines if l.name not in NOT_OP_LINES + (ASYNC_LINE,)]


def _events(line: Any) -> List[Tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name) for e in line.events]


SLICE = "bench:slice"


def reduce_data(data: Any, cuts_s: Iterable[Tuple[float, float]] = ()) -> Optional[Dict[str, Any]]:
    """The summary of one profile, or None where no device operation ran."""
    devices: Dict[str, List[Tuple[int, int, str]]] = {}
    modules: Dict[str, List[Tuple[int, int, str]]] = {}
    background: Dict[str, List[Tuple[int, int, str]]] = {}
    annotations: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            events = [ev for line in _op_lines(plane) for ev in _events(line)]
            if events:
                devices[plane.name] = events
                background[plane.name] = [ev for line in plane.lines
                                          if line.name == ASYNC_LINE for ev in _events(line)]
                modules[plane.name] = [ev for line in plane.lines
                                       if line.name == MODULE_LINE for ev in _events(line)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations.extend(ev for ev in _events(line)
                                   if ev[2].startswith(ANNOTATION_PREFIX))
    if not devices:
        return None
    slices = [ev for ev in annotations if ev[2] == SLICE]
    annotations = [ev for ev in annotations if ev[2] != SLICE]
    everything = [ev for evs in devices.values() for ev in evs] + annotations
    t0 = slices[0][0] if slices else min(a for a, _, _ in everything)
    t1 = slices[0][1] if slices else max(b for _, b, _ in everything)
    holes = merge(clip([(t0 + int(a * 1e9), t0 + int(b * 1e9)) for a, b in cuts_s], t0, t1))
    window = t1 - t0 - total(holes)

    def kept(intervals: Iterable[Interval]) -> List[Interval]:
        return subtract(merge(clip(intervals, t0, t1)), holes)

    busy, collective = [], []
    for plane_name, events in devices.items():
        busy.append(total(kept((a, b) for a, b, _ in events)))
        collective.append(total(kept(
            (a, b) for a, b, n in events + background[plane_name] if is_collective(n))))

    first_chip = sorted(devices)[0]
    first = devices[first_chip]
    by_op: Dict[str, int] = {}
    by_kernel: Dict[str, int] = {}
    for a, b, name in first:
        inside = total(kept([(a, b)]))
        by_op[op_name(name)] = by_op.get(op_name(name), 0) + inside
        kernel = kernel_name(name)
        if kernel is not None and inside:
            by_kernel[kernel] = by_kernel.get(kernel, 0) + inside
    steps = sum(1 for a, b, name in modules[first_chip]
                if name.startswith(STEP_MODULE) and kept([((a + b) // 2, (a + b) // 2 + 1)]))
    merged = merge(kept((a, b) for a, b, _ in first) + holes)  # a hole is no gap
    edges = [t0] + [x for iv in merged for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    by_cause: Dict[str, int] = {}
    notes = {name: clip(spans, t0, t1) for name, spans in merge_by_name(annotations).items()}
    for a, b in gaps:
        cause, covered = "host: other", 0
        for name, spans in notes.items():
            c = overlap(spans, a, b)
            if c > covered:
                cause, covered = name, c
        if 2 * covered < (b - a):
            cause = "host: other"
        by_cause[cause] = by_cause.get(cause, 0) + (b - a)

    def top(table: Dict[str, int]) -> List[List[Any]]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / 1e9] for name, ns in rows]

    n = len(devices)
    return {
        "chips": n,
        "cut_s": total(holes) / 1e9,
        "window_s": window / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "idle_share": 1.0 - sum(busy) / n / window,
        "collective_s": sum(collective) / n / 1e9,
        "collective_share": sum(collective) / n / window,
        "busy_s_by_chip": {name: b / 1e9 for name, b in zip(devices, busy)},
        "device_ops": top(by_op),
        "kernels_s": {name: ns / 1e9 for name, ns in sorted(by_kernel.items())},
        "steps": steps,
        "idle_gaps": top(by_cause),
        "longest_gap_s": max((b - a for a, b in gaps), default=0) / 1e9,
        "n_gaps": len(gaps),
        "annotation_s": {name: total(spans) / 1e9 for name, spans in notes.items()},
    }


def merge_by_name(events: Iterable[Tuple[int, int, str]]) -> Dict[str, List[Interval]]:
    by_name: Dict[str, List[Interval]] = {}
    for a, b, name in events:
        by_name.setdefault(name, []).append((a, b))
    return {name: merge(spans) for name, spans in by_name.items()}


def overlap(spans: List[Interval], a: int, b: int) -> int:
    return sum(max(0, min(b, y) - max(a, x)) for x, y in spans if x < b and y > a)


def reduce_file(path: Path, cuts_s: Iterable[Tuple[float, float]] = ()) -> Optional[Dict[str, Any]]:
    from jax.profiler import ProfileData

    return reduce_data(ProfileData.from_file(str(path)), cuts_s)


def reduce_dir(trace_dir: Path, cuts_s: Iterable[Tuple[float, float]] = ()) -> Optional[Dict[str, Any]]:
    path = find_xplane(trace_dir)
    return reduce_file(path, cuts_s) if path is not None else None


def dump(path: Path, n_events: int = 6) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(path)).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for e in events[:n_events]:
                print(f"    {e.name!r} start_ns={e.start_ns} dur_ns={e.duration_ns}")


if __name__ == "__main__":
    target = Path(sys.argv[-1])
    file = target if target.is_file() else find_xplane(target)
    if file is None:
        sys.exit(f"no .xplane.pb under {target}")
    if "--dump" in sys.argv:
        dump(file)
    else:
        print(json.dumps(reduce_file(file), indent=1))
