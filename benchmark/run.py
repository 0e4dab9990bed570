"""One command, one cell, one run.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and everything that belongs to it by
name (``configs/<config>.json``, ``traffic/<mix>.json``, the mix's generator,
the configuration's reference, one reader per per-layer metric), makes data
and weights from the seed, warms the cell's shapes, measures for ``--seconds``
and prints one JSON object as its last line. It needs the TPU and as many
chips as the cell asks for; without them it exits non-zero and prints no
result. ``--rehearse-cpu`` runs the same control flow at tiny widths on the
CPU (four virtual devices for a four-chip cell); its last line says
``"rehearsal": true``, names the cpu and carries no metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from common import (BenchError, device_description, load_cell, load_module,  # noqa: E402
                    start_jax)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    cell = load_cell(args.workload)
    start_jax(int(cell["chips"]), args.rehearse_cpu)

    runner = load_module(".", f"{cell['traffic_file']['kind']}_cell")
    out = runner.run(cell, args)

    # where the set-up went, in seconds from the start of the process
    print(f"setup phases { {k: round(t - T_PROCESS_START, 3) for k, t in out['setup_marks'].items()} }",
          flush=True)
    metrics = {}
    if args.trace:
        for metric in cell["per_layer"]:
            try:
                value = load_module("layer_metrics", metric["name"]).read(out["record"])
            except BenchError as e:
                if not args.rehearse_cpu:  # the CPU has no peak to divide by
                    raise
                print(f"rehearsal: {metric['name']} not read: {e}", flush=True)
                continue
            if value is not None:  # a reader that finds nothing leaves its metric out
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    else:
        values = dict(out["end_to_end"], setup_s=out["window_open_at"] - T_PROCESS_START)
        for metric in cell["end_to_end"]:
            metrics[metric["name"]] = {"value": values[metric["name"]], "unit": metric["unit"]}
    device = device_description(out["memory_peaks"])
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    summary = out["record"].get("trace")
    if args.trace and summary:
        device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    if args.rehearse_cpu:  # never to be read as a chip run
        line = {"rehearsal": True, "correct": out["correct"], "attempted": out["attempted"],
                "failed": out["failed"], "metrics": {}, "device": device,
                "would_report": sorted(metrics)}
    # each number `correct` compared beside its limit: last in the line, and
    # the last lines on standard error
    line["compared"] = out.get("compared", {})
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        code = 2
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # the loop's and the server's daemon threads need no farewell
