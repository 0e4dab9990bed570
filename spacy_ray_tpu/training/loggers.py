"""Training loggers: the pluggable ``[training.logger]`` registry slot.

Capability parity with the reference's console logger plugin (reference
loggers.py:8-66, registered ``spacy-ray.ConsoleLogger.v1`` via
setup.cfg:40-41; SURVEY.md §5.5). Same protocol: the factory returns a
setup function taking the pipeline and returning ``(log_step, finalize)``;
``log_step(info_or_None)`` is called every step (None = no new row).

TPU additions (SURVEY.md §5.5 "add words/sec/chip and step-time metrics as
first-class"): WPS and WPS/chip columns computed from the loop's counters.
"""

from __future__ import annotations

import sys
from typing import Any, Callable, Dict, IO, List, Optional, Tuple

from ..registry import registry


def _fmt(value: float, width: int = 8, places: int = 2) -> str:
    return f"{value:{width}.{places}f}"


def _elapsed(seconds: float) -> str:
    """H:MM:SS wall-clock elapsed — the reference's first column
    (reference loggers.py:52)."""
    s = int(seconds)
    return f"{s // 3600}:{(s % 3600) // 60:02d}:{s % 60:02d}"


@registry.loggers("spacy-ray.ConsoleLogger.v1")
@registry.loggers("spacy_ray_tpu.ConsoleLogger.v1")
def console_logger(progress_bar: bool = False):
    def setup(nlp, stdout: IO = sys.stdout, stderr: IO = sys.stderr):
        import time

        pipe_names = [
            n for n in nlp.head_names() if nlp.components[n].trainable
        ]
        score_keys = list(nlp.config.get("training", {}).get("score_weights", {}) or {})
        if not score_keys:
            # same fallback as the loop's final score: the components'
            # declared default weights (positive-weight keys only)
            from .loop import default_pipeline_score_weights

            score_keys = [
                k for k, v in default_pipeline_score_weights(nlp).items() if v > 0
            ]
        loss_cols = [f"Loss {n}" for n in pipe_names]
        score_cols = score_keys
        # Stp50/Stp95: rolling step-time percentiles in ms, populated when
        # [training] metrics_dir enables telemetry (blank otherwise) —
        # SURVEY §5.5's step-time-as-first-class-metric column
        header = (
            ["T", "E", "#", "W"]
            + loss_cols
            + score_cols
            + ["Stp50", "Stp95", "WPS", "EvalS", "Score"]
        )
        widths = [max(len(h), 8) for h in header]
        stdout.write(" ".join(h.rjust(w) for h, w in zip(header, widths)) + "\n")
        stdout.write(" ".join("-" * w for w in widths) + "\n")
        t0 = time.perf_counter()
        eval_freq = int(nlp.config.get("training", {}).get("eval_frequency", 0) or 0)
        pending = 0  # steps since the last printed row (progress bar)

        def log_step(info: Optional[Dict[str, Any]]) -> None:
            nonlocal pending
            if info is None:
                if progress_bar and stderr is not None:
                    pending += 1
                    if eval_freq:
                        done = int(20 * pending / eval_freq)
                        bar = "#" * done + "-" * (20 - done)
                        stderr.write(f"\r[{bar}] {pending}/{eval_freq}")
                    else:
                        stderr.write(f"\rstep +{pending}")
                    stderr.flush()
                return
            if progress_bar and stderr is not None and pending:
                stderr.write("\r" + " " * 40 + "\r")
                stderr.flush()
            pending = 0
            row: List[str] = [
                _elapsed(time.perf_counter() - t0).rjust(widths[0]),
                str(info.get("epoch", 0)).rjust(widths[1]),
                str(info.get("step", 0)).rjust(widths[2]),
                str(info.get("words", 0)).rjust(widths[3]),
            ]
            losses = info.get("losses", {})
            for i, name in enumerate(pipe_names):
                row.append(_fmt(float(losses.get(name, 0.0)), widths[4 + i]))
            scores = info.get("other_scores", {})
            for j, key in enumerate(score_keys):
                val = scores.get(key)
                col = widths[4 + len(pipe_names) + j]
                row.append(_fmt(float(val) * 100, col) if val is not None else " " * col)
            for j, key in enumerate(("step_ms_p50", "step_ms_p95")):
                val = info.get(key)
                col = widths[-5 + j]
                row.append(
                    _fmt(float(val), col, 1) if val is not None else " " * col
                )
            row.append(_fmt(float(info.get("wps", 0.0)), widths[-3], 0))
            row.append(_fmt(float(info.get("eval_seconds", 0.0)), widths[-2]))
            score = info.get("score")
            row.append(
                _fmt(float(score) * 100, widths[-1]) if score is not None else " " * widths[-1]
            )
            stdout.write(" ".join(row) + "\n")
            stdout.flush()

        def finalize() -> None:
            if progress_bar and stderr is not None and pending:
                stderr.write("\r" + " " * 40 + "\r")
                stderr.flush()

        return log_step, finalize

    return setup


@registry.loggers("spacy_ray_tpu.JsonlLogger.v1")
def jsonl_logger(path: Optional[str] = None):
    """Machine-readable per-step log (jsonl) for dashboards/benchmarks."""
    import json

    def setup(nlp, stdout: IO = sys.stdout, stderr: IO = sys.stderr):
        from .resilience import drain_events
        from .telemetry import sanitize_json

        handle = open(path, "a", encoding="utf8") if path else None

        def log_step(info: Optional[Dict[str, Any]]) -> None:
            if info is None:
                return
            rec = {
                k: info.get(k)
                for k in (
                    "epoch", "step", "words", "wps", "eval_seconds",
                    "score", "losses", "other_scores", "input_pipeline",
                    # telemetry gauge snapshot (step-time p50/p95, HBM,
                    # compile count) when [training] metrics_dir is on
                    "telemetry",
                )
            }
            if rec.get("telemetry") is None:
                rec.pop("telemetry", None)
            # resilience events since the last row (resume anomalies,
            # retries, checkpoint fallbacks, preemption) — jsonl is the
            # machine-readable record, so anomalies must land here too
            events = drain_events()
            if events:
                rec["events"] = events
            # sanitize: a NaN loss/score must not emit a bare `NaN` token
            # (invalid JSON) in the machine-readable log
            line = json.dumps(sanitize_json(rec), default=float)
            if handle:
                handle.write(line + "\n")
                handle.flush()
            else:
                stdout.write(line + "\n")

        def finalize() -> None:
            # events queued AFTER the last row (the `preempted` record and
            # any final-checkpoint retries live exactly there) still land
            # in the jsonl file as a trailing events-only record
            events = drain_events()
            if events:
                line = json.dumps({"events": events}, default=float)
                if handle:
                    handle.write(line + "\n")
                else:
                    stdout.write(line + "\n")
            if handle:
                handle.close()

        return log_step, finalize

    return setup
