"""A training cell: ``training.loop.train`` — the function the ``train``
command calls — on a corpus made from the seed, observed at its step boundary
from outside. Nothing in the program is changed; the harness puts five
wrappers round names the loop looks up when it runs:

* ``loop.make_train_step``: the step boundary (window edges, losses, compiles);
* ``loop.place_batch``: the batch the step is given, still on the host (real
  words and padded cells, counted from the mask);
* ``collate_pool.PipelineStats``: a handle on the loop's own stage clocks;
* ``prefetch.prefetch_iter`` (traced run only): a ``TraceAnnotation`` round the
  loop's wait for its next batch, on the profiler's clock;
* ``loop.resolve_dot_name``: a handle on the corpus the loop resolved, to ask
  it whether its epochs hand out fresh examples.
"""

from __future__ import annotations

import collections
import math
import os
import signal
import threading
import time
from pathlib import Path
from typing import Any, Deque, Dict, List, Optional, Tuple

from common import ROOT, BenchError, WorkDir, load_module, memory_peaks, write_jsonl

# ISSUE 22: an update call that compiles inside the window is cut out, and
# more than this many in one window fail ``correct``
MAX_COMPILES_IN_WINDOW = 2
# blocked steps left out of ``train_step_ms``: while ``stop_trace`` writes the
# trace the loop stands still and the input pipeline fills up (the prefetch
# queue's two batches and the one in the producer's hand), so the first blocked
# steps after it run at the device's pace, not the pipeline's. 3 until PR 32;
# trf's traced run had five piled up (PERF.md sections 6 and 7)
QUEUED_AHEAD = 5
# a mix that says what its documents do ``on_repeat`` may pass its corpus's
# end; no later pass may then run faster than the first by more than this
# (words/s between update calls, passes of ``PASS_MIN_STEPS`` intervals or
# more). Between two readings on the chip (PERF.md section 6, PR 32)
LATER_PASS_OVER_FIRST = 1.5
PASS_MIN_STEPS = 3


class StepSpy:
    """Wraps the loop's update function. One call is one optimizer step.

    Untraced run: warm-up (at least ``warm_steps`` steps and ``warm_seconds``
    from the first step, so that a cell with short steps meets its rarer batch
    shapes too), then one window of ``seconds``: it opens on
    ``block_until_ready`` of the last dispatched loss and closes the same way
    at the first step boundary after ``seconds``; nothing blocks in between.
    An update call that compiles (a batch shape the warm-up did not meet) is
    blocked on and cut out, time and words, and the window runs that much
    longer.

    Traced run: the same, but the window is ``trace_seconds`` long and lies
    inside a profiler trace (the "slice"); after it each step is blocked, and
    the intervals between completions (the first ``QUEUED_AHEAD`` left out)
    give ``train_step_ms``.
    """

    def __init__(self, *, seconds: float, warm_steps: int, warm_seconds: float,
                 trace_dir: Optional[Path],
                 trace_seconds: float, words_fifo: Deque[Tuple[int, int, int]],
                 compile_count: Any, loop_thread_compiles: Any,
                 stats_handles: List[Any]) -> None:
        self.seconds = float(seconds)
        self.warm_steps = int(warm_steps)
        self.warm_seconds = float(warm_seconds)
        self.t_first_call: Optional[float] = None
        self.trace_dir = trace_dir
        self.window_seconds = float(trace_seconds) if trace_dir else self.seconds
        self.words_fifo = words_fifo
        self.compile_count = compile_count  # the program's hook: every thread
        self.loop_thread_compiles = loop_thread_compiles  # the harness's: this thread
        self.stats_handles = stats_handles
        self.phase = "warm"
        self.steps: List[Dict[str, Any]] = []
        self.losses: List[Any] = []
        self.residency: Optional[Dict[str, Any]] = None
        self.takes_shadow = False
        self.update: Any = None
        self.largest: Tuple[int, Any] = (0, None)  # cells, the call's abstract arguments
        self.edges: Dict[str, Any] = {}
        self.cut_seconds = 0.0
        self.cut_words = 0
        self.cut_cells = 0
        self.cut_sq_words = 0
        self.compiles_in_window = 0
        self.cuts: List[Tuple[float, float]] = []  # seconds from the window's opening
        self.blocked_done_at: List[float] = []  # stop_trace's return, then each blocked step
        self.t_run_open: Optional[float] = None
        self.slice_note: Any = None

    # -- helpers ---------------------------------------------------------
    def _stage_seconds(self) -> Dict[str, float]:
        if not self.stats_handles:
            return {}
        stats = self.stats_handles[-1]
        with stats._lock:
            return dict(stats.seconds)

    def _edge(self) -> Dict[str, Any]:
        return {"t": time.perf_counter(), "stages": self._stage_seconds(),
                "compiles": self.compile_count(), "step": len(self.steps)}

    def _block_last(self) -> None:
        import jax

        if self.losses:
            jax.block_until_ready(self.losses[-1])

    # -- the wrapper -----------------------------------------------------
    def wrap(self, update: Any) -> Any:
        import jax

        self.takes_shadow = bool(getattr(update, "takes_shadow", False))
        self.update = update

        def run(*args: Any) -> Any:
            if self.residency is None:
                self.residency = _residency(args, self.takes_shadow)
            n = len(self.steps)
            if self.t_first_call is None:
                self.t_first_call = time.perf_counter()
            if self.phase == "warm" and n >= self.warm_steps and (
                time.perf_counter() - self.t_first_call >= self.warm_seconds
            ):
                self._block_last()
                if self.trace_dir is not None:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(str(self.trace_dir), profiler_options=options)
                    # the slice on the profiler's own clock
                    self.slice_note = jax.profiler.TraceAnnotation("bench:slice")
                    self.slice_note.__enter__()
                self.t_run_open = time.perf_counter()
                self.edges["open"] = self._edge()
                self.phase = "open"
            elif self.phase == "open" and (
                time.perf_counter() - self.edges["open"]["t"] - self.cut_seconds
                >= self.window_seconds
            ):
                self._block_last()
                self.edges["close"] = self._edge()
                if self.trace_dir is not None:
                    self.slice_note.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    self.blocked_done_at.append(time.perf_counter())
                    self.phase = "blocked"
                else:
                    self._finish()
            elif self.phase == "blocked" and (
                time.perf_counter() - self.t_run_open >= self.seconds
                and len(self.paced_done_at()) >= 4
            ):
                self._finish()

            words, cells, sq_words = self.words_fifo.popleft()
            if cells > self.largest[0]:  # the arguments are donated: keep their shapes
                self.largest = (cells, jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=getattr(x, "sharding", None)), args))
            before = self.loop_thread_compiles()
            t_call = time.perf_counter()
            if self.trace_dir is not None and self.phase == "open":
                with jax.profiler.TraceAnnotation("bench:update_call"):
                    out = update(*args)
            else:
                out = update(*args)
            loss = out[-2]
            self.losses.append(loss)
            self.steps.append({"words": words, "cells": cells, "sq_words": sq_words,
                               "phase": self.phase, "t_call": t_call})
            compiled = self.loop_thread_compiles() - before
            if compiled and self.phase == "open":
                # a shape the warm-up did not meet: block on this call and cut
                # its time and its words out of the window
                jax.block_until_ready(loss)
                t_done = time.perf_counter()
                self.cut_seconds += t_done - t_call
                self.cuts.append((t_call - self.edges["open"]["t"],
                                  t_done - self.edges["open"]["t"]))
                self.cut_words += words
                self.cut_cells += cells
                self.cut_sq_words += sq_words
                self.compiles_in_window += compiled
                self.steps[-1]["cut"] = True
            if self.phase == "blocked":
                jax.block_until_ready(loss)
                self.blocked_done_at.append(time.perf_counter())
            return out

        run.__dict__.update(getattr(update, "__dict__", {}))
        return run

    def paced_done_at(self) -> List[float]:
        """When each blocked step completed, from the last of the
        ``QUEUED_AHEAD`` on: those were collated while the loop was busy
        writing the trace and complete at the device's pace, not the
        pipeline's."""
        return self.blocked_done_at[QUEUED_AHEAD:]

    def _finish(self) -> None:
        """The loop polls for a shutdown request after each step and stops
        at the boundary, as it does when a run is preempted."""
        self.phase = "done"
        os.kill(os.getpid(), signal.SIGTERM)

    # -- what the window held ----------------------------------------------
    def window(self) -> Dict[str, Any]:
        if "close" not in self.edges:
            raise BenchError("the loop ended before the window closed")
        a, b = self.edges["open"], self.edges["close"]
        steps = self.steps[a["step"]:b["step"]]
        seconds = b["t"] - a["t"] - self.cut_seconds
        words = sum(s["words"] for s in steps) - self.cut_words
        return {
            "seconds": seconds,
            "steps": len(steps),
            "words": words,
            "cells": sum(s["cells"] for s in steps) - self.cut_cells,
            # the words a word attends to: its own document's, so the mean
            # document length weighted by words (sum L^2 / sum L)
            "attention_context_words":
                (sum(s["sq_words"] for s in steps) - self.cut_sq_words) / max(words, 1),
            "stage_seconds": {k: b["stages"].get(k, 0.0) - a["stages"].get(k, 0.0)
                              for k in b["stages"]},
            "compiles": self.compiles_in_window,
            "compiles_edge_to_edge": b["compiles"] - a["compiles"],
            "cut_seconds": self.cut_seconds,
        }


def step_memory(spy: StepSpy) -> Optional[Dict[str, int]]:
    """The compiler's own account of the largest step the run made: the
    program is lowered again from the shapes of that call's arguments and comes
    back from the compile cache. ``temp`` is what the step needs beside its
    arguments and results; the runtime's ``peak_bytes_reserved`` is held
    against it (``common.memory_peaks``)."""
    lower = getattr(spy.update, "lower", None)
    if lower is None or spy.largest[1] is None:
        return None
    try:
        analysis = lower(*spy.largest[1]).compile().memory_analysis()
        return {k: int(getattr(analysis, f"{k}_size_in_bytes"))
                for k in ("argument", "output", "alias", "temp")}
    except Exception as e:  # a backend without the analysis: the metric is left out
        print(f"step memory not read: {type(e).__name__}: {e}", flush=True)
        return None


def _residency(step_args: Tuple[Any, ...], takes_shadow: bool) -> Dict[str, Any]:
    """Where the step's arguments live, from their shapes and shardings: on
    four chips the batch is split four ways, Adam's state is sharded and the
    parameters are replicated, or the cell is not correct."""
    import jax

    names = ["params", "opt_state"] + (["shadow"] if takes_shadow else []) + [
        "tokens", "targets"]
    out: Dict[str, Any] = {}
    for name, tree in zip(names, step_args):
        total, per_dev = 0, collections.defaultdict(int)
        for x in jax.tree_util.tree_leaves(tree):
            total += math.prod(x.shape) * x.dtype.itemsize
            shard = math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
            for d in x.sharding.device_set:
                per_dev[d.id] += shard
        out[name] = {"global_bytes": total, "devices": len(per_dev),
                     "bytes_on_fullest_device": max(per_dev.values())}
    chips = out["params"]["devices"]
    problems: List[str] = []
    if chips > 1:
        if out["params"]["bytes_on_fullest_device"] != out["params"]["global_bytes"]:
            problems.append("params are not replicated")
        if out["opt_state"]["bytes_on_fullest_device"] > 0.5 * out["opt_state"]["global_bytes"]:
            problems.append("optimizer state is not sharded")
        for name in ("tokens", "targets"):
            if chips * out[name]["bytes_on_fullest_device"] != out[name]["global_bytes"]:
                problems.append(f"{name} not split {chips} ways")
    out["problems"] = problems
    return out


def _loss_rule(losses: List[float], rule: str) -> Tuple[bool, Dict[str, float]]:
    third = max(len(losses) // 3, 1)
    first = sum(losses[:third]) / third
    last = sum(losses[-third:]) / third
    if rule == "last_third_below_first_third":
        return last < first, {"first_third_mean": first, "last_third_mean": last}
    raise BenchError(f"unknown loss rule {rule!r} in the traffic file")


def runtime_mismatches(runtime: Dict[str, Any], expected: Dict[str, Any]) -> List[str]:
    """``expect_runtime`` of the configuration's file against the program's
    ``runtime`` report: for each key a prefix, or a list of prefixes any of
    which the report may start with (the path the parent takes, or a kernel
    that a later PR brings). Nothing else is admitted: a reference path, a
    disabled kernel or a failed probe starts with none of them."""
    out = []
    for key, allowed in expected.items():
        prefixes = (allowed,) if isinstance(allowed, str) else tuple(allowed)
        if not str(runtime.get(key, "")).startswith(prefixes):
            out.append(f"runtime {key}: {runtime.get(key)!r}, expected " + (
                repr(allowed) if isinstance(allowed, str) else f"one of {list(allowed)!r}"))
    return out


def corpus_rule(counted: int, corpus_words: int, on_repeat: Optional[str], train_corpus: Any,
                by_pass: Dict[int, Dict[str, float]]) -> Tuple[List[str], Dict[str, List[Any]]]:
    """May the run have passed its corpus's end? The loop keeps each
    document's targets on its ``Example``, so a repeated epoch collates many
    times faster than the first and is another workload: a mix that does not
    say what happens ``on_repeat`` may not take more words than its corpus
    has. A mix that names a file of ``benchmark/on_repeat`` (fresh examples on
    a repeated epoch, every pass at the first one's cost) may. It is held to
    the corpus the loop resolved reporting ``augmented``, and to what the
    window shows (``by_pass``: ``rates_by_pass``): no later pass of
    ``PASS_MIN_STEPS`` steps or more between update calls faster than the
    first by more than ``LATER_PASS_OVER_FIRST``."""
    problems: List[str] = []
    augmented = bool(getattr(train_corpus, "augmented", False))
    first = by_pass.get(1)
    later = [group["words_per_s"] for n, group in by_pass.items()
             if n > 1 and group["steps"] >= PASS_MIN_STEPS]
    over_first = (max(later) / first["words_per_s"]
                  if later and first and first["steps"] >= PASS_MIN_STEPS else None)
    if on_repeat is None:
        if counted > corpus_words:
            problems.append(f"the run took {counted} words of a corpus of {corpus_words}: "
                            "an epoch repeated; the mix needs a larger n_train, or to say "
                            "what its documents do on_repeat")
    else:
        if not augmented:
            problems.append(f"the mix asks for {on_repeat!r} on a repeated epoch, and the corpus "
                            f"the loop resolved ({type(train_corpus).__name__}) does not report "
                            "`augmented`: a second pass would find the first one's targets kept")
        if over_first is not None and over_first > LATER_PASS_OVER_FIRST:
            problems.append(f"a later pass over the corpus ran at {over_first:.3f} times the "
                            f"first one's words/s between update calls (limit "
                            f"{LATER_PASS_OVER_FIRST}): it found targets kept, {by_pass}")
    return problems, {
        "words_taken_of_corpus": [counted, corpus_words],
        "corpus_passes": [counted / max(corpus_words, 1), 1.0 if on_repeat is None else None],
        "corpus_hands_out_fresh_examples": [augmented, on_repeat is not None],
        "later_pass_rate_over_first": [over_first, LATER_PASS_OVER_FIRST],
    }


def rates_by_pass(steps: List[Dict[str, Any]], first: int, last: int,
                  corpus_words: int) -> Dict[int, Dict[str, float]]:
    """Words a second between update calls, for each pass over the corpus
    that the window's steps ``[first, last)`` belong to (a step belongs to the
    pass in which its batch begins; the warm-up's words count towards where
    the corpus ends). In a cell whose pace the input pipeline sets, a pass
    that found the first one's targets kept would read several times the
    first. A call that was cut out for compiling is blocked on, so the
    interval that follows it is left out."""
    taken = sum(s["words"] for s in steps[:first + 1])
    out: Dict[int, Dict[str, float]] = {}
    for before, step in zip(steps[first:last], steps[first + 1:last]):
        if not before.get("cut"):
            group = out.setdefault(1 + taken // max(corpus_words, 1),
                                   {"steps": 0, "words": 0, "seconds": 0.0})
            group["steps"] += 1
            group["words"] += step["words"]
            group["seconds"] += step["t_call"] - before["t_call"]
        taken += step["words"]
    for group in out.values():
        group["words_per_s"] = group["words"] / group["seconds"]
    return out


def run(cell: Dict[str, Any], args: Any) -> Dict[str, Any]:
    import jax
    import jax.monitoring

    import spacy_ray_tpu.training.collate_pool as collate_pool
    import spacy_ray_tpu.training.loop as loop
    import spacy_ray_tpu.training.prefetch as prefetch
    from spacy_ray_tpu.config import load_config
    from spacy_ray_tpu.devices import runtime_report
    from spacy_ray_tpu.registry import registry
    from spacy_ray_tpu.training.telemetry import compile_count, install_compile_hook

    config_file, traffic = cell["config_file"], cell["traffic_file"]
    rehearsal = bool(args.rehearse_cpu)
    chips = int(cell["chips"])
    docs_spec = dict(traffic["docs"])
    if rehearsal:
        docs_spec.update(traffic.get("rehearse", {}).get("docs", {}))
    warm = {"warm_steps": traffic["warm_steps"], "warm_seconds": traffic["warm_seconds"]}
    if rehearsal:
        warm.update({k: v for k, v in traffic.get("rehearse", {}).items() if k in warm})
    generator = load_module("generators", docs_spec["generator"])
    # what a repeated epoch does to a document: nothing said, and the run may
    # not pass its corpus's end; or a file of ``benchmark/on_repeat``, by name
    on_repeat = docs_spec.get("on_repeat")
    repeat = None if on_repeat is None else load_module("on_repeat", on_repeat)
    if repeat is not None:
        repeat.register(registry)
    install_compile_hook()
    # the program's hook counts compilations on every thread (the collate
    # stage compiles small programs on the prefetch thread); to know that an
    # update call compiled, count those on the loop's own thread
    loop_thread = threading.get_ident()
    on_loop_thread = [0]

    def count_on_loop_thread(name: str, seconds: float, **kw: Any) -> None:
        if name.endswith(("backend_compile_duration", "backend_compile_time")) and (
            threading.get_ident() == loop_thread
        ):
            on_loop_thread[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count_on_loop_thread)

    # the set-up's stations: JAX and the chip up, the corpus made and written,
    # the loop's first update call (corpus read, model built, first batch
    # collated), the window's opening (program compiled or loaded, warm-up)
    marks = {"jax_up": time.perf_counter()}
    with WorkDir() as work:
        # ---- data from the seed -------------------------------------------
        train_docs = generator.generate(int(docs_spec["n_train"]), args.seed, docs_spec)
        corpus_words = sum(len(d["tokens"]) for d in train_docs)
        write_jsonl(work / "train.jsonl", train_docs)
        del train_docs
        write_jsonl(work / "dev.jsonl",
                    generator.generate(int(docs_spec["n_dev"]), args.seed + 1, docs_spec))
        marks["corpus_written"] = time.perf_counter()
        overrides = {
            **config_file.get("overrides", {}),
            **(config_file.get("rehearse_overrides", {}) if rehearsal else {}),
            **traffic.get("overrides", {}),
            **(traffic.get("rehearse", {}).get("overrides", {}) if rehearsal else {}),
            "paths.train": str(work / "train.jsonl"),
            "paths.dev": str(work / "dev.jsonl"),
            "training.seed": args.seed,
            # no evaluation, no checkpoint, no end by step count: the window ends the run
            "training.eval_frequency": 10 ** 9,
            "training.max_steps": 10 ** 9,
        }
        config = load_config(ROOT / config_file["program_config"], overrides,
                             interpolate=False)
        if repeat is not None:
            config = config.apply_overrides(repeat.overrides(config))

        # ---- the five wrappers --------------------------------------------
        words_fifo: Deque[Tuple[int, int, int]] = collections.deque()
        stats_handles: List[Any] = []
        trace_dir = (work / "trace") if args.trace else None
        spy = StepSpy(seconds=args.seconds, **warm, trace_dir=trace_dir,
                      trace_seconds=min(float(traffic["trace_seconds"]), args.seconds),
                      words_fifo=words_fifo, compile_count=compile_count,
                      loop_thread_compiles=lambda: on_loop_thread[0],
                      stats_handles=stats_handles)
        real = {"step": loop.make_train_step, "place": loop.place_batch,
                "stats": collate_pool.PipelineStats, "prefetch": prefetch.prefetch_iter,
                "corpus": loop.resolve_dot_name}
        corpora: Dict[str, Any] = {}  # the corpora the loop resolved, by dotted name

        def resolving(config_: Any, resolved: Any, dot_name: str) -> Any:
            corpora[dot_name] = real["corpus"](config_, resolved, dot_name)
            return corpora[dot_name]

        def counting_place(tree: Any, *a: Any, **k: Any) -> Any:
            mask = getattr(tree, "mask", None)
            if mask is not None:  # the tokens of one optimizer step, on the host
                lengths = mask.sum(-1).astype("int64")
                words_fifo.append((int(lengths.sum()), int(mask.size),
                                   int((lengths * lengths).sum())))
            return real["place"](tree, *a, **k)

        class HandledStats(real["stats"]):  # type: ignore[misc, valid-type]
            def __init__(self) -> None:
                super().__init__()
                stats_handles.append(self)

        class AnnotatedWait:
            def __init__(self, it: Any) -> None:
                self.it = it

            def __iter__(self) -> "AnnotatedWait":
                return self

            def __next__(self) -> Any:
                with jax.profiler.TraceAnnotation("bench:input_wait"):
                    return next(self.it)

            def close(self) -> None:
                close = getattr(self.it, "close", None)
                if close is not None:
                    close()

        loop.make_train_step = lambda *a, **k: spy.wrap(real["step"](*a, **k))
        loop.place_batch = counting_place
        loop.resolve_dot_name = resolving
        collate_pool.PipelineStats = HandledStats
        if args.trace:
            prefetch.prefetch_iter = lambda it, size=2: AnnotatedWait(
                real["prefetch"](it, size))
        try:
            nlp, result = loop.train(config, n_workers=chips, stdout_log=False)
        finally:
            loop.make_train_step = real["step"]
            loop.place_batch = real["place"]
            loop.resolve_dot_name = real["corpus"]
            collate_pool.PipelineStats = real["stats"]
            prefetch.prefetch_iter = real["prefetch"]

        # ---- after the window ------------------------------------------------
        window = spy.window()
        peaks = memory_peaks()
        runtime = {**runtime_report(nlp), **result.resolved}
        losses = [float(x) for x in jax.device_get(spy.losses)]
        residency = spy.residency or {"problems": ["no step ran"]}
        trace_summary = None
        if args.trace:
            import trace_reduce

            trace_summary = trace_reduce.reduce_dir(trace_dir, cuts_s=spy.cuts)
        step_mem = step_memory(spy) if args.trace else None

        # ---- correct -----------------------------------------------------------
        problems: List[str] = list(residency["problems"])
        if residency.get("params", {}).get("devices") != chips:
            problems.append(f"the step ran on {residency.get('params', {}).get('devices')} "
                            f"devices, the cell asks for {chips}")
        mismatched = [] if rehearsal else runtime_mismatches(
            runtime, config_file["expect_runtime"][str(chips)])
        problems.extend(mismatched)
        non_finite = sum(1 for x in losses if not math.isfinite(x))
        if non_finite:
            problems.append(f"{non_finite} non-finite losses")
        falls, loss_means = _loss_rule(losses, traffic["loss_rule"])
        if not falls:
            problems.append(f"loss did not fall: {loss_means}")
        counted = sum(s["words"] for s in spy.steps)
        if counted != result.words_seen:
            problems.append(f"harness counted {counted} words, the loop {result.words_seen}")
        if window["compiles"] > MAX_COMPILES_IN_WINDOW:
            problems.append(f"{window['compiles']} compilations inside the window")
        train_corpus = corpora.get(
            config.get("training", {}).get("train_corpus", "corpora.train"))
        by_pass = rates_by_pass(spy.steps, spy.edges["open"]["step"],
                                spy.edges["close"]["step"], corpus_words)
        corpus_problems, corpus_compared = corpus_rule(
            counted, corpus_words, on_repeat, train_corpus, by_pass)
        problems.extend(corpus_problems)
        import trunk_check

        trunk = trunk_check.check(
            nlp, nlp.params, cell["config"],
            generator.generate(trunk_check.N_SEQUENCES, args.seed + 7919, docs_spec),
            seed=args.seed)
        if not trunk["ok"]:
            problems.append(f"trunk differs from the reference: {trunk}")
        # every number `correct` compared, beside its limit
        compared = {
            "devices": [residency.get("params", {}).get("devices"), chips],
            "residency_problems": [len(residency["problems"]), 0],
            "runtime_mismatches": [len(mismatched), 0],
            "non_finite_losses": [non_finite, 0],
            "loss_last_third_below_first": [loss_means["last_third_mean"],
                                            loss_means["first_third_mean"]],
            "words_counted_equal_loop": [counted, result.words_seen],
            "compiles_in_window": [window["compiles"], MAX_COMPILES_IN_WINDOW],
            **corpus_compared,
            **trunk_check.compared(trunk, cell["config"]),
        }

    wps_chip = window["words"] / window["seconds"] / chips
    blocked, paced = spy.blocked_done_at, spy.paced_done_at()
    record = {
        "kind": "train", "chips": chips, "window": window, "runtime": runtime,
        "trace": trace_summary, "memory_peaks": peaks, "step_memory": step_mem,
        "train_wps_chip": wps_chip,
        "step_intervals_s": [b - a for a, b in zip(paced, paced[1:])],
        "config": config_file, "device_kind": jax.devices()[0].device_kind,
    }
    print(f"window {window}", flush=True)
    print(f"memory peaks {peaks}; step memory (compiler) {step_mem}; corpus words "
          f"{corpus_words}, taken {counted}; words/s between update calls by pass of the "
          f"corpus {by_pass}; blocked step intervals (s), the first {QUEUED_AHEAD} left out "
          f"of train_step_ms {[round(b - a, 3) for a, b in zip(blocked, blocked[1:])]}",
          flush=True)
    print(f"runtime {runtime}", flush=True)
    if trace_summary:
        print("trace " + str({k: trace_summary[k] for k in (
            "chips", "window_s", "busy_s", "busy_s_by_chip", "collective_s", "steps",
            "kernels_s", "longest_gap_s", "annotation_s")}), flush=True)
    print(f"losses first/last third {loss_means}; steps {len(losses)} "
          f"(warm-up {spy.edges['open']['step']}); trunk {trunk}; residency "
          f"{ {k: v for k, v in residency.items() if k != 'problems'} }", flush=True)
    if problems:
        print(f"NOT CORRECT: {problems}", flush=True)
    return {
        "correct": not problems,
        "compared": compared,
        "attempted": len(losses),
        "failed": non_finite,
        "end_to_end": {"train_wps_chip": wps_chip},
        "window_open_at": spy.t_run_open,
        "setup_marks": dict(marks, first_update_call=spy.t_first_call,
                            window_open=spy.t_run_open),
        "record": record,
        "memory_peaks": peaks,
    }
