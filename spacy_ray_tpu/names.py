"""Every name the program writes into a profiler trace, in one place.

A trace is read by name: the benchmark's readers, ``PERF.md`` section 3 and
whoever opens a ``train --profile`` trace in Perfetto look for exactly these
strings, so they are constants here and nowhere a literal. Changing one is a
change to what a metric reads.

Host side: one span call (``PipelineStats.timer`` / ``TraceBuffer.span``)
records a stage's seconds, a Chrome-trace span when telemetry is on, and a
``jax.profiler.TraceAnnotation`` named ``SPAN_PREFIX + key`` on the thread
that did the work. ``/`` separates a parent from its child. Every span is per
batch or per micro-batch, or once a run for set-up's stations; never per
document. The compile ledger adds a Chrome-trace span for each build event
of a program, after the event (JAX reports it when it is over).

Device side: ``jax.named_scope`` names (metadata of the compiled operations,
no operation is added), the ``name=`` of each pallas kernel, and the function
names of the jitted programs (an XLA module is ``jit_<name>``).
"""

from __future__ import annotations

# ---- host spans (keys of PipelineStats.seconds) ----------------------------
SPAN_PREFIX = "srt:"

READ = "read"  # corpus + batcher: one update's raw batches
COLLATE = "collate"  # collate_group: raw batches -> stacked host arrays
TRANSFER = "transfer"  # place_batch: device_put of tokens and targets
QUEUE_WAIT = "queue_wait"  # the loop waiting for its next group
# children of COLLATE; a cache hit has none (it is COLLATE's self time)
COLLATE_FEATURES = "collate/features"  # vocab.featurize + attr_keys/mask/vector_rows
COLLATE_TARGETS = "collate/targets"  # the loop over head_names()
COLLATE_STACK = "collate/stack"  # np.stack of the micro-batches (accumulate_gradient > 1)
# child of a head's span: a call that leaves the host (Component.make_targets'
# contract). No shipped head opens it: the collate path stays on the host
DEVICE_CALL = "device_call"
# the loop thread, per dispatch: from the return of next(groups) to the next
# call of it, evaluation and checkpoint excluded
LOOP_HOST = "loop_host"
LOOP_DISPATCH = "loop_host/dispatch"  # rng split, k > 1 stacking, the update call: the enqueue
# train()'s set-up, station by station, each once a run on the loop's thread
# (TrainResult.resolved["setup"] has their seconds without the prefix)
SETUP_PIPELINE = "setup/pipeline"  # Pipeline.from_config + initialize: corpus read, Examples, labels, weights
SETUP_PLACE = "setup/place"  # mesh, optimizer, placement, opt state, averages, shadow, make_train_step; resume excluded
SETUP_DEV = "setup/dev"  # the dev corpus materialised
SETUP_FIRST_BATCH = "setup/first_batch"  # the loop's wait for its first group
SETUP_FIRST_UPDATE = "setup/first_update"  # the first update call: trace, lower, compile or cache load, enqueue
SETUP_STATIONS = (SETUP_PIPELINE, SETUP_PLACE, SETUP_DEV, SETUP_FIRST_BATCH, SETUP_FIRST_UPDATE)
# the compile ledger (training/telemetry.py): one Chrome-trace span for each
# trace, lower and backend event of a program, on the thread that did it
COMPILE = "compile"


def collate_head(name: str) -> str:
    """The span of one head's ``make_targets``: ``collate/targets/<name>``."""
    return f"{COLLATE_TARGETS}/{name}"


def compile_span(program: str) -> str:
    """The span of one build event of ``program``: ``compile/<program>``."""
    return f"{COMPILE}/{program}"


# ---- device scopes ------------------------------------------------------------
SCOPE_EMBED = "embed"  # hash embeddings + their mix (CNN and transformer alike)
SCOPE_TRUNK = "trunk"  # the encoder: CNN window layers or the transformer stack
SCOPE_LOSS = "loss"  # the sum of the heads' losses and the auxiliary terms
SCOPE_UPDATE = "update"  # optimizer apply (fused or not), shadow refresh, grad norm
SCOPE_GRAD_ACCUM = "grad_accum"  # the scan over micro-batches
# inside SCOPE_TRUNK, the latent-attention / routed-expert trunk
# (models/latent_moe.py); "/" separates a part from its whole
SCOPE_ATTN = "attn"  # latent attention: projections, scores, output
SCOPE_ATTN_ROPE = "attn/rope"  # rotary positions on q_pe and the shared k_pe
SCOPE_DENSE_FFN = "dense_ffn"  # the leading dense layers' gated FFN
SCOPE_MOE_ROUTER = "moe/router"  # float32 scores, top-k, weights
SCOPE_MOE_DISPATCH = "moe/dispatch"  # sort the (word, choice) pairs by held expert, gather rows
SCOPE_MOE_EXPERTS = "moe/experts"  # the grouped products over the experts held
SCOPE_MOE_COMBINE = "moe/combine"  # un-sort, weight and sum each word's pairs
SCOPE_MOE_SHARED = "moe/shared"  # the shared experts, every word
# inside SCOPE_TRUNK, the trunk built from a layer pattern (models/hybrid_ssm.py):
# every operation of a layer lies under its kind, so a trace splits the step
# by kind of layer. The expert layers' parts are SCOPE_MOE_* above
SCOPE_MAMBA = "mamba"  # a Mamba-2 mixer: projections, convolution, gate, norm
SCOPE_MAMBA_SCAN = "mamba/scan"  # the chunked selective scan alone
SCOPE_ATTENTION = "attention"  # grouped-key attention: projections, scores, output
SCOPE_MOE = "moe"  # an expert layer's pre-norm and residual (its parts: SCOPE_MOE_*)
SCOPE_KDA = "kda"  # a delta-rule mixer: projections, convolutions, gates, norm
SCOPE_KDA_SCAN = "kda/scan"  # the chunked delta-rule recurrence alone (models/delta_attention.py)
SCOPE_GATED_ATTENTION = "gated_attention"  # grouped-key attention with an output gate


def head_scope(name: str) -> str:
    """One head's forward and loss: ``head/<component name>``."""
    return f"head/{name}"


# ---- device counters (keys of the step's ``metrics``) --------------------------------
# A layer may count on the device while the step is traced (``Context.
# add_metrics``). A key that starts with COUNTER_PREFIX is a SUM: the step adds
# it up over micro-batches, the loop over steps, and whichever model carries
# ``meta[SUMMARISE_COUNTERS]`` turns the run's totals into its block of
# ``TrainResult.resolved`` (docs/OBSERVABILITY.md). Neither the step nor the
# loop knows whose counters they are.
COUNTER_PREFIX = "count_"
SUMMARISE_COUNTERS = "summarise_counters"  # meta key: totals dict -> dict for ``resolved``
# the routed trunk's (models/latent_moe.py)
MOE_ASSIGNMENTS = "count_moe_assignments"  # real words x top_k x expert layers
MOE_ASSIGNMENTS_HELD = "count_moe_assignments_held"  # those that land on an expert held here
MOE_COMPUTED = "count_moe_computed"  # of those, the pairs whose expert's output came back
MOE_MAX_LOAD = "count_moe_max_load"  # rows of the fullest held expert, summed over layers
MOE_LAYER_CALLS = "count_moe_layer_calls"  # expert layers run (micro-batches x layers)
MOE_BOUNDED_CALLS = "count_moe_bounded_calls"  # of those, the calls whose live pairs fit the bound
MOE_BUFFER_ROWS = "count_moe_buffer_rows"  # rows of the buffer each call moved (C_q, C or N x top_k)
MOE_TIER_CALLS = "count_moe_tier_calls"  # of the bounded calls, those on the quarter tier C_q
# the state-space layers' scan (models/hybrid_ssm.py), from the batch's mask
SSM_CHUNKS = "count_ssm_chunks"  # (row, chunk) blocks the scan ran: rows x T / chunk x M layers
SSM_LIVE_CHUNKS = "count_ssm_live_chunks"  # of those, the blocks holding at least one real word
# the delta-rule layers' recurrence (the same trunk's K layers), likewise
KDA_CHUNKS = "count_kda_chunks"  # (row, chunk) blocks it ran: rows x T / chunk x K layers
KDA_LIVE_CHUNKS = "count_kda_live_chunks"  # of those, the blocks holding at least one real word

# ---- pallas kernels -------------------------------------------------------------
KERNEL_FLASH_FWD = "srt_flash_fwd"
KERNEL_FLASH_BWD = "srt_flash_bwd"
KERNEL_FUSED_ADAM = "srt_fused_adam"
KERNEL_HASH_EMBED = "srt_hash_embed"
KERNEL_INT8_MATMUL = "srt_int8_matmul"

# ---- jitted programs --------------------------------------------------------------
PROGRAM_TRAIN_STEP = "srt_train_step"
PROGRAM_TRAIN_STEP_MULTI = "srt_train_step_multi"  # steps_per_dispatch > 1
PROGRAM_EVAL_FORWARD = "srt_eval_forward"
PROGRAM_SHARD_APPLY = "srt_shard_apply"  # the trainer fleet's owner-shard apply
PROGRAMS = (PROGRAM_TRAIN_STEP, PROGRAM_TRAIN_STEP_MULTI, PROGRAM_EVAL_FORWARD, PROGRAM_SHARD_APPLY)
PROGRAM_OTHER = "other"  # the compile ledger's name for every program not above
