"""HTTP front-end for the serving engine: a stdlib ``ThreadingHTTPServer``
JSON API plus the graceful-drain orchestration.

Endpoints:

* ``POST /v1/parse`` — body ``{"texts": [...], "timeout_ms": optional}``;
  response ``{"docs": [...], "batch": {"occupancy", "B", "T"}}`` with
  docs in the same JSON schema the bulk ``parse`` CLI writes
  (``training/corpus._doc_to_json`` — one schema for offline and online
  output). Typed serving errors map to HTTP statuses: 429 queue full,
  503 draining, 504 deadline, 413 too large, 400 malformed.
* ``GET /healthz`` — 200 ``{"status": "ok"}`` while serving, 503
  ``{"status": "draining"}`` once shutdown began (a load balancer's
  take-me-out signal).
* ``GET /metrics`` — the :class:`~.engine.ServingTelemetry` snapshot
  (counters/gauges + latency p50/p95/p99); with telemetry disabled it
  reports ``{"telemetry": "disabled"}`` and touches nothing.

Graceful drain reuses the trainer's step-boundary-drain semantics
(``training/resilience.ShutdownCoordinator``): SIGTERM/SIGINT set a flag
(plus a callback that trips the admission gate immediately), the main
thread then 1) rejects new admissions, 2) waits for every queued and
in-flight batch to complete — the serving analog of "finish the step,
then checkpoint" — and 3) stops the listener and exits 0. A drain that
exceeds the timeout escalates to a hard stop with a nonzero exit, the
same honest-failure contract the trainer's escalation path keeps.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ..training.resilience import ShutdownCoordinator, log_event
from .batcher import (
    Draining,
    NotReady,
    REQUEST_ID_HEADER,
    ServingError,
    SwapFailed,
    UnknownModel,
    clean_request_id,
    etag_for,
    if_none_match_hit,
    mint_request_id,
)
from .engine import InferenceEngine, ServingTelemetry

__all__ = ["ServingHTTPServer", "Server"]

logger = logging.getLogger("spacy_ray_tpu.serving")

MAX_BODY_BYTES = 8 << 20  # an 8 MiB text payload is an abuse, not a parse


class ServingHTTPServer(ThreadingHTTPServer):
    """One handler thread per connection; handlers do host-side work
    (JSON, tokenization) and block in ``engine.submit_*`` — the device
    never sees more than the one dispatch thread."""

    daemon_threads = True

    def __init__(
        self,
        addr: Tuple[str, int],
        engine: InferenceEngine,
        telemetry: Optional[ServingTelemetry] = None,
    ) -> None:
        super().__init__(addr, _Handler)
        self.engine = engine
        self.tel = telemetry
        # multi-model serving (docs/SERVING.md "Multi-model fleet"),
        # all three None unless serve --model-manifest wired them:
        # registry resolves names, residency owns the per-model engine
        # hot set, admission enforces tenant quotas + class mapping.
        # With no manifest the request path below never touches them —
        # the legacy single-model contract, bit-identical.
        self.registry = None
        self.residency = None
        self.admission = None
        # optional diagnosis layer (docs/OBSERVABILITY.md "Alerting &
        # incidents"): the in-process AlertEngine whose states
        # /admin/alerts and the /metrics alerts block serve. None unless
        # telemetry is on AND the CLI wired one (zero-calls contract).
        self.alerts = None
        self.draining = False
        # checkpoint directories /admin/swap may load from. EMPTY means
        # the admin swap surface is OFF (403): accepting an arbitrary
        # client-supplied path would let anyone who can reach the port
        # point the server at weights they control. Configured via
        # serve --watch / --swap-dir (Server wires it through).
        self.allowed_swap_dirs: list = []


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # loopback is immune, but over a real link Nagle + delayed ACK can
    # add ~40ms between the header write and the body write
    disable_nagle_algorithm = True
    server: ServingHTTPServer

    # stdlib default logs every request to stderr; route to the logger so
    # production stderr stays signal, not access-log noise
    def log_message(self, fmt: str, *args: Any) -> None:
        logger.debug("%s " + fmt, self.address_string(), *args)

    def _reply(
        self,
        status: int,
        payload: Dict[str, Any],
        request_id: Optional[str] = None,
        etag: Optional[str] = None,
    ) -> None:
        body = json.dumps(payload).encode("utf8")
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        if request_id is not None:
            # the trace identity rides the response on EVERY outcome —
            # a 504 is exactly the response whose id gets looked up
            self.send_header(REQUEST_ID_HEADER, request_id)
        if etag is not None:
            self.send_header("ETag", etag)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_not_modified(
        self, etag: str, request_id: Optional[str] = None
    ) -> None:
        """Body-less 304: the client's cached body is still exact. A 304
        carries no body by definition, but Content-Length: 0 is stamped
        anyway so naive keep-alive clients can't desync the stream."""
        self.send_response(304)
        self.send_header("ETag", etag)
        if request_id is not None:
            self.send_header(REQUEST_ID_HEADER, request_id)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def _reply_text(self, status: int, text: str, content_type: str) -> None:
        body = text.encode("utf8")
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_error(
        self, err: ServingError, request_id: Optional[str] = None
    ) -> None:
        self._reply(
            err.http_status, {"error": err.code, "message": str(err)},
            request_id,
        )

    # -- GET ------------------------------------------------------------
    def do_GET(self) -> None:  # noqa: N802 (stdlib handler naming)
        parsed = urlparse(self.path)
        self.path = parsed.path  # route on the bare path below
        if self.path == "/metrics":
            self._get_metrics(parse_qs(parsed.query))
            return
        if self.path == "/trace":
            self._get_trace()
            return
        if self.path == "/admin/exemplars":
            self._get_exemplars()
            return
        if self.path == "/admin/alerts":
            # read-only (like /admin/exemplars): alert STATE is
            # diagnosis, not control, so it is not swap-gated
            if self.server.alerts is None:
                self._reply(200, {"alerts": "disabled"})
            else:
                from ..training.telemetry import sanitize_json

                self._reply(
                    200,
                    sanitize_json({"alerts": self.server.alerts.states()}),
                )
            return
        if self.path == "/healthz":
            if self.server.draining:
                self._reply(503, {"status": "draining"})
            elif not self.server.engine.ready:
                # readiness gate: the listener comes up BEFORE the bucket
                # warmup sweep (so a router can probe), but traffic routed
                # here now would hit a live mid-warmup compile — 503 until
                # the sweep completes and the dispatch thread is running
                self._reply(
                    503,
                    {
                        "status": "warming",
                        "warmed_buckets": len(self.server.engine.warmed),
                    },
                )
            else:
                payload = {
                    "status": "ok",
                    "pipeline": list(self.server.engine.nlp.pipe_names),
                    "warmed_buckets": len(self.server.engine.warmed),
                    "max_batch_docs": self.server.engine.max_batch_docs,
                    "max_doc_len": self.server.engine.max_doc_len,
                    # the engine's honest labels: admission discipline
                    # and the precision the device actually runs —
                    # operators read them here
                    "batching": self.server.engine.batching,
                    "precision": self.server.engine.overlay.resolved,
                    "precision_label": self.server.engine.overlay.label,
                    # live-serving identity: which checkpoint
                    # generation the dispatch thread is serving (null
                    # = the model as loaded from disk) and how many
                    # flips got it there — the router's canary split
                    # and the fleet's generation-tagged metrics key
                    # on exactly this pair
                    "generation": self.server.engine.serving_generation,
                    "swap_count": self.server.engine.swap_count,
                }
                if self.server.residency is not None:
                    # multi-model placement advertisement: the router's
                    # probe loop learns which models live here (and each
                    # one's generation) from this block — placement
                    # discovery costs zero extra requests
                    payload["resident_models"] = (
                        self.server.residency.resident_info()
                    )
                    payload["residency"] = self.server.residency.stats()
                    if self.server.registry is not None:
                        payload["default_model"] = (
                            self.server.registry.default_model
                        )
                if self.server.tel is not None:
                    # monotonic-clock anchor for the cross-process trace
                    # collector (docs/OBSERVABILITY.md "Distributed
                    # tracing"): maps this replica's trace timestamps
                    # onto the shared wall-clock timeline
                    payload["anchor"] = self.server.tel.trace.anchor()
                self._reply(200, payload)
        else:
            self._reply(404, {"error": "not_found", "message": self.path})

    def _get_metrics(self, query: Dict[str, Any]) -> None:
        tel = self.server.tel
        engine = self.server.engine
        fmt = (query.get("format") or [""])[0]
        if tel is None:
            if fmt == "prometheus":
                from ..training.prometheus import EXPOSITION_CONTENT_TYPE

                # comment-only exposition: a scraper sees an honest
                # empty scrape, and the disabled path still constructs
                # zero telemetry objects (test-enforced)
                self._reply_text(
                    200, "# srt telemetry disabled\n",
                    EXPOSITION_CONTENT_TYPE,
                )
                return
            self._reply(
                200,
                {
                    "telemetry": "disabled",
                    "generation": engine.serving_generation,
                    "swap_count": engine.swap_count,
                },
            )
            return
        from ..training.telemetry import sanitize_json

        snap = tel.snapshot()
        # stamp the snapshot with the generation it describes:
        # merge_serving_snapshots groups per-replica snapshots by
        # this key, which is what makes fleet slo_window
        # percentiles splittable by generation
        snap["generation"] = engine.serving_generation
        snap["swap_count"] = engine.swap_count
        residency = self.server.residency
        if residency is not None:
            # per-model sub-snapshots (each resident engine carries its
            # own telemetry): merge_serving_snapshots groups these into
            # the fleet's by_model block, and the Prometheus branch
            # below emits them as model-labeled series
            models: Dict[str, Any] = {}
            for name, eng in sorted(residency.engines().items()):
                if eng.tel is None:
                    continue
                msnap = eng.tel.snapshot()
                msnap["model"] = name
                msnap["generation"] = eng.serving_generation
                msnap["swap_count"] = eng.swap_count
                models[name] = msnap
            if models:
                snap["models"] = models
            snap["residency"] = residency.stats()
        if self.server.alerts is not None:
            # the compact alert block `telemetry top` renders; full
            # per-rule states live on /admin/alerts
            snap["alerts"] = self.server.alerts.summary()
        if fmt == "prometheus":
            from ..training.prometheus import (
                EXPOSITION_CONTENT_TYPE,
                PromFamilies,
            )

            fam = PromFamilies()
            fam.add_snapshot(snap, prefix="srt_serving")
            # add_snapshot only walks counters/gauges/histograms — the
            # snapshot's "process" block becomes the shared (unprefixed)
            # srt_process_* family here, same names on every surface
            from ..training.hoststats import add_process_family

            add_process_family(fam, snap.get("process"))
            # live-serving identity as explicit gauges (counters span
            # generations, so the generation is NOT a label on them —
            # it is its own series)
            if engine.serving_generation is not None:
                fam.add(
                    "srt_serving_generation_id", "gauge",
                    engine.serving_generation,
                )
            fam.add("srt_serving_swap_count", "gauge", engine.swap_count)
            win = snap.get("slo_window")
            if isinstance(win, dict):
                for q in ("p50", "p95", "p99"):
                    fam.add(
                        "srt_serving_request_latency_window_seconds",
                        "gauge",
                        win.get(f"request_latency_{q}"),
                        {
                            "quantile": q.replace("p", "0."),
                            "window_s": int(win.get("window_s") or 0),
                        },
                    )
            if isinstance(snap.get("models"), dict):
                # model-labeled twins of the srt_serving_* families: one
                # series set per resident model, so per-model p99 is
                # scrapeable without parsing the JSON surface
                for name, msnap in sorted(snap["models"].items()):
                    fam.add_snapshot(
                        msnap, prefix="srt_serving",
                        labels={"model": name},
                    )
                    mwin = msnap.get("slo_window")
                    if isinstance(mwin, dict):
                        for q in ("p50", "p95", "p99"):
                            fam.add(
                                "srt_serving_request_latency_window_seconds",
                                "gauge",
                                mwin.get(f"request_latency_{q}"),
                                {
                                    "model": name,
                                    "quantile": q.replace("p", "0."),
                                    "window_s": int(
                                        mwin.get("window_s") or 0
                                    ),
                                },
                            )
            if self.server.alerts is not None:
                # srt_alert_state{alert,severity} 0/1/2 + fired totals —
                # the scraper-side view of the in-process state machine
                self.server.alerts.add_prometheus(fam)
            self._reply_text(200, fam.render(), EXPOSITION_CONTENT_TYPE)
            return
        self._reply(200, sanitize_json(snap))

    def _get_trace(self) -> None:
        tel = self.server.tel
        if tel is None:
            self._reply(200, {"trace": "disabled"})
            return
        from ..training.telemetry import sanitize_json

        payload = tel.trace.payload()
        payload["anchor"] = tel.trace.anchor()
        payload["role"] = "replica"
        self._reply(200, sanitize_json(payload))

    def _get_exemplars(self) -> None:
        tel = self.server.tel
        if tel is None:
            self._reply(200, {"exemplars": "disabled"})
            return
        from ..training.telemetry import sanitize_json

        self._reply(200, sanitize_json(tel.exemplars()))

    # -- POST -----------------------------------------------------------
    def do_POST(self) -> None:  # noqa: N802
        try:
            length = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            length = -1
        if length < 0 or length > MAX_BODY_BYTES:
            # body not consumed: the connection must close, or its bytes
            # would be parsed as the next keep-alive request
            self.close_connection = True
            self._reply(
                400,
                {
                    "error": "bad_request",
                    "message": f"Content-Length must be 0..{MAX_BODY_BYTES}",
                },
            )
            return
        body = self.rfile.read(length)  # consume BEFORE any early reply:
        # an unread body desyncs every later request on this connection
        if self.path in ("/admin/swap", "/admin/rollback"):
            self._handle_admin(body)
            return
        if self.path == "/admin/models/load":
            self._handle_model_load(body)
            return
        if self.path != "/v1/parse" and not (
            self.server.registry is not None
            and self.path.startswith("/v1/models/")
        ):
            self._reply(404, {"error": "not_found", "message": self.path})
            return
        # trace identity: honor a client/router-supplied id, mint one
        # otherwise — every reply below (success AND typed errors)
        # carries it back in the response header
        request_id = clean_request_id(
            self.headers.get(REQUEST_ID_HEADER)
        ) or mint_request_id()
        if self.server.draining:
            self._reply_error(Draining("server is draining"), request_id)
            return
        if not self.server.engine.ready:
            self._reply_error(
                NotReady("bucket warmup in progress; not admitting yet"),
                request_id,
            )
            return
        # multi-model resolution (no manifest → registry is None and
        # this whole block is skipped; the legacy path is untouched):
        # path wins over the X-SRT-Model header wins over the default —
        # an unknown name is the typed 404, never a silent fallback
        model_name: Optional[str] = None
        if self.server.registry is not None:
            try:
                model_name, _ = self.server.registry.resolve_model(
                    self.path, self.headers
                )
            except UnknownModel as e:
                if self.server.tel is not None:
                    self.server.tel.request_rejected(e, request_id)
                self._reply_error(e, request_id)
                return
        try:
            payload = json.loads(body or b"{}")
        except ValueError:
            self._reply(
                400, {"error": "bad_request", "message": "body is not JSON"},
                request_id,
            )
            return
        texts = payload.get("texts") if isinstance(payload, dict) else None
        if (
            not isinstance(texts, list)
            or not texts
            or not all(isinstance(t, str) for t in texts)
        ):
            self._reply(
                400,
                {
                    "error": "bad_request",
                    "message": 'body must be {"texts": [<non-empty list of '
                    'strings>], "timeout_ms": optional int}',
                },
                request_id,
            )
            return
        timeout_s: Optional[float] = None
        if isinstance(payload.get("timeout_ms"), (int, float)):
            timeout_s = max(float(payload["timeout_ms"]) / 1000.0, 1e-3)
        from ..training.corpus import _doc_to_json

        # tenant admission (quota BEFORE the queue, metered in docs) +
        # SLO-class resolution for the batcher's weighted fair queue
        klass = "default"
        if self.server.admission is not None:
            from .multimodel.registry import TENANT_HEADER

            try:
                klass = self.server.admission.admit(
                    self.headers.get(TENANT_HEADER), n_docs=len(texts)
                )
            except ServingError as e:
                if self.server.tel is not None:
                    self.server.tel.request_rejected(e, request_id)
                self._reply_error(e, request_id)
                return
        # resolve the engine: the residency hot set for a named model
        # (loading it on first use, LRU-evicting past capacity), the
        # server's single engine otherwise
        engine = self.server.engine
        if self.server.residency is not None and model_name is not None:
            try:
                engine = self.server.residency.engine_for(model_name)
            except ServingError as e:
                if self.server.tel is not None:
                    self.server.tel.request_rejected(e, request_id)
                self._reply_error(e, request_id)
                return
        # conditional response (docs/SERVING.md "Data plane"): the ETag
        # is a pure function of (texts, model, generation), so it is
        # known HERE, before any inference — a matching If-None-Match
        # skips the queue, the device, and serialization entirely. The
        # check validates against the CURRENT generation: post-swap, the
        # tag differs and the request falls through to a full parse.
        admission_etag = etag_for(
            texts, model_name or "", engine.serving_generation
        )
        if if_none_match_hit(
            self.headers.get("If-None-Match"), admission_etag
        ):
            if engine.tel is not None:
                engine.tel.conditional_hit()
            self._reply_not_modified(admission_etag, request_id)
            return
        try:
            req = engine.submit_texts(
                texts, timeout_s=timeout_s, request_id=request_id,
                klass=klass,
            )
        except ServingError as e:
            self._reply_error(e, request_id)
            return
        t_ser = time.perf_counter()
        docs_json = [_doc_to_json(d) for d in req.docs]
        serialize_s = time.perf_counter() - t_ser
        # exemplars ride the tel of the engine that served the request,
        # so a per-model engine's p99 threshold judges its own traffic
        tel = engine.tel
        if tel is not None and req.latency_s is not None:
            # slow-request exemplar: the per-stage breakdown that turns
            # "p99 regressed" into "this request waited HERE"
            tel.consider_exemplar(
                request_id=req.request_id,
                latency_s=req.latency_s,
                stages={
                    "queue_wait": (
                        req.started_at - req.enqueued_at
                        if req.started_at is not None else None
                    ),
                    "dispatch_wait": (
                        req.dispatched_at - req.enqueued_at
                        if req.dispatched_at is not None else None
                    ),
                    "device": req.device_s,
                    "serialize": serialize_s,
                },
                n_docs=len(req.docs),
                B=req.batch_info.get("B"),
                T=req.batch_info.get("T"),
                generation=req.batch_info.get("generation"),
            )
        # the stamped ETag uses the generation the batch ACTUALLY ran on
        # (a swap can land between admission and dispatch) — the tag must
        # identify the body it rides, not the body admission expected
        self._reply(
            200,
            {"docs": docs_json, "batch": req.batch_info},
            request_id,
            etag=etag_for(
                texts, model_name or "", req.batch_info.get("generation")
            ),
        )


    def _handle_model_load(self, body: bytes) -> None:
        """Placement control (docs/SERVING.md "Multi-model fleet"):
        ``{"model": <name>}`` pulls a MANIFEST model into this replica's
        hot set (load + warmup on this handler thread; resident traffic
        keeps dispatching). Unlike /admin/swap this needs no directory
        allowlist — the loadable set is exactly the operator-provided
        manifest, never a client-supplied path."""
        if self.server.residency is None:
            self._reply(
                403,
                {
                    "error": "forbidden",
                    "message": "multi-model serving is not configured "
                    "(serve --model-manifest)",
                },
            )
            return
        if self.server.draining:
            self._reply_error(Draining("server is draining; no loads"))
            return
        try:
            payload = json.loads(body or b"{}")
        except ValueError:
            self._reply(
                400, {"error": "bad_request", "message": "body is not JSON"}
            )
            return
        name = payload.get("model") if isinstance(payload, dict) else None
        if not isinstance(name, str) or not name:
            self._reply(
                400,
                {"error": "bad_request", "message": 'body must be {"model": '
                 "<manifest model name>}"},
            )
            return
        try:
            self.server.residency.engine_for(name)
        except ServingError as e:
            self._reply_error(e)
            return
        self._reply(
            200,
            {
                "model": name,
                "resident": self.server.residency.resident(),
                "residency": self.server.residency.stats(),
            },
        )

    # -- admin: live hot-swap control (docs/SERVING.md "Continuous
    # learning"). These run on the LISTENER, not a side channel, so the
    # fleet controller reaches replicas over the address it already
    # knows; staging runs on this handler thread while the dispatch
    # thread keeps serving, and the flip itself is an O(pointers)
    # exchange at a dispatch boundary.
    def _handle_admin(self, body: bytes) -> None:
        engine = self.server.engine
        if self.server.draining:
            self._reply_error(Draining("server is draining; no swaps"))
            return
        # optional per-model target (multi-model serving): swap/rollback
        # the named RESIDENT engine instead of the default — hot-swap
        # works per model, and swapping a model that is not resident is
        # a typed refusal, not a surprise cold load
        model = None
        if body:
            try:
                parsed = json.loads(body)
                if isinstance(parsed, dict):
                    model = parsed.get("model")
            except ValueError:
                pass  # the swap path below replies 400 for non-JSON
        if isinstance(model, str) and model:
            if self.server.residency is None:
                self._reply(
                    403,
                    {
                        "error": "forbidden",
                        "message": "per-model swap needs multi-model "
                        "serving (serve --model-manifest)",
                    },
                )
                return
            try:
                engine = self.server.residency.engine_for(model, load=False)
            except ServingError as e:
                self._reply_error(e)
                return
        if not self.server.allowed_swap_dirs:
            # the WHOLE admin surface keys off the swap-dir config —
            # rollback included: an ungated rollback on an open port
            # would let any client revert a fleet to stale weights (and
            # toggle generations at will, since rollback is its own
            # inverse)
            self._reply(
                403,
                {
                    "error": "forbidden",
                    "message": "admin swap/rollback is disabled: no swap "
                    "directory configured (serve --watch/--swap-dir)",
                },
            )
            return
        if self.path == "/admin/rollback":
            try:
                result = engine.rollback()
            except ServingError as e:
                self._reply_error(e)
                return
            self._reply(200, {k: v for k, v in result.items()})
            return
        # /admin/swap {"dir": <ckpt dir>, "generation": optional stamp}
        if not engine.ready:
            self._reply_error(
                NotReady("bucket warmup in progress; not swapping yet")
            )
            return
        try:
            payload = json.loads(body or b"{}")
        except ValueError:
            self._reply(
                400, {"error": "bad_request", "message": "body is not JSON"}
            )
            return
        ckpt_dir = payload.get("dir") if isinstance(payload, dict) else None
        if not isinstance(ckpt_dir, str) or not ckpt_dir:
            self._reply(
                400,
                {
                    "error": "bad_request",
                    "message": 'body must be {"dir": <checkpoint dir>, '
                    '"generation": optional int}',
                },
            )
            return
        from pathlib import Path

        allowed = self.server.allowed_swap_dirs
        try:
            requested = Path(ckpt_dir).resolve()
        except OSError:
            requested = None
        if requested is None or not any(
            requested == Path(d).resolve() for d in allowed
        ):
            # not an allowlisted checkpoint directory: loading weights
            # from an arbitrary client-supplied path is how a reachable
            # port becomes an arbitrary-model (or worse) endpoint
            self._reply(
                403,
                {
                    "error": "forbidden",
                    "message": (
                        "dir is not an allowed swap directory (configure "
                        "via serve --watch/--swap-dir)"
                        if allowed
                        else "admin swap is disabled: no swap directory "
                        "configured (serve --watch/--swap-dir)"
                    ),
                },
            )
            return
        from ..training.checkpoint import CheckpointCorrupt, Checkpoints

        try:
            ckpts = Checkpoints(ckpt_dir)
            generation = payload.get("generation")
            if generation is None:
                generation = ckpts.latest_intact_generation(
                    params_only=True
                )
                if generation is None:
                    raise SwapFailed(
                        f"no intact checkpoint generation in {ckpt_dir}"
                    )
            # params-only: the swap discards opt_state, so the admin
            # route neither hashes nor unpickles it (no pickle.load on
            # a network-reachable path, and half the I/O per swap)
            state = ckpts.load_generation_params(int(generation))
            result = engine.swap_params(
                state["params"], int(generation), source="admin"
            )
        except CheckpointCorrupt as e:
            # a torn generation is a refused swap, not a crash — the
            # caller (controller/operator) picks another generation
            self._reply_error(SwapFailed(str(e)))
            return
        except ServingError as e:
            self._reply_error(e)
            return
        self._reply(200, {k: v for k, v in result.items()})


class Server:
    """Lifecycle orchestration: start the listener, wait for a shutdown
    request (signal or programmatic), drain gracefully, exit.

    ``run()`` is the CLI path (installs SIGTERM/SIGINT handlers);
    ``start()`` + ``request_shutdown()`` + ``wait()`` is the in-process
    test path — same drain code either way.
    """

    def __init__(
        self,
        engine: InferenceEngine,
        host: str = "127.0.0.1",
        port: int = 8080,
        *,
        telemetry: Optional[ServingTelemetry] = None,
        drain_timeout_s: float = 30.0,
        watcher: Optional[Any] = None,
        swap_dirs: Optional[list] = None,
        alerts: Optional[Any] = None,
        recorder: Optional[Any] = None,
        observe_interval_s: float = 2.0,
        registry: Optional[Any] = None,
        residency: Optional[Any] = None,
        admission: Optional[Any] = None,
    ) -> None:
        self.engine = engine
        self.tel = telemetry
        # multi-model serving (all None without --model-manifest)
        self.registry = registry
        self.residency = residency
        self.admission = admission
        # the diagnosis layer (docs/OBSERVABILITY.md "Alerting &
        # incidents"): an AlertEngine and/or FlightRecorder, both fed by
        # one observer ticker off the hot path. Only ever constructed by
        # the CLI when telemetry is on — with telemetry off there is no
        # ticker, zero rule evaluations, zero ring writes (guard-tested).
        self.alerts = alerts
        self.recorder = recorder
        self.observe_interval_s = float(observe_interval_s)
        self._observer: Optional[threading.Thread] = None
        self._observer_stop = threading.Event()
        self.drain_timeout_s = float(drain_timeout_s)
        # optional live-serving CheckpointWatcher (serve --watch): started
        # only after the engine is ready (swapping mid-warmup would race
        # the sweep), stopped before the drain (a swap mid-drain serves
        # nobody)
        self.watcher = watcher
        self.httpd = ServingHTTPServer((host, port), engine, telemetry)
        self.httpd.alerts = alerts
        self.httpd.registry = registry
        self.httpd.residency = residency
        self.httpd.admission = admission
        # /admin/swap allowlist: the watched dir plus any explicit
        # --swap-dir entries; empty = admin swaps 403 (see
        # ServingHTTPServer.allowed_swap_dirs)
        dirs = [str(d) for d in (swap_dirs or [])]
        if watcher is not None and str(watcher.ckpt_dir) not in dirs:
            dirs.append(str(watcher.ckpt_dir))
        self.httpd.allowed_swap_dirs = dirs
        self._stop = threading.Event()
        self._serve_thread: Optional[threading.Thread] = None

    @property
    def address(self) -> Tuple[str, int]:
        host, port = self.httpd.server_address[:2]
        return str(host), int(port)

    def start(self) -> Tuple[str, int]:
        self._serve_thread = threading.Thread(
            target=self.httpd.serve_forever,
            kwargs={"poll_interval": 0.1},
            name="serve-http",
            daemon=True,
        )
        self._serve_thread.start()
        if self.tel is not None and (
            self.alerts is not None or self.recorder is not None
        ):
            self._observer = threading.Thread(
                target=self._observe_loop,
                name="serve-observer",
                daemon=True,
            )
            self._observer.start()
        return self.address

    def _observe_loop(self) -> None:
        """The diagnosis ticker: snapshot the telemetry registry every
        ``observe_interval_s``, feed the flight-recorder ring (which
        also persists the black box, the SIGKILL-survivable copy), and
        evaluate the alert rules. First tick runs immediately so a
        replica that dies young still leaves a black box."""
        while True:
            try:
                snap = self.tel.snapshot()
                snap["generation"] = self.engine.serving_generation
                snap["swap_count"] = self.engine.swap_count
                if self.recorder is not None:
                    self.recorder.record(snap)
                if self.alerts is not None:
                    self.alerts.evaluate(snap)
            except Exception:
                logger.exception("observer tick failed")
            if self._observer_stop.wait(self.observe_interval_s):
                return

    def request_shutdown(self, signum: Optional[int] = None) -> None:
        """Safe from a signal handler: flag writes and an Event set only
        — no locks. The batcher's own drain gate (a Condition under a
        non-reentrant lock) is tripped by ``wait`` on the waiting
        thread; taking it HERE could self-deadlock if a second signal
        lands while that thread holds the lock (e.g. k8s re-signalling
        mid-drain). The HTTP admission gate (``draining``) still flips
        instantly, so new requests 503 from the first signal on."""
        self.httpd.draining = True
        self._stop.set()

    def wait(self) -> int:
        """Block until shutdown is requested, then drain. Returns the
        process exit code: 0 for a clean drain, 1 when in-flight work
        had to be abandoned at the timeout."""
        self._stop.wait()
        self.httpd.draining = True
        self._observer_stop.set()
        if self._observer is not None:
            self._observer.join(timeout=5.0)
            self._observer = None
        if self.watcher is not None:
            self.watcher.stop()
        self.engine.batcher.begin_drain()
        if self.residency is not None:
            self.residency.begin_drain()
        log_event(
            "serve-drain",
            "shutdown requested — draining "
            f"{self.engine.batcher.queue_depth()} queued doc(s)",
            level=logging.INFO,
        )
        clean = self.engine.drain(self.drain_timeout_s)
        if not clean:
            log_event(
                "serve-drain-timeout",
                f"drain exceeded {self.drain_timeout_s:.1f}s — hard stop",
            )
            self.engine.stop()
        if self.residency is not None:
            # every resident engine gets the same graceful drain the
            # default engine got (the default is in the hot set too —
            # its second drain is an idempotent no-op)
            if not self.residency.stop_all(self.drain_timeout_s):
                clean = False
        self.httpd.shutdown()
        self.httpd.server_close()
        return 0 if clean else 1

    def run(
        self, *, banner: bool = True, warmup_engine: Optional[bool] = None
    ) -> int:
        coordinator = ShutdownCoordinator()
        coordinator.add_callback(self.request_shutdown)
        coordinator.install()
        try:
            host, port = self.start()
            if banner:
                # exact, parseable line: the drain subprocess test, the
                # fleet replica supervisor (and any operator script) read
                # the bound port from it
                print(f"serving on http://{host}:{port}", flush=True)
            if warmup_engine is not None:
                # listener-first startup: the port is announced and
                # /healthz answers "warming" (503) while the bucket sweep
                # compiles; a SIGTERM landing mid-warmup is honored right
                # after (wait() returns immediately on the set flag)
                self.engine.start(warmup=warmup_engine)
                if banner and self.engine.warmed:
                    print(
                        f"warmed {len(self.engine.warmed)} (B, T) bucket "
                        "programs; ready", flush=True,
                    )
            if self.watcher is not None and not self._stop.is_set():
                self.watcher.start()
                if banner:
                    print(
                        f"watching {self.watcher.ckpt_dir} for new "
                        "checkpoint generations "
                        f"(every {self.watcher.interval_s:.1f}s)",
                        flush=True,
                    )
            return self.wait()
        finally:
            coordinator.restore()
