"""Model registry + request resolution for multi-tenant, multi-model
serving (docs/SERVING.md "Multi-model fleet").

One manifest file declares everything the fleet needs to serve many
pipelines to many tenants: the model catalog (name → pipeline dir), the
SLO classes (weight for fair queuing + a per-class window-p99 target),
and the tenants (class membership + token-bucket quota). The router and
every replica load the SAME manifest, so "which model is this request
for" and "which class does this tenant ride in" resolve identically at
the edge and at the device.

Resolution contract (property-tested):

* path wins: ``/v1/models/<name>/parse`` names the model explicitly and
  overrides any header;
* the ``X-SRT-Model`` header selects a model on the legacy ``/v1/parse``
  path;
* neither present → the manifest's ``default_model`` — which is what
  preserves the legacy single-model contract bit-identically (a client
  that never heard of models sees no difference);
* an unknown name → typed 404 ``unknown_model`` (batcher.UnknownModel),
  never a silent fallback: serving the default under the wrong name
  would poison the per-model cache and per-model SLO accounting.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Mapping, Optional, Tuple

from ..batcher import UnknownModel

__all__ = [
    "MODEL_HEADER",
    "TENANT_HEADER",
    "MODEL_PATH_RE",
    "ClassSpec",
    "TenantSpec",
    "ModelSpec",
    "ModelRegistry",
]

# request headers (the path form wins over MODEL_HEADER; TENANT_HEADER
# absent → the anonymous default tenant: default class, no quota)
MODEL_HEADER = "X-SRT-Model"
TENANT_HEADER = "X-SRT-Tenant"

# /v1/models/<name>/parse — name restricted to sane token characters so
# a hostile path segment can never smuggle separators into cache keys,
# Prometheus labels, or forwarded URLs
MODEL_PATH_RE = re.compile(r"\A/v1/models/([A-Za-z0-9._-]{1,64})/parse\Z")

_NAME_RE = re.compile(r"\A[A-Za-z0-9._-]{1,64}\Z")

DEFAULT_CLASS = "default"


@dataclass(frozen=True)
class ClassSpec:
    """One SLO class: ``weight`` is the fair-queuing share (docs
    dispatched under saturation converge to the weight ratio), and
    ``p99_target_ms`` is the window-p99 bound the placement policy
    judges this class against."""

    name: str
    weight: float = 1.0
    p99_target_ms: Optional[float] = None


@dataclass(frozen=True)
class TenantSpec:
    """One tenant: class membership plus an optional token-bucket quota
    in DOCS per second (docs are the serving cost unit everywhere —
    queue bounds, batch occupancy — so quotas meter the same thing).
    ``quota_docs_per_s`` None = unlimited (the anonymous default)."""

    name: str
    klass: str = DEFAULT_CLASS
    quota_docs_per_s: Optional[float] = None
    quota_burst: Optional[float] = None


@dataclass(frozen=True)
class ModelSpec:
    """One servable pipeline: ``path`` is a spaCy pipeline directory
    exactly like the ``serve`` command's positional argument."""

    name: str
    path: str


class ModelRegistry:
    """The manifest, parsed and validated once; immutable thereafter.

    Construction performs NO I/O beyond reading the manifest file and
    NO telemetry: the zero-telemetry-calls guard extends to this whole
    subsystem (a registry is pure lookup tables).
    """

    def __init__(
        self,
        models: Dict[str, ModelSpec],
        default_model: str,
        classes: Optional[Dict[str, ClassSpec]] = None,
        tenants: Optional[Dict[str, TenantSpec]] = None,
    ) -> None:
        if not models:
            raise ValueError("manifest declares no models")
        for name in models:
            if not _NAME_RE.match(name):
                raise ValueError(f"invalid model name {name!r}")
        if default_model not in models:
            raise ValueError(
                f"default_model {default_model!r} is not in the manifest's "
                f"models ({sorted(models)})"
            )
        self.models: Dict[str, ModelSpec] = dict(models)
        self.default_model = default_model
        self.classes: Dict[str, ClassSpec] = dict(classes or {})
        # the default class always exists (weight 1.0): the anonymous
        # tenant and any tenant without a class ride in it
        self.classes.setdefault(DEFAULT_CLASS, ClassSpec(DEFAULT_CLASS))
        for cname, spec in self.classes.items():
            if not (spec.weight > 0):
                raise ValueError(
                    f"class {cname!r} weight must be > 0, got {spec.weight!r}"
                )
        self.tenants: Dict[str, TenantSpec] = dict(tenants or {})
        for tname, tspec in self.tenants.items():
            if tspec.klass not in self.classes:
                raise ValueError(
                    f"tenant {tname!r} names unknown class {tspec.klass!r}"
                )
            if (
                tspec.quota_docs_per_s is not None
                and not (tspec.quota_docs_per_s > 0)
            ):
                raise ValueError(
                    f"tenant {tname!r} quota_docs_per_s must be > 0"
                )

    # -- manifest I/O ----------------------------------------------------
    @classmethod
    def from_manifest(cls, path: str) -> "ModelRegistry":
        """Parse a JSON manifest::

            {
              "default_model": "tagger",
              "models": {"tagger": {"path": "models/tagger"},
                         "ner":    {"path": "models/ner"}},
              "classes": {"gold":  {"weight": 4, "p99_target_ms": 500},
                          "batch": {"weight": 1, "p99_target_ms": 5000}},
              "tenants": {"acme":  {"class": "gold",
                                    "quota_docs_per_s": 200,
                                    "quota_burst": 400}}
            }

        Relative model paths resolve against the manifest's directory,
        so a manifest travels with its models.
        """
        p = Path(path)
        raw = json.loads(p.read_text(encoding="utf-8"))
        if not isinstance(raw, dict):
            raise ValueError(f"manifest {path} is not a JSON object")
        models_raw = raw.get("models")
        if not isinstance(models_raw, dict) or not models_raw:
            raise ValueError(f"manifest {path} has no 'models' table")
        models: Dict[str, ModelSpec] = {}
        for name, m in models_raw.items():
            if not isinstance(m, dict) or "path" not in m:
                raise ValueError(
                    f"manifest model {name!r} needs a 'path' entry"
                )
            mpath = Path(str(m["path"]))
            if not mpath.is_absolute():
                mpath = p.parent / mpath
            models[str(name)] = ModelSpec(name=str(name), path=str(mpath))
        default_model = str(raw.get("default_model") or "")
        if not default_model:
            if len(models) == 1:
                default_model = next(iter(models))
            else:
                raise ValueError(
                    f"manifest {path} needs 'default_model' when it "
                    "declares more than one model"
                )
        classes: Dict[str, ClassSpec] = {}
        for cname, c in (raw.get("classes") or {}).items():
            if not isinstance(c, dict):
                raise ValueError(f"manifest class {cname!r} must be an object")
            classes[str(cname)] = ClassSpec(
                name=str(cname),
                weight=float(c.get("weight", 1.0)),
                p99_target_ms=(
                    float(c["p99_target_ms"])
                    if c.get("p99_target_ms") is not None else None
                ),
            )
        tenants: Dict[str, TenantSpec] = {}
        for tname, t in (raw.get("tenants") or {}).items():
            if not isinstance(t, dict):
                raise ValueError(
                    f"manifest tenant {tname!r} must be an object"
                )
            tenants[str(tname)] = TenantSpec(
                name=str(tname),
                klass=str(t.get("class", DEFAULT_CLASS)),
                quota_docs_per_s=(
                    float(t["quota_docs_per_s"])
                    if t.get("quota_docs_per_s") is not None else None
                ),
                quota_burst=(
                    float(t["quota_burst"])
                    if t.get("quota_burst") is not None else None
                ),
            )
        return cls(models, default_model, classes=classes, tenants=tenants)

    # -- lookups ---------------------------------------------------------
    def names(self) -> List[str]:
        return sorted(self.models)

    def spec(self, name: str) -> ModelSpec:
        try:
            return self.models[name]
        except KeyError:
            raise UnknownModel(
                f"model {name!r} is not in the registry "
                f"(known: {self.names()})"
            ) from None

    def class_weights(self) -> Dict[str, float]:
        """``{class: weight}`` for the batcher's weighted fair queue."""
        return {c.name: c.weight for c in self.classes.values()}

    def tenant(self, name: Optional[str]) -> TenantSpec:
        """The tenant spec for a (possibly absent) tenant header. An
        unknown or missing tenant is the ANONYMOUS tenant: default
        class, no quota — the legacy contract for clients that never
        heard of tenancy."""
        if name is not None and name in self.tenants:
            return self.tenants[name]
        return TenantSpec(name=name or "anonymous")

    def p99_target_ms(self, klass: str) -> Optional[float]:
        spec = self.classes.get(klass)
        return spec.p99_target_ms if spec is not None else None

    # -- request resolution ---------------------------------------------
    def resolve_model(
        self, path: str, headers: Optional[Mapping[str, str]] = None
    ) -> Tuple[str, bool]:
        """Resolve the model a request names. Returns ``(name,
        explicit)`` where ``explicit`` is True when the client named the
        model (path or header) rather than falling through to the
        default. Raises ``UnknownModel`` (typed 404) for a name the
        registry does not know, and for any path that is neither
        ``/v1/parse`` nor a well-formed ``/v1/models/<name>/parse``.

        Precedence: path > header > default_model.
        """
        m = MODEL_PATH_RE.match(path)
        if m:
            name = m.group(1)
            self.spec(name)  # raises UnknownModel
            return name, True
        if path.startswith("/v1/models/"):
            raise UnknownModel(
                f"malformed model path {path!r} (expected "
                "/v1/models/<name>/parse)"
            )
        header = None
        if headers is not None:
            header = headers.get(MODEL_HEADER)
        if header:
            self.spec(header)  # raises UnknownModel
            return header, True
        return self.default_model, False

    def describe(self) -> Dict[str, Any]:
        """JSON-safe summary (healthz / metrics surfaces)."""
        return {
            "default_model": self.default_model,
            "models": self.names(),
            "classes": {
                c.name: {
                    "weight": c.weight, "p99_target_ms": c.p99_target_ms,
                }
                for c in self.classes.values()
            },
            "tenants": sorted(self.tenants),
        }
