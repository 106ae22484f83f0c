"""Typed system properties — the framework's config/flag system.

Mirrors the reference's three-tier config model (SURVEY.md §5): this module
is tier 1, the equivalent of ``GeoMesaSystemProperties.SystemProperty``
(geomesa-utils/.../conf/GeoMesaSystemProperties.scala:17-27) and the query
knobs in ``QueryProperties`` (geomesa-index-api/.../conf/
QueryProperties.scala:17-44).  Values resolve, in order: programmatic
override → environment variable (dots become underscores, upper-cased) →
default.  Tier 2 is per-schema user data (features/feature_type.py), tier
3 per-query hints (index/query options).
"""

from __future__ import annotations

import os
import threading
import warnings
from dataclasses import dataclass
from typing import Any

__all__ = ["SystemProperty", "SchemaOption", "QueryProperties",
           "ObsProperties", "ArrowProperties", "SchemaProperties",
           "ConfigProperties", "ResilienceProperties",
           "DensityProperties", "PlanningProperties",
           "set_property", "clear_property", "config_generation",
           "known_option_names", "check_option_name",
           "UnknownOptionWarning"]

_overrides: dict[str, Any] = {}
_lock = threading.Lock()
#: bumped on every programmatic override change — hot paths (obs
#: tracing) cache resolved property values keyed on this so they pay a
#: plain int read per call instead of the override lock, while
#: ``set_property`` still takes effect immediately
_generation = 0

#: the option registry (ISSUE 13): every declared knob — tier-1
#: SystemProperty AND tier-2 SchemaOption — keyed by name.  Filled by
#: ``_register_declarations`` at the bottom of this module; the static
#: analyzer (geomesa_tpu/analysis, check ``config-option``) reads the
#: SAME declarations off this file's AST, so the static and runtime
#: halves cannot drift.
_REGISTRY: dict[str, Any] = {}
#: names already warned about (one warning per unknown name, not one
#: per lookup)
_warned: set[str] = set()


class UnknownOptionWarning(UserWarning):
    """A ``geomesa.*`` option name nobody declared — almost always a
    typo that would otherwise silently read the default forever."""


def known_option_names() -> frozenset:
    """Every declared option name (system properties + schema
    options)."""
    return frozenset(_REGISTRY)


def check_option_name(name: str, *, raise_in_strict: bool = True) -> None:
    """Strict-option gate (ISSUE 13 satellite): a ``geomesa.*`` name
    that is not declared in this module warns — and RAISES under
    ``geomesa.config.strict`` — so a typo'd option fails loudly
    instead of silently defaulting.  Non-``geomesa.`` names pass
    untouched (embedders may ride the override store).
    ``raise_in_strict=False`` demotes strict mode to the warning
    (``clear_property``: removing a stale override is inherently safe
    and must stay possible WHILE strict is on)."""
    if not name.startswith("geomesa.") or name in _REGISTRY \
            or not _REGISTRY:
        return
    msg = (f"unregistered option {name!r}: not declared in "
           f"geomesa_tpu/config.py (typo?) — known names: "
           f"docs/configuration.md")
    if raise_in_strict and ConfigProperties.STRICT.to_bool():
        raise ValueError(msg)
    if name not in _warned:
        _warned.add(name)
        warnings.warn(msg, UnknownOptionWarning, stacklevel=3)


def config_generation() -> int:
    return _generation


def set_property(name: str, value) -> None:
    global _generation
    check_option_name(name)
    with _lock:
        _overrides[name] = value
        _generation += 1


def clear_property(name: str) -> None:
    global _generation
    check_option_name(name, raise_in_strict=False)
    with _lock:
        _overrides.pop(name, None)
        _generation += 1


@dataclass(frozen=True)
class SystemProperty:
    """A named, typed knob with env-var and programmatic override."""

    name: str
    default: Any

    @property
    def env_var(self) -> str:
        return self.name.replace(".", "_").upper()

    def get(self):
        check_option_name(self.name)
        with _lock:
            if self.name in _overrides:
                return _overrides[self.name]
        raw = os.environ.get(self.env_var)
        if raw is None:
            return self.default
        if isinstance(self.default, bool):
            return raw.strip().lower() in ("1", "true", "yes")
        if isinstance(self.default, int):
            return int(raw)
        if isinstance(self.default, float):
            return float(raw)
        return raw

    def to_int(self) -> int:
        return int(self.get())

    def to_bool(self) -> bool:
        return bool(self.get())


@dataclass(frozen=True)
class SchemaOption:
    """A declared tier-2 option: a ``geomesa.*`` key read from a
    schema's user data (``features/feature_type.py``) rather than the
    process environment.  Declared here purely so the option REGISTRY
    is complete — both the runtime strict mode and the static
    ``config-option`` check resolve every ``"geomesa.*"`` literal in
    the tree against these declarations; resolution itself stays where
    it always was (``sft.user_data.get(...)``)."""

    name: str
    default: Any = None
    doc: str = ""


class ConfigProperties:
    """The config system's own knobs."""

    #: strict option mode: unregistered ``geomesa.*`` names RAISE at
    #: ``set_property``/lookup instead of warning (CI wants typos
    #: fatal; interactive embedders may prefer the warning)
    STRICT = SystemProperty("geomesa.config.strict", False)


class SchemaProperties:
    """Tier-2 per-schema option declarations (the user-data keys the
    datastore and feature types honor — docs/configuration.md)."""

    #: index layout profile: ``lean`` selects the tiered SoA lean
    #: index families (docs/design.md)
    INDEX_PROFILE = SchemaOption("geomesa.index.profile", "",
                                 "index layout profile ('lean')")
    #: explicit index-version pin list, or 'current'
    INDEX_VERSIONS = SchemaOption("geomesa.index.versions", "",
                                  "pin index versions")
    #: which attribute is THE temporal axis (else first Date attr)
    INDEX_DTG = SchemaOption("geomesa.index.dtg", "",
                             "temporal attribute override")
    #: comma list restricting which index kinds build
    INDICES_ENABLED = SchemaOption("geomesa.indices.enabled", "",
                                   "restrict built indexes")
    #: z3 time-bin interval: 'day' | 'week' | 'month' | 'year'
    Z3_INTERVAL = SchemaOption("geomesa.z3.interval", "week",
                               "z3 time-bin period")
    #: xz curve resolution (g in the XZ-ordering papers)
    XZ_PRECISION = SchemaOption("geomesa.xz.precision", 12,
                                "xz curve precision")
    #: feature-id minting strategy ('z3' = locality-preserving)
    FID_STRATEGY = SchemaOption("geomesa.fid.strategy", "",
                                "feature-id strategy")
    #: age-off retention expression (age_off.py)
    AGE_OFF = SchemaOption("geomesa.age.off", "",
                           "age-off retention window")
    #: registered query interceptors (planning/interceptor.py)
    QUERY_INTERCEPTORS = SchemaOption("geomesa.query.interceptors", "",
                                      "query interceptor chain")
    #: lean-profile HBM budget in bytes for this schema's device tiers
    LEAN_HBM_BUDGET = SchemaOption("geomesa.lean.hbm.budget", 0,
                                   "lean device-tier byte budget")
    #: lean LSM size-tier factor (0 disables auto-compaction)
    LEAN_COMPACTION_FACTOR = SchemaOption(
        "geomesa.lean.compaction.factor", 4, "LSM size-tier factor")
    #: lean generation capacity in slots (rollover threshold)
    LEAN_GENERATION_SLOTS = SchemaOption(
        "geomesa.lean.generation.slots", 0, "generation slot capacity")


class QueryProperties:
    """Planner guardrails (QueryProperties.scala:17-44 equivalents)."""

    #: target number of scan ranges per query (split across time bins)
    SCAN_RANGES_TARGET = SystemProperty("geomesa.scan.ranges.target", 2000)
    #: query timeout in seconds; 0 disables (ThreadManagement reaper analog)
    QUERY_TIMEOUT = SystemProperty("geomesa.query.timeout", 0)
    #: skip the exact geometry re-check and trust index-key resolution
    #: accepted for parity with the reference (QueryProperties.scala); a
    #: deliberate no-op here: the exact double-precision re-check is FUSED
    #: into the scan kernel's candidate mask, so "loose" would save
    #: nothing — results are always exact at zero extra cost
    LOOSE_BBOX = SystemProperty("geomesa.query.loose.bounding.box", False)
    #: refuse queries that would scan the full table (opt-in, like the
    #: reference's BlockFullTableScans)
    BLOCK_FULL_TABLE_SCANS = SystemProperty(
        "geomesa.scan.block.full.table", False)
    #: cost strategy: 'stats' (cost-based) or 'index' (heuristic priority)
    COST_TYPE = SystemProperty("geomesa.query.cost.type", "stats")
    #: use the Pallas candidate-filter kernel on TPU backends (falls back
    #: to the fused XLA path automatically if lowering fails)
    PALLAS_SCAN = SystemProperty("geomesa.scan.pallas", True)


class ObsProperties:
    """Observability knobs (the ``geomesa.obs.*`` option family —
    docs/observability.md).  Sampler kind and the slow threshold are
    re-read per trace so tests and operators can flip them live via
    :func:`set_property`; capacities are read once at tracer
    construction."""

    #: master switch — off makes every span a shared no-op
    ENABLED = SystemProperty("geomesa.obs.enabled", True)
    #: root-span sampling: 'always', 'ratio', 'slow' (retain only
    #: slower-than-threshold traces), or 'never'
    SAMPLER = SystemProperty("geomesa.obs.sampler", "always")
    #: fraction of root spans recorded under the 'ratio' sampler
    SAMPLE_RATIO = SystemProperty("geomesa.obs.sample.ratio", 0.1)
    #: slow-query threshold in ms: traces at/over it land in the slow
    #: log (and are what the 'slow' sampler retains); <= 0 disables
    SLOW_MS = SystemProperty("geomesa.obs.slow.ms", 500.0)
    #: ring-buffer exporter capacity (traces)
    TRACE_CAPACITY = SystemProperty("geomesa.obs.trace.capacity", 256)
    #: JSONL trace-sink size cap in bytes: the exporter rotates so the
    #: live file plus one predecessor stay within this total (long
    #: bench runs must not grow the sink without bound); <= 0 disables
    #: rotation.  Re-read per export, so it is live-tunable.
    TRACE_MAX_BYTES = SystemProperty("geomesa.obs.trace.max_bytes",
                                     128 * 2 ** 20)
    #: slow-query log capacity (traces)
    SLOW_CAPACITY = SystemProperty("geomesa.obs.slow.capacity", 64)
    #: count XLA backend compiles via the jax.monitoring listener
    #: (jax.compile.* metrics); the classic silent TPU perf cliff
    RECOMPILE_TRACK = SystemProperty("geomesa.obs.recompile.track", True)
    #: access-temperature tracking (obs/heat.py): per-(schema, index,
    #: generation) touch counters folded into a decayed temperature
    #: score — the workload data plane the tier autopilot consumes.
    #: Off reduces every record site to one cached bool read.
    HEAT_ENABLED = SystemProperty("geomesa.obs.heat.enabled", True)
    #: temperature decay constant τ in seconds: each touch contributes
    #: ``exp(-(now - t)/τ)`` to a generation's score, so a touch fades
    #: to ~37% after τ seconds (half-life τ·ln 2 ≈ 0.69τ)
    HEAT_TAU_S = SystemProperty("geomesa.obs.heat.tau.s", 600.0)
    #: hard bound on tracked (schema, index, generation) entries —
    #: beyond it the coldest entries evict (bounded memory under
    #: generation churn)
    HEAT_MAX_ENTRIES = SystemProperty("geomesa.obs.heat.max.entries",
                                      8192)
    #: write-path device attribution: when a write runs under a
    #: RECORDING span, block on the live index generation at the end of
    #: the write so the trace carries honest block-until-ready device
    #: ms (the scan-span discipline).  Blocking only forces work that
    #: must complete anyway; off keeps appends fully pipelined even
    #: while traced
    WRITE_BLOCK = SystemProperty("geomesa.obs.write.block", True)
    #: background-job registry retention (obs/jobs.py): finished
    #: IngestJob/CompactionJob records kept for /debug/jobs
    JOBS_CAPACITY = SystemProperty("geomesa.obs.jobs.capacity", 128)
    #: /metrics.prom scrape cache: while a scrape is younger than this
    #: many ms, the next scrape reuses its rendered text instead of
    #: re-walking storage and re-publishing every gauge (aggressive
    #: scrapers must not hammer storage_report); <= 0 disables the
    #: cache (every scrape walks).  ``?mesh=1`` scrapes never cache —
    #: the mesh merge is a collective that must run when driven.
    SCRAPE_MIN_INTERVAL_MS = SystemProperty(
        "geomesa.obs.scrape.min.interval.ms", 0.0)
    #: hard cap on recorded spans per trace: past it, child spans
    #: yield the shared no-op and the root accumulates a
    #: ``spans.dropped`` count — a 10k-generation scan must not
    #: balloon the ring exporter; <= 0 disables the cap
    TRACE_MAX_SPANS = SystemProperty("geomesa.obs.trace.max.spans",
                                     4096)


class ArrowProperties:
    """Arrow-native streaming result path knobs (the ``geomesa.arrow.*``
    option family — docs/arrow.md, ISSUE 14).  All three are re-read
    per stream, so operators can tune a live serving process."""

    #: rows per emitted Arrow record batch on the streaming result path
    #: (``store.query_arrow`` default when ``chunk_rows`` is not passed;
    #: the reference's ArrowScan batch-size hint)
    CHUNK_ROWS = SystemProperty("geomesa.arrow.chunk.rows", 65536)
    #: distinct-value ceiling for AUTO dictionary encoding: a string
    #: attribute dictionary-encodes only while its observed cardinality
    #: (sampled on the first chunk) stays at/below this — past it the
    #: column ships as plain utf8 (a dictionary bigger than the data
    #: saves nothing and bloats every delta message)
    DICTIONARY_THRESHOLD = SystemProperty(
        "geomesa.arrow.dictionary.threshold", 1024)
    #: streaming-response flush threshold in bytes: the chunked
    #: Arrow-IPC web response (``/query?format=arrow``) coalesces
    #: encoded IPC messages until at least this many bytes are buffered
    #: before handing a chunk to the WSGI layer (tiny record batches
    #: must not become tiny socket writes); <= 0 flushes per batch
    STREAM_BUFFER_BYTES = SystemProperty(
        "geomesa.arrow.stream.buffer.bytes", 1 << 20)


class ResilienceProperties:
    """Resilience-layer knobs (ISSUE 16, geomesa_tpu/resilience):
    admission gating, degraded execution, and the deterministic
    fault-injection harness.  Everything defaults OFF — an unconfigured
    store behaves exactly as before this layer existed."""

    #: HBM admission ceiling in bytes: new queries shed (Backpressure)
    #: while the live ``storage.total.device_bytes`` gauge exceeds this;
    #: 0 disables the HBM check
    HBM_HEADROOM = SystemProperty("geomesa.resilience.hbm.headroom", 0)
    #: max concurrently-admitted queries per process; 0 = unbounded
    ADMISSION_MAX_CONCURRENT = SystemProperty(
        "geomesa.resilience.admission.max.concurrent", 0)
    #: how long an over-budget request may queue (ms) before shedding
    ADMISSION_QUEUE_MS = SystemProperty(
        "geomesa.resilience.admission.queue.ms", 50.0)
    #: bounded retries after a transient (RESOURCE_EXHAUSTED) device
    #: failure demotes the offending generation's payload to host
    RETRY_MAX = SystemProperty("geomesa.resilience.retry.max", 1)
    #: consecutive transient failures before a generation's device
    #: dispatch circuit opens (host-tier routing until cooldown)
    BREAKER_THRESHOLD = SystemProperty(
        "geomesa.resilience.breaker.threshold", 3)
    #: seconds an open breaker refuses device dispatch before half-open
    BREAKER_COOLDOWN_S = SystemProperty(
        "geomesa.resilience.breaker.cooldown.s", 30.0)
    #: armed fault points (resilience/faults.py): comma-separated
    #: ``point[:trigger][=kind]`` — bare point fires every hit, integer
    #: trigger fires on exactly the Nth hit, float < 1 fires with that
    #: seeded probability; kind is ``error`` (poison) or ``oom``
    #: (classified transient).  Empty disables injection entirely.
    FAULT_POINTS = SystemProperty("geomesa.resilience.fault.points", "")
    #: RNG seed for probabilistic fault triggers — same seed + same hit
    #: order = same injected failures (deterministic chaos runs)
    FAULT_SEED = SystemProperty("geomesa.resilience.fault.seed", 0)


class ServingProperties:
    """Fused serving plane knobs (ISSUE 17, geomesa_tpu/serving):
    query fusion — coalescing concurrent compatible queries into one
    batched device dispatch — and per-tenant fairness over it."""

    #: master switch for query fusion; False routes every request down
    #: the solo path untouched
    FUSE_ENABLED = SystemProperty("geomesa.serving.fuse.enabled", True)
    #: how long (ms) a batch leader lingers collecting riders before
    #: dispatching the fused batch
    FUSE_WINDOW_MS = SystemProperty("geomesa.serving.fuse.window.ms", 2.0)
    #: max requests fused into one batched dispatch; a full batch
    #: dispatches immediately without waiting out the window
    FUSE_MAX_BATCH = SystemProperty("geomesa.serving.fuse.max.batch", 64)
    #: per-tenant queued-request ceiling; a tenant at its ceiling sheds
    #: (Backpressure → 503) instead of growing the queue; 0 = unbounded
    TENANT_QUEUE_MAX = SystemProperty("geomesa.serving.tenant.queue.max", 0)
    #: deficit-round-robin quantum (window-count units) each tenant
    #: earns per batch-assembly pass — larger values trade fairness
    #: granularity for fewer scheduling rounds
    TENANT_QUANTUM = SystemProperty("geomesa.serving.tenant.quantum", 4)


class DensityProperties:
    """Density-pyramid knobs (ISSUE 18, docs/density.md): sealed
    generations precompute world-aligned multi-resolution density
    grids so whole-extent/zoomed-out heatmaps and ``/tiles/{z}/{x}/{y}``
    requests sum cached cells instead of rescanning history."""

    #: base pyramid resolution (cells per axis, power of two): each
    #: sealed generation's pyramid starts at a (base, base) world grid
    #: and halves down from there.  Tile requests whose effective world
    #: resolution exceeds the base fall back to the direct density scan
    PYRAMID_BASE = SystemProperty("geomesa.density.pyramid.base", 512)
    #: reduction-ladder depth; 0 = the full ladder down to 1×1
    PYRAMID_LEVELS = SystemProperty("geomesa.density.pyramid.levels", 0)
    #: byte ceiling for the per-index pyramid cache (the shared
    #: PartialCache LRU/invalidation policy density partials use)
    PYRAMID_CACHE_BYTES = SystemProperty(
        "geomesa.density.pyramid.cache.bytes", 256 * (1 << 20))
    #: build trigger: ``off`` (builds happen only on explicit
    #: ``store.build_pyramids`` / ``jobs.run_pyramid_build`` calls) or
    #: ``seal`` (a generation seal schedules a build-behind job —
    #: never blocking the append, never changing results)
    PYRAMID_BUILD = SystemProperty("geomesa.density.pyramid.build", "off")


class PlanningProperties:
    """Cost-based planning knobs (ISSUE 19, docs/planning.md):
    sketch-fed cardinality estimation and adaptive mid-query
    replanning.  All are re-read per query plan, so a live process
    retunes without restart."""

    #: sketch-fed estimation master switch: off makes the decider cost
    #: strategies from whole-store stats / heuristics only
    ESTIMATOR_ENABLED = SystemProperty(
        "geomesa.planning.estimator.enabled", True)
    #: live-row floor below which the sketch tier is skipped entirely:
    #: the cold per-generation sketch folds (device dispatches + XLA
    #: compiles) cannot amortize on a store a whole scan finishes in
    #: milliseconds, and at small scale a misplanned strategy costs
    #: less than building the tables — the decider plans from
    #: whole-store stats / heuristics exactly as if the estimator were
    #: off.  0 sketches every store regardless of size
    ESTIMATOR_MIN_ROWS = SystemProperty(
        "geomesa.planning.estimator.min.rows", 262_144)
    #: assumed selectivity of an attribute equality with no usable
    #: stat (fraction of the store the strategy is costed at) — the
    #: named replacement for the old bare ``total / 10``
    SELECTIVITY_EQUALS_DEFAULT = SystemProperty(
        "geomesa.planning.selectivity.equals.default", 0.1)
    #: assumed selectivity of an attribute range/prefix with no usable
    #: stat — the named replacement for the old bare ``total / 4``
    SELECTIVITY_RANGE_DEFAULT = SystemProperty(
        "geomesa.planning.selectivity.range.default", 0.25)
    #: adaptive-replan divergence trigger: when a scan's candidate
    #: probe observes more than ``threshold × estimate`` rows, the
    #: remaining scan aborts and the query replans ONCE with the
    #: observed actual folded in; <= 0 disables replanning
    REPLAN_THRESHOLD = SystemProperty(
        "geomesa.planning.replan.threshold", 8.0)
    #: observed-row floor below which a divergence never triggers a
    #: replan — aborting a tiny scan costs more than finishing it
    REPLAN_MIN_ROWS = SystemProperty(
        "geomesa.planning.replan.min.rows", 4096)


class SloProperties:
    """SLO plane knobs (ISSUE 20, geomesa_tpu/obs/slo.py —
    docs/slo.md): per-class latency objectives, rolling burn windows,
    and the alert ring.  Everything re-reads through a
    config-generation cache, so a live process retunes without
    restart."""

    #: master switch: off makes the root-span finish hook a no-op —
    #: no stage ledger, no windows, no exemplars (tracing itself is
    #: governed separately by ``geomesa.obs.enabled``; the SLO plane
    #: only ever sees traces the tracer recorded)
    ENABLED = SystemProperty("geomesa.slo.enabled", True)
    #: latency/availability objectives, one per request class:
    #: comma-separated ``class:latency_ms:target`` triples.  A request
    #: counts against the class's error budget when it errored OR its
    #: end-to-end latency (admission queue included) exceeded
    #: ``latency_ms``; ``target`` is the good-fraction objective the
    #: burn rate normalizes against (burn = bad_fraction / (1-target))
    OBJECTIVES = SystemProperty(
        "geomesa.slo.objectives",
        "query:250:0.99,write:1000:0.99,tile.render:250:0.99")
    #: short burn window in seconds (the fast-burn signal)
    WINDOW_SHORT_S = SystemProperty("geomesa.slo.window.short.s", 300.0)
    #: long burn window in seconds (the sustained-burn confirmation)
    WINDOW_LONG_S = SystemProperty("geomesa.slo.window.long.s", 3600.0)
    #: rolling-window time-bucket width in seconds (retention is
    #: ceil(window.long.s / bucket.s) buckets per (class, tenant))
    BUCKET_S = SystemProperty("geomesa.slo.bucket.s", 10.0)
    #: multi-window alert threshold: an alert fires (edge-triggered)
    #: when BOTH windows' burn rates exceed this, and re-arms when the
    #: short window drops back under; <= 0 disables alerting
    BURN_ALERT = SystemProperty("geomesa.slo.burn.alert", 10.0)
    #: bounded /debug/alerts ring capacity (threshold crossings kept)
    ALERTS_CAPACITY = SystemProperty("geomesa.slo.alerts.capacity", 128)
    #: distinct-tenant label bound: past it, new tenants fold into the
    #: ``other`` label (bounded metric cardinality under tenant churn)
    TENANTS_MAX = SystemProperty("geomesa.slo.tenants.max", 64)


def _register_declarations() -> None:
    """Fill the option registry from the declaration classes above —
    the one place a knob becomes 'known' to the strict mode."""
    for cls in (QueryProperties, ObsProperties, ArrowProperties,
                SchemaProperties, ConfigProperties, ResilienceProperties,
                ServingProperties, DensityProperties, PlanningProperties,
                SloProperties):
        for value in vars(cls).values():
            if isinstance(value, (SystemProperty, SchemaOption)):
                _REGISTRY[value.name] = value


_register_declarations()

#: default scan-ranges budget (import-time snapshot users can override per
#: call; the live knob is QueryProperties.SCAN_RANGES_TARGET)
DEFAULT_MAX_RANGES = QueryProperties.SCAN_RANGES_TARGET.default
