"""Global configuration from environment variables.

Equivalent of /root/reference/src/GlobalSettings.ts:54-89 plus the Rust DP's
env (/root/reference/kmamiz_data_processor/src/env.rs), with TPU-specific
additions (mesh shape, batch padding policy).
"""
from __future__ import annotations

import os
import re
from dataclasses import dataclass, field


def _env_bool(name: str) -> bool:
    return os.environ.get(name) == "true"


@dataclass
class Settings:
    port: str = field(default_factory=lambda: os.environ.get("PORT", "3000"))
    timezone: str = field(default_factory=lambda: os.environ.get("TZ", "Asia/Taipei"))
    api_version: str = field(default_factory=lambda: os.environ.get("API_VERSION", "1"))
    log_level: str = field(default_factory=lambda: os.environ.get("LOG_LEVEL", "info"))
    kube_api_host: str = field(
        default_factory=lambda: os.environ.get("KUBEAPI_HOST", "http://127.0.0.1:8080")
    )
    is_running_in_kubernetes: bool = field(
        default_factory=lambda: _env_bool("IS_RUNNING_IN_K8S")
    )
    zipkin_url: str = field(
        default_factory=lambda: os.environ.get("ZIPKIN_URL", "http://localhost:9411")
    )
    storage_uri: str = field(
        default_factory=lambda: os.environ.get(
            "STORAGE_URI", os.environ.get("MONGODB_URI", "file://./kmamiz-data")
        )
    )
    external_data_processor: str = field(
        default_factory=lambda: os.environ.get("EXTERNAL_DATA_PROCESSOR", "")
    )
    # checkpoint directory of a trained forecast head (models/trainer.py);
    # empty disables the GET /model routes' inference
    model_dir: str = field(
        default_factory=lambda: os.environ.get("KMAMIZ_MODEL_DIR", "")
    )
    aggregate_interval: str = field(
        default_factory=lambda: os.environ.get("AGGREGATE_INTERVAL", "*/5 * * * *")
    )
    realtime_interval: str = field(
        default_factory=lambda: os.environ.get("REALTIME_INTERVAL", "0/5 * * * *")
    )
    dispatch_interval: str = field(
        default_factory=lambda: os.environ.get("DISPATCH_INTERVAL", "0/30 * * * *")
    )
    envoy_log_level: str = field(
        default_factory=lambda: os.environ.get("ENVOY_LOG_LEVEL", "info")
    )
    reset_endpoint_dependencies: bool = field(
        default_factory=lambda: _env_bool("RESET_ENDPOINT_DEPENDENCIES")
    )
    read_only_mode: bool = field(default_factory=lambda: _env_bool("READ_ONLY_MODE"))
    enable_testing_endpoints: bool = field(
        default_factory=lambda: _env_bool("ENABLE_TESTING_ENDPOINTS")
    )
    service_port: str = field(
        default_factory=lambda: os.environ.get(
            "SERVICE_PORT", os.environ.get("PORT", "3000")
        )
    )
    serve_only: bool = field(default_factory=lambda: _env_bool("SERVE_ONLY"))
    inactive_endpoint_threshold: str = field(
        default_factory=lambda: os.environ.get("INACTIVE_ENDPOINT_THRESHOLD", "")
    )
    deprecated_endpoint_threshold: str = field(
        default_factory=lambda: os.environ.get("DEPRECATED_ENDPOINT_THRESHOLD", "")
    )
    simulator_mode: bool = field(default_factory=lambda: _env_bool("SIMULATOR_MODE"))

    # static serving (index.ts:46-53): SPA build dir + Envoy filter binary
    static_dir: str = field(
        default_factory=lambda: os.environ.get("KMAMIZ_STATIC_DIR", "./dist")
    )
    # default: the in-tree artifact tools/build_wasm_filter.py assembles
    wasm_path: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_WASM_PATH", "./envoy/filter/kmamiz_filter.wasm"
        )
    )

    # TPU-specific
    mesh_devices: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_MESH_DEVICES", "0"))
    )  # 0 = all available
    span_batch_pad: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_SPAN_BATCH_PAD", "2"))
    )  # pad batches to powers of this base to bound recompilation
    # -- sparse kernels / capacity growth (docs/SPARSE_KERNELS.md) -----
    # ops/sparse.py and graph/store.py read these env vars directly (the
    # knobs must work in bare kernel benchmarks without a Settings
    # instance); mirrored here so one `Settings()` dump shows them.
    sparse_backend: str = field(
        default_factory=lambda: os.environ.get("KMAMIZ_SPARSE", "sparse")
    )  # xla | sparse
    store_grow: str = field(
        default_factory=lambda: os.environ.get("KMAMIZ_STORE_GROW", "segment")
    )  # segment = compile-free overflow tail; repack = pow2 re-pad
    store_tail_shift: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_STORE_TAIL_SHIFT", "3")
        )
    )  # tail rows = max(256, capacity >> shift); 3 = 12.5% headroom

    # resilience layer (kmamiz_tpu/resilience/, docs/RESILIENCE.md).
    # The modules read these env vars directly (they must work without a
    # Settings instance, e.g. in the external DP process); the fields
    # here mirror them so one `Settings()` dump shows the whole config.
    quarantine_dir: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_QUARANTINE_DIR", "./kmamiz-data/quarantine"
        )
    )
    ingest_max_bytes: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_INGEST_MAX_BYTES", str(256 * 1024 * 1024))
        )
    )  # trace-bomb size cap for one raw ingest payload
    # -- ingest wire / transfer overlap (docs/INGEST_WIRE.md) ----------
    parse_shards: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_PARSE_SHARDS", "4")
        )
    )  # work-stealing chunks per parse worker (clamped 1..64 natively)
    upload_depth: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_UPLOAD_DEPTH", "2")
        )
    )  # in-flight host->device upload windows (0 = legacy synchronous)
    # the wire FORMAT itself has no env toggle on this side: ingest
    # auto-detects per payload (KMZC magic -> columnar, else JSON); the
    # emitter toggle is the Envoy filter's plugin-config `wire_format`
    # key (envoy/EnvoyFilter-WASM.yaml)
    tick_deadline_ms: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_TICK_DEADLINE_MS", "0")
        )
    )  # 0 = watchdog off; >0 = degrade to last-good past this
    wal_enabled: bool = field(
        default_factory=lambda: os.environ.get("KMAMIZ_WAL", "0") == "1"
    )
    wal_dir: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_WAL_DIR", "./kmamiz-data/wal"
        )
    )
    breaker_threshold: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_BREAKER_THRESHOLD", "5")
        )
    )  # consecutive failures before an upstream breaker opens
    breaker_cooldown_s: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_BREAKER_COOLDOWN_S", "30")
        )
    )
    dp_timeout_s: float = field(
        default_factory=lambda: float(os.environ.get("KMAMIZ_DP_TIMEOUT_S", "30"))
    )  # external-DP request timeout (was a hardcoded 30)

    # tenancy layer (kmamiz_tpu/tenancy/, docs/TENANCY.md). Like the
    # resilience knobs, the tenancy modules read these env vars directly;
    # the fields mirror them so one `Settings()` dump shows everything.
    tenant_header: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_TENANT_HEADER", "x-kmamiz-tenant"
        )
    )  # HTTP header carrying the tenant name (the /t/<tenant>/ path prefix wins)
    max_tenants: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_MAX_TENANTS", "64"))
    )  # arena admission cap; joins past it get 429
    tenant_batch_window_ms: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_TENANT_BATCH_WINDOW_MS", "0")
        )
    )  # 0 = per-request ticks; >0 = gather concurrent tenant ticks this long
    max_tenant_series: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_MAX_TENANT_SERIES", "32")
        )
    )  # distinct tenant label values before folding into __other__
    tenant_shard: bool = field(
        default_factory=lambda: os.environ.get("KMAMIZ_TENANT_SHARD", "1") != "0"
    )  # shard the stacked tenant arena over the device mesh's spans axis

    # scenario factory (kmamiz_tpu/scenarios/, docs/SCENARIOS.md). The
    # scenarios modules read these env vars directly; the fields mirror
    # them so one `Settings()` dump shows everything.
    scenario_seed: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_SCENARIO_SEED", "0")
        )
    )  # matrix seed: one integer composes every topology/traffic/storyline
    scenario_matrix: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_SCENARIO_MATRIX", "11"))
    )  # matrix size; archetype i % len(ARCHETYPES) at index i
    scenario_ticks: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_SCENARIO_TICKS", "10"))
    )  # soak length per scenario, in DP ticks
    scenario_storylines: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_SCENARIO_STORYLINES", "all"
        )
    )  # comma list filtering the storyline vocabulary ("all" = everything)

    # graftfleet (kmamiz_tpu/fleet/, docs/FLEET.md). The fleet modules
    # read these env vars directly (the ring must be buildable before
    # any Settings instance exists); the fields mirror them so one
    # `Settings()` dump shows everything.
    fleet_size: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_FLEET_SIZE", "1"))
    )  # front-end workers behind the coordinator (>= 2 enables fleet mode)
    fleet_vnodes: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_FLEET_VNODES", "64"))
    )  # virtual nodes per worker on the consistent-hash ring
    fleet_seed: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_FLEET_SEED", "0"))
    )  # ring hash seed; same seed => same tenant placement everywhere
    fleet_coord_port: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_FLEET_COORD_PORT", "0")
        )
    )  # coordinator HTTP port (0 = ephemeral / in-process only)
    fleet_drain_timeout_ms: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_FLEET_DRAIN_TIMEOUT_MS", "5000")
        )
    )  # migration drain budget; a handoff past this aborts to the source
    lock_witness: bool = field(
        default_factory=lambda: os.environ.get("KMAMIZ_LOCK_WITNESS", "0")
        == "1"
    )  # graftrace runtime lock witness (analysis/concurrency/witness.py);
    # the witness module reads the env var directly at arm time — this
    # field mirrors it so one `Settings()` dump shows everything

    # graftprof profiler (kmamiz_tpu/telemetry/profiling/, the
    # "Profiling" section of docs/OBSERVABILITY.md). The profiling
    # modules read these env vars directly (the host event ring must
    # work before any Settings instance exists); the fields mirror them
    # so one `Settings()` dump shows everything.
    prof_enabled: bool = field(
        default_factory=lambda: os.environ.get("KMAMIZ_PROF", "1")
        not in ("0", "false", "")
    )  # master gate for the host event ring (re-read once per tick)
    prof_ring: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_PROF_RING", "4096"))
    )  # host event ring capacity, in events (min 64)
    prof_flight_dir: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_PROF_FLIGHT_DIR", "./kmamiz-data/flight"
        )
    )  # flight-recorder crash box for SLO-breach artifacts
    prof_flight_ticks: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_PROF_FLIGHT_TICKS", "64")
        )
    )  # ticks of evidence frozen into each flight artifact
    prof_flight_max: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_PROF_FLIGHT_MAX", "16"))
    )  # newest artifacts kept; older ones pruned
    prof_flight_debounce_s: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_PROF_FLIGHT_DEBOUNCE_S", "5")
        )
    )  # min seconds between artifacts (breaker flaps must not flood)
    profile_max_s: float = field(
        default_factory=lambda: float(os.environ.get("KMAMIZ_PROFILE_MAX_S", "10"))
    )  # hard bound on one POST /debug/profile jax.profiler capture

    # STLGT continual trainer (kmamiz_tpu/models/stlgt/, docs/STLGT.md).
    # The trainer reads these env vars directly (it is constructed
    # lazily at the first fold, before any Settings instance need
    # exist); the fields mirror them so one `Settings()` dump shows
    # everything.
    stlgt_enabled: bool = field(
        default_factory=lambda: os.environ.get("KMAMIZ_STLGT", "0")
        not in ("0", "false", "")
    )  # master gate for the continual trainer fold hook (default OFF)
    stlgt_refresh: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_STLGT_REFRESH", "1"))
    )  # refresh cadence: stale-slot retrain every N folds
    stlgt_history: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_STLGT_HISTORY", "8"))
    )  # example ring depth, in fold windows (pads to a pow2 bucket)
    stlgt_epochs: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_STLGT_EPOCHS", "2"))
    )  # scan-fused epochs per refresh (static arg of the epoch block)
    stlgt_hidden: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_STLGT_HIDDEN", "32"))
    )  # transformer width H (attention cost is O(N * H^2))
    stlgt_lr: float = field(
        default_factory=lambda: float(os.environ.get("KMAMIZ_STLGT_LR", "0.05"))
    )  # adamw learning rate of the continual refresh
    stlgt_quantiles: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_STLGT_QUANTILES", "0.5,0.95,0.99"
        )
    )  # the three forecast quantile levels (comma list, ascending)
    stlgt_horizon_max: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_STLGT_HORIZON_MAX", "24")
        )
    )  # upper clamp on ?horizon= sqrt-widening; the route 400s beyond

    # graftpilot control plane (kmamiz_tpu/control/, docs/CONTROL.md).
    # The controller reads these env vars directly at decision time
    # (fold cadence); the fields mirror them so one `Settings()` dump
    # shows everything.
    control_enabled: bool = field(
        default_factory=lambda: os.environ.get("KMAMIZ_CONTROL", "0")
        not in ("0", "false", "")
    )  # master gate for the forecast-driven control plane (default OFF)
    control_slo_ms: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_CONTROL_SLO_MS", "250")
        )
    )  # forecast-p99 SLO; KMAMIZ_CONTROL_SLO_MS_<TENANT> overrides
    control_hysteresis: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_CONTROL_HYSTERESIS", "2")
        )
    )  # consecutive evals to enter AND leave shedding (no-flap)
    control_warmup_gate: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_CONTROL_WARMUP_GATE", "0.5")
        )
    )  # attribution score arming proactive breaker warm-up
    control_mode: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_CONTROL_MODE", "defer"
        )
    )  # defer (serve last-good, marked) or shed (429) on admission
    control_horizon: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_CONTROL_HORIZON", "1")
        )
    )  # hours-ahead forecast admission judges (clamped to horizon max)
    control_probe_s: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_CONTROL_PROBE_S", "1.0")
        )
    )  # shortened breaker probe cooldown while warmed

    # graftcost program-cost model (kmamiz_tpu/cost/, docs/COST_MODEL.md).
    # The cost plane reads these env vars directly (its hooks fire from
    # merge finalizes before any Settings instance need exist); the
    # fields mirror them so one `Settings()` dump shows everything.
    cost_enabled: bool = field(
        default_factory=lambda: os.environ.get("KMAMIZ_COST", "0")
        not in ("0", "false", "")
    )  # master gate for the learned cost plane (default OFF)
    cost_prewarm: str = field(
        default_factory=lambda: os.environ.get("KMAMIZ_COST_PREWARM", "1")
    )  # "1" background-thread prewarm, "sync" harness-drained, "0" forecast only
    cost_horizon: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_COST_HORIZON", "3"))
    )  # crossings projected within this many merges arm predictive prewarm
    cost_examples: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_COST_EXAMPLES", "256"))
    )  # fixed ridge-fit table rows (pow2-clamped 32..4096; one shape = one compile)

    # graftstream micro-tick pipeline (kmamiz_tpu/server/stream.py, the
    # "Streaming micro-ticks" section of docs/TICK_PIPELINE.md). The
    # stream engine reads these env vars directly on the hot path; the
    # fields mirror them so one `Settings()` dump shows everything.
    stream_enabled: bool = field(
        default_factory=lambda: os.environ.get("KMAMIZ_STREAM", "0")
        not in ("0", "false", "")
    )  # overlapped micro-tick engine (default OFF: serial parity reference)
    stream_depth: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_STREAM_DEPTH", "2"))
    )  # prepared-tick hand-off queue bound (clamped 1..8)
    stream_epoch_ticks: int = field(
        default_factory=lambda: int(
            os.environ.get("KMAMIZ_STREAM_EPOCH_TICKS", "32")
        )
    )  # micro-ticks per watchdog deadline-cache epoch (floor 1)

    # graftsoak sweep engine (kmamiz_tpu/soak/, docs/SCENARIOS.md).
    # The soak engine and its worker subprocesses read these env vars
    # directly (workers start fresh interpreters); the fields mirror
    # them so one `Settings()` dump shows everything.
    soak_dir: str = field(
        default_factory=lambda: os.environ.get(
            "KMAMIZ_SOAK_DIR", os.path.join("kmamiz-data", "soak")
        )
    )  # sweep manifest / per-cell records / flight boxes root
    soak_workers: int = field(
        default_factory=lambda: int(
            os.environ.get(
                "KMAMIZ_SOAK_WORKERS", min(4, max(1, os.cpu_count() or 1))
            )
        )
    )  # worker subprocesses claiming cells from the shared manifest
    soak_ticks: int = field(
        default_factory=lambda: int(os.environ.get("KMAMIZ_SOAK_TICKS", "6"))
    )  # measured ticks per sweep cell (matrix default stays 10)
    soak_archetypes: str = field(
        default_factory=lambda: os.environ.get("KMAMIZ_SOAK_ARCHETYPES", "")
    )  # csv archetype override ("" = all minus subprocess-heavy)
    soak_pass_floor: float = field(
        default_factory=lambda: float(
            os.environ.get("KMAMIZ_SOAK_PASS_FLOOR", "0.9999")
        )
    )  # four nines: non-poison cell pass rate the sweep gates on
    soak_bundle: str = field(
        default_factory=lambda: os.environ.get("KMAMIZ_SOAK_BUNDLE", "")
    )  # recorded WAL bundle dir for the wal-replay archetype ("" = synthesize)

    def __post_init__(self) -> None:
        k8s_host = os.environ.get("KUBERNETES_SERVICE_HOST")
        k8s_port = os.environ.get("KUBERNETES_SERVICE_PORT")
        if self.is_running_in_kubernetes and k8s_host and k8s_port:
            self.kube_api_host = f"https://{k8s_host}:{k8s_port}"


_THRESHOLD_RE = re.compile(r"(?:(\d+)d)?(?:(\d+)h)?(?:(\d+)m)?")


def parse_threshold_ms(threshold: str) -> int:
    """Parse "1d2h30m"-style thresholds to milliseconds
    (reference EndpointDependencies.parseThresholdToMilliseconds)."""
    if not threshold:
        return 0
    m = _THRESHOLD_RE.match(threshold)
    if not m:
        return 0
    days = int(m.group(1) or 0)
    hours = int(m.group(2) or 0)
    minutes = int(m.group(3) or 0)
    return (days * 86400 + hours * 3600 + minutes * 60) * 1000


settings = Settings()
