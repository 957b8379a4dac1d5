"""The central DI container (reference ``container/container.go:26-131``).

Owns the logger, metrics manager, and every configured datasource; creates
each from config at boot exactly like the reference's ``Create``
(``container/container.go:56-131``): Redis/SQL/PubSub gated on their env
keys, plus the net-new TPU backend gated on ``TPU_ENABLED``/``TPU_MODEL``
(SURVEY §2.6: the TPU client is a container member like ``SQL``/``Redis``).
Aggregate health mirrors ``container/health.go:8-28``.
"""

from __future__ import annotations

import time
from typing import Any, Optional

from gofr_tpu.config.env import Config
from gofr_tpu.logging import Level, Logger, RemoteLevelLogger, level_from_string
from gofr_tpu.metrics import Manager, new_metrics_manager


class Container:
    def __init__(self, config: Config, logger: Optional[Logger] = None) -> None:
        self.config = config
        self.app_name = config.get_or_default("APP_NAME", "gofr-tpu-app")
        self.app_version = config.get_or_default("APP_VERSION", "dev")
        self.logger: Logger = logger or Logger(
            level=level_from_string(config.get("LOG_LEVEL"), Level.INFO)
        )
        self.metrics: Manager = new_metrics_manager(self.logger)

        self.sql = None
        self.redis = None
        self.pubsub = None
        self.mongo = None  # injected seam (reference datasource/mongo.go:8)
        self.tpu = None  # net-new: TPU inference backend (SURVEY §2.6)
        self.tpu_embed = None  # secondary encoder engine (TPU_EMBED_MODEL)
        self.services: dict[str, Any] = {}  # name → service.HTTP clients

        self._remote_logger: Optional[RemoteLevelLogger] = None

    # -- creation (reference container/container.go:41-131) --------------

    @classmethod
    def create(cls, config: Config, logger: Optional[Logger] = None) -> "Container":
        c = cls(config, logger=logger)
        c.logger.infof(
            "container created for app %s (version %s)", c.app_name, c.app_version
        )

        remote_url = config.get_or_default("REMOTE_LOG_URL", "")
        if remote_url:
            interval = float(config.get_or_default("REMOTE_LOG_FETCH_INTERVAL", "15"))
            c._remote_logger = RemoteLevelLogger(
                c.logger, remote_url, interval, metrics=c.metrics
            )
            c._remote_logger.start()

        c.register_framework_metrics()

        # Datasources are created lazily-by-config, each in its own module so
        # a missing backend never breaks boot (reference logs and continues).
        from gofr_tpu.datasource.redis import new_redis_from_config

        c.redis = new_redis_from_config(config, c.logger, c.metrics)

        from gofr_tpu.datasource.sql import new_sql_from_config

        c.sql = new_sql_from_config(config, c.logger, c.metrics)

        from gofr_tpu.datasource.pubsub import new_pubsub_from_config

        c.pubsub = new_pubsub_from_config(config, c.logger, c.metrics)

        from gofr_tpu.serving.backend import new_tpu_from_config

        c.tpu = new_tpu_from_config(config, c.logger, c.metrics)

        from gofr_tpu.serving.backend import new_tpu_embed_from_config

        c.tpu_embed = new_tpu_embed_from_config(config, c.logger, c.metrics)
        return c

    def use_mongo(self, client) -> None:
        """User-injected Mongo driver (reference ``gofr.go:376-378``)."""
        self.mongo = client

    def use_pubsub(self, client) -> None:
        """User-injected pub/sub client (same seam as ``use_mongo`` — lets
        apps wire a broker whose driver the framework doesn't bundle)."""
        self.pubsub = client

    # -- service registry (reference gofr.go:189-199) ---------------------

    def get_http_service(self, name: str):
        return self.services.get(name)

    def get_publisher(self):
        return self.pubsub

    def get_subscriber(self):
        return self.pubsub

    # -- framework metrics (reference container/container.go:143-172) -----

    def register_framework_metrics(self) -> None:
        m = self.metrics
        # System / app metrics.
        m.new_gauge("app_go_routines", "number of async tasks + threads")
        m.new_gauge("app_sys_memory_alloc", "resident memory bytes")
        m.new_gauge("app_sys_total_alloc", "total allocated bytes")
        m.new_gauge("app_go_numGC", "gc collection count")
        m.new_gauge("app_go_sys", "runtime sys bytes")
        # HTTP server/client (buckets follow container.go:153-154).
        http_buckets = (0.001, 0.003, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 30)
        m.new_histogram("app_http_response", "HTTP server response time in s", http_buckets)
        m.new_histogram(
            "app_http_service_response", "outbound HTTP client response time in s", http_buckets
        )
        # Redis / SQL (container.go:158-163).
        m.new_histogram(
            "app_redis_stats", "redis command duration in ms",
            (0.05, 0.075, 0.1, 0.125, 0.15, 0.2, 0.3, 0.5, 0.75, 1, 2, 3),
        )
        m.new_histogram(
            "app_sql_stats", "sql query duration in ms",
            (0.05, 0.075, 0.1, 0.125, 0.15, 0.2, 0.3, 0.5, 0.75, 1, 2, 3, 4, 5, 7.5, 10),
        )
        m.new_gauge("app_sql_open_connections", "open sql connections")
        m.new_gauge("app_sql_inUse_connections", "in-use sql connections")
        # PubSub.
        m.new_counter("app_pubsub_publish_total_count", "messages published")
        m.new_counter("app_pubsub_publish_success_count", "publish successes")
        m.new_counter("app_pubsub_subscribe_total_count", "subscribe polls")
        m.new_counter("app_pubsub_subscribe_success_count", "messages handled")
        # Durable async serving plane (serving/async_serving.py;
        # TPU_ASYNC; docs/advanced-guide/resilience.md "Async serving &
        # delivery semantics"): the at-least-once delivery counters and
        # the two live-state gauges the lag control signal reads.
        m.new_counter(
            "app_tpu_async_consumed_total",
            "async request messages consumed (acked) by the serving plane",
        )
        m.new_counter(
            "app_tpu_async_published_total",
            "async reply messages published to the reply topic",
        )
        m.new_counter(
            "app_tpu_async_redelivered_total",
            "async request messages re-leased after a nack or an "
            "expired lease (at-least-once redelivery)",
        )
        m.new_counter(
            "app_tpu_async_dead_lettered_total",
            "async request messages parked on the dead-letter topic "
            "after exhausting their redelivery budget",
        )
        m.new_gauge(
            "app_tpu_async_lag",
            "request-topic backlog (ready messages) the async plane "
            "has not yet leased — the consumer-lag scale signal",
        )
        m.new_gauge(
            "app_tpu_async_inflight_leases",
            "async request messages leased and riding the engine",
        )
        # Net-new TPU serving metrics (SURVEY §2.6 per-chip observability).
        m.new_gauge("app_tpu_queue_depth", "dynamic batcher queue depth")
        m.new_gauge("app_tpu_hbm_used_bytes", "per-chip HBM in use")
        m.new_gauge("app_tpu_kv_slots_in_use", "KV-cache slots occupied")
        m.new_gauge("app_tpu_lora_adapters", "loaded LoRA adapters")
        m.new_histogram(
            "app_tpu_infer_latency", "device execute latency in s",
            (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2, 5),
        )
        m.new_histogram(
            "app_tpu_batch_size", "executed batch sizes",
            (1, 2, 4, 8, 16, 32, 64, 128, 256),
        )
        m.new_counter("app_tpu_tokens_generated", "tokens generated")
        m.new_counter(
            "app_tpu_prefix_hits", "prompts admitted via prefix-KV reuse"
        )
        m.new_gauge(
            "app_tpu_kv_blocks_free", "paged KV cache: free pool blocks"
        )
        # Automatic block-level prefix caching (TPU_AUTO_PREFIX;
        # docs/advanced-guide/prefix-caching.md): radix-index lookups at
        # admission, prompt tokens served by aliased cached blocks
        # instead of re-prefill, and the index's resident block count.
        m.new_counter(
            "app_tpu_prefix_lookup_total",
            "radix prefix-cache lookups at admission (result=hit|miss)",
        )
        m.new_counter(
            "app_tpu_prefix_hit_tokens_total",
            "prompt tokens admission-aliased from cached KV blocks "
            "(prefill skipped)",
        )
        m.new_gauge(
            "app_tpu_prefix_cached_blocks",
            "KV blocks currently held by the radix prefix index",
        )
        # Request-lifecycle resilience (docs/advanced-guide/resilience.md):
        # shedding, cancellation, deadlines, and the scheduler watchdog.
        m.new_counter(
            "app_tpu_requests_shed_total",
            "submits rejected by admission control (429/504 before a slot)",
        )
        m.new_counter(
            "app_tpu_requests_cancelled_total",
            "sequences retired mid-decode by cancel/disconnect",
        )
        m.new_counter(
            "app_tpu_deadline_exceeded_total",
            "sequences retired because their deadline expired",
        )
        m.new_counter(
            "app_tpu_watchdog_trips_total",
            "scheduler watchdog trips (stalled device step)",
        )
        # Self-healing supervision (serving/supervisor.py): warm engine
        # restarts, requests carried across them, and the health state
        # machine (0=SERVING 1=DEGRADED 2=RESTARTING 3=DOWN).
        m.new_counter(
            "app_tpu_engine_restarts_total",
            "supervisor warm restarts after a trip or scheduler crash",
        )
        m.new_counter(
            "app_tpu_requests_replayed_total",
            "in-flight requests replayed across an engine restart",
        )
        m.new_gauge(
            "app_tpu_engine_state",
            "engine health state machine "
            "(0=SERVING 1=DEGRADED 2=RESTARTING 3=DOWN)",
        )
        m.new_gauge(
            "app_http_service_circuit_open",
            "circuit breaker state per downstream service (1 = open)",
        )
        # Replica-tier failover (service/replica_pool.py): per-replica
        # routing state, mid-stream failovers, probe failures, hedges.
        m.new_gauge(
            "app_tpu_replica_state",
            "per-replica routing state "
            "(0=SERVING 1=DEGRADED 2=RESTARTING 3=DOWN/demoted)",
        )
        m.new_counter(
            "app_tpu_failovers_total",
            "in-flight requests adopted by a sibling replica after a "
            "replica died",
        )
        m.new_counter(
            "app_tpu_probe_failures_total",
            "synthetic health probes failed (replica demoted from routing)",
        )
        m.new_counter(
            "app_tpu_hedged_requests_total",
            "unary requests hedged or retried on a second replica",
        )
        # Multi-host data plane (service/replica_pool.py +
        # service/pool_scaler.py): pool composition by routing state,
        # load-adaptive scale events, and remote SSE streams resumed on
        # a sibling after a network loss.
        m.new_gauge(
            "app_tpu_pool_replicas",
            "replica-pool composition by routing state "
            "(serving/degraded/restarting/down/draining)",
        )
        m.new_counter(
            "app_tpu_scale_events_total",
            "pool-scaler resize events (direction=up|down)",
        )
        m.new_counter(
            "app_tpu_remote_stream_failovers_total",
            "remote SSE streams that died mid-stream and resumed on a "
            "sibling replica",
        )
        # Request-lifecycle observability (serving/observability.py;
        # docs/advanced-guide/observability.md): phase-latency
        # histograms — exactly one record per request per phase,
        # computed at retirement from host-side timestamps — and
        # per-window utilization gauges.
        lat_buckets = (
            0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
            1, 2.5, 5, 10, 30,
        )
        m.new_histogram(
            "app_tpu_entry_seconds",
            "HTTP handler took the request up → submit (body parse, "
            "tokenisation, validation)", lat_buckets,
        )
        m.new_histogram(
            "app_tpu_queue_wait_seconds",
            "submit → admission into a KV slot", lat_buckets,
        )
        m.new_histogram(
            "app_tpu_prefill_wait_seconds",
            "admission → the first prefill chunk step's dispatch",
            lat_buckets,
        )
        m.new_histogram(
            "app_tpu_prefill_dispatch_seconds",
            "first prefill chunk step's dispatch → prefill finalize "
            "(one chunk a pass, a window fetch between them)",
            lat_buckets,
        )
        m.new_histogram(
            "app_tpu_first_token_wait_seconds",
            "prefill finalize → first token in hand (the chunk behind "
            "the in-flight windows, its compute, the emit-flush poll)",
            lat_buckets,
        )
        m.new_histogram(
            "app_tpu_delivery_seconds",
            "first token in hand → its SSE chunk written", lat_buckets,
        )
        m.new_histogram(
            "app_tpu_prefill_seconds",
            "admission → prefill finalize (chunked)", lat_buckets,
        )
        m.new_histogram(
            "app_tpu_ttft_seconds",
            "submit → first token emitted", lat_buckets,
        )
        m.new_histogram(
            "app_tpu_inter_token_seconds",
            "per-request mean gap between generated tokens",
            (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1),
        )
        m.new_histogram(
            "app_tpu_e2e_seconds",
            "submit → retirement (whole request)",
            (0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120),
        )
        m.new_gauge(
            "app_tpu_batch_occupancy",
            "live decode slots / total slots, set once per window",
        )
        ratio_buckets = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)
        m.new_histogram(
            "app_tpu_window_occupancy",
            "slots live when a decode window was dispatched / total "
            "slots, one record per processed window", ratio_buckets,
        )
        m.new_histogram(
            "app_tpu_kv_live_ratio",
            "cache positions that were context when a decode window was "
            "dispatched (the live slots' lengths) / slots x max_len, one "
            "record per processed window", ratio_buckets,
        )
        m.new_histogram(
            "app_tpu_decode_read_ratio",
            "positions of every slot the dense decode attention read at a "
            "decode window's last step (the rung that holds the longest "
            "live slot) / max_len, one record per processed window; 1.0: "
            "the whole cache", ratio_buckets,
        )
        m.new_histogram(
            "app_tpu_prefill_attn_visit_ratio",
            "block-steps a prefill chunk step's blocked attention ran (each "
            "row over the blocks of positions up to its own last one) / "
            "rows x the longest row's blocks, one record per dispatched "
            "step of a latent or a hybrid cache; 1.0: every row as deep as "
            "the deepest", ratio_buckets,
        )
        m.new_gauge(
            "app_tpu_kv_bytes_per_token",
            "KV-cache bytes one token holds (all cache entries, keys and "
            "values, scales included): what an operator sizes slots by",
        )
        m.new_histogram(
            "app_tpu_prefill_fill_ratio",
            "prompt tokens in a prefill chunk step / the token rows of "
            "the step that ran (its row count x TPU_PREFILL_CHUNK), one "
            "record per step",
            ratio_buckets,
        )
        m.new_counter(
            "app_tpu_prefill_steps_total",
            "prefill chunk steps dispatched, by the row count the step "
            "ran at (rows: 1 when one row waited, else TPU_PREFILL_BATCH)",
        )
        m.new_counter(
            "app_tpu_moe_routes_total",
            "routes (computed token x expert layer x chosen expert) of a "
            "grouped expert layer, by where the chosen expert lives: held "
            "here, or absent (left out, another chip's share); from counts "
            "the prefill and decode steps return beside their tokens",
        )
        m.new_counter(
            "app_tpu_moe_product_steps_total",
            "dispatched steps of an expert model by the product its expert "
            "layers ran (product=grouped: each expert multiplied by the rows "
            "routed to it; einsum: every expert by every row; picked from "
            "the step's rows) and program (prefill_chunk|decode_window, one "
            "a dispatch)",
        )
        m.new_histogram(
            "app_tpu_moe_expert_load_ratio",
            "rows of the fullest held expert / mean rows of the held "
            "experts, averaged over a prefill step's expert layers, one "
            "record per prefill step whose expert layers ran grouped (1.0: "
            "even load)",
            (1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0, 16.0, 64.0, 256.0),
        )
        # A hybrid stack (sparse attention + lightning layers;
        # docs/advanced-guide/hybrid-sparse-linear-models.md).
        m.new_counter(
            "app_tpu_sparse_attn_queries_total",
            "queries (computed token x sparse layer) of a block-sparse "
            "attention layer by the branch each took: selected (the chosen "
            "blocks of keys only) or dense (under the dense length), and by "
            "program; from counts the steps return beside their tokens",
        )
        m.new_histogram(
            "app_tpu_sparse_attn_read_ratio",
            "positions attended through the choice of blocks / positions in "
            "context, over a decode window's live slots past the dense "
            "length; one record per window that had one", ratio_buckets,
        )
        m.new_gauge(
            "app_tpu_state_bytes_per_slot",
            "bytes a slot holds whatever its length (the lightning layers' "
            "float32 states), from the arrays as allocated; beside "
            "app_tpu_kv_bytes_per_token, which counts what grows with tokens",
        )
        m.new_counter(
            "app_tpu_state_resets_total",
            "prompts whose first chunk started a slot's fixed-size state "
            "from zero (a slot admitted again must not see its former "
            "occupant's)",
        )
        # Disaggregated prefill/decode tiers (TPU_REPLICA_ROLES;
        # docs/advanced-guide/resilience.md): cross-tier KV-block
        # transfers by outcome, their wall-clock cost, and whether the
        # pool is currently serving tiered or fused.
        m.new_counter(
            "app_tpu_tier_transfers_total",
            "prefill→decode KV-block transfers by outcome (result="
            "ok|fused|failed_over|local_fused|expired) and leg "
            "(leg=dma|device|wire|host|none)",
        )
        m.new_counter(
            "app_tpu_tier_transfer_bytes_total",
            "KV-cache bytes shipped by successful tier transfers, per "
            "leg (leg=dma|device|wire|host)",
        )
        m.new_counter(
            "app_tpu_tier_sources_total",
            "remote prefill-source pulls by outcome (kind="
            "hit|miss|rejected|error|expired) — the pull-mode twin of "
            "app_tpu_tier_transfers_total",
        )
        m.new_histogram(
            "app_tpu_tier_transfer_seconds",
            "prefill→decode transfer wall clock (extract→import)",
            (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1, 2.5, 5),
        )
        m.new_gauge(
            "app_tpu_tier_mode",
            "replica-pool serving mode (1 = disaggregated tiers, 0 = "
            "fused)",
        )
        # GSPMD-sharded serving (TPU_TP; docs/advanced-guide/
        # sharded-serving.md): devices per mesh axis (axis label; an
        # unsharded engine reports axis="tp" value 1).
        m.new_gauge(
            "app_tpu_mesh_devices",
            "serving mesh devices per axis (axis=tp|cp; 1 = unsharded)",
        )
        # Device-resource observability (serving/device_telemetry.py;
        # docs/advanced-guide/observability.md "Device-resource
        # signals"): the HBM ledger's per-component bytes and derived
        # headroom, XLA compile accounting with the steady-state
        # recompile counter (a compile after the warm-up fence is
        # always a fixed-shape-discipline bug), and paged-KV pool
        # saturation.
        m.new_gauge(
            "app_tpu_hbm_bytes",
            "HBM ledger bytes by component "
            "(params/lora/kv_pool/prefix_pool/workspace)",
        )
        m.new_gauge(
            "app_tpu_hbm_headroom_ratio",
            "free fraction of the per-device HBM budget "
            "(budget slack + free paged-KV blocks)",
        )
        m.new_counter(
            "app_tpu_compiles_total",
            "XLA program compiles by serving program",
        )
        m.new_histogram(
            "app_tpu_compile_seconds",
            "wall clock of a compiling call (trace + XLA compile — the "
            "latency a request actually pays)",
            (0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120),
        )
        m.new_counter(
            "app_tpu_steady_state_recompiles_total",
            "compiles AFTER the warm-up fence — always a fixed-shape-"
            "discipline bug (graftlint GL015 is the static twin)",
        )
        m.new_gauge(
            "app_tpu_kv_pool_occupancy_ratio",
            "paged KV pool: used blocks / total blocks",
        )
        m.new_gauge(
            "app_tpu_kv_pool_fragmentation_ratio",
            "paged KV pool: radix-cached (reclaimable-under-pressure) "
            "blocks / used blocks",
        )
        # Tenant attribution + SLO burn rates (serving/tenant_ledger.py
        # + serving/slo.py; docs/advanced-guide/observability.md "Tenant
        # attribution & SLOs"). Tenant labels are CLAMPED to the first
        # TPU_TENANT_LABEL_MAX distinct tenants (overflow folds into
        # tenant="_other"; the full table is /debug/tenants) — tenant
        # ids are request-controlled strings and must never become
        # unbounded label cardinality (graftlint GL016 is the static
        # twin of the clamp).
        m.new_counter(
            "app_tpu_tenant_tokens_total",
            "tokens attributed per tenant (phase=prefill|decode; "
            "label-clamped, overflow in tenant=_other)",
        )
        m.new_counter(
            "app_tpu_tenant_kv_block_seconds_total",
            "paged-KV occupancy attributed per tenant "
            "(block·seconds; Σ tenants == pool-wide occupancy integral)",
        )
        m.new_counter(
            "app_tpu_tenant_requests_total",
            "requests per tenant by outcome "
            "(ok|shed|cancelled|deadline|error)",
        )
        m.new_gauge(
            "app_tpu_slo_burn_rate",
            "error-budget burn rate per objective and window "
            "(slo=ttft|e2e|availability, window=5m|1h; 1.0 = spending "
            "exactly the budget)",
        )
        m.new_gauge(
            "app_tpu_slo_compliant",
            "1 while every SLO burn rate is within budget, else 0",
        )
        m.new_gauge(
            "app_tpu_slo_tenant_burn_rate",
            "per-tenant-override burn rate (TPU_SLO_TENANT_* knobs; "
            "label set bounded by configuration, not by traffic)",
        )
        # Brownout overload control (serving/brownout.py; docs/
        # advanced-guide/resilience.md "Brownout & overload control"):
        # the degradation-ladder level, its transitions, and the
        # per-action counters (clamp_tokens / suppress_hedge /
        # skip_probe / shed_<class> — all bounded vocabularies).
        m.new_gauge(
            "app_tpu_brownout_level",
            "brownout degradation level (0 = nominal .. 3 = replica "
            "deprioritized from routing)",
        )
        m.new_counter(
            "app_tpu_brownout_transitions_total",
            "brownout ladder transitions (direction=up|down)",
        )
        m.new_counter(
            "app_tpu_brownout_actions_total",
            "brownout actions taken (action=clamp_tokens|"
            "suppress_hedge|skip_probe|shed_<slo class>)",
        )
        # Scheduler-loop profiler (serving/loop_profiler.py; docs/
        # advanced-guide/observability.md "Scheduler-loop signals"):
        # per-phase wall time of the last scheduler pass (the bounded
        # phase vocabulary sums to pass wall time), the busy fraction
        # over a rolling pass window, the host-bookkeeping share of
        # busy time (THE "is host bookkeeping starving the TPU"
        # signal), and the hysteretic stall-anomaly counter.
        m.new_gauge(
            "app_tpu_loop_phase_seconds",
            "scheduler-loop pass wall seconds by phase (phase=reap|"
            "ledger|brownout|control|sweep|tier_import|prefill|"
            "emit_flush|dispatch|device_window|idle|other; sums to "
            "pass wall time)",
        )
        m.new_counter(
            "app_tpu_loop_phase_seconds_total",
            "scheduler-loop wall seconds by phase, summed over closed "
            "passes (same phase vocabulary; a difference of two scrapes "
            "is the loop's time over exactly that interval)",
        )
        m.new_counter(
            "app_tpu_gc_pause_seconds_total",
            "wall seconds the Python collector ran in this process "
            "(generation=0|1|2)",
        )
        m.new_gauge(
            "app_tpu_loop_utilization",
            "busy fraction of scheduler-loop wall time over the "
            "rolling pass window (1 - idle share)",
        )
        m.new_gauge(
            "app_tpu_loop_host_overhead_ratio",
            "host-bookkeeping share of busy scheduler-loop time "
            "(busy minus the device-window seam, over busy)",
        )
        m.new_counter(
            "app_tpu_loop_stalls_total",
            "scheduler-loop stall anomalies (pass over TPU_LOOP_STALL_S "
            "or TPU_LOOP_STALL_FACTOR x rolling p95; kind=absolute|p95)",
        )
        # The device's own timeline (the loop profiler's watcher: each
        # dispatched program stamped as the device finishes it) and the
        # entry layer's hand-off of each window's tokens.
        device_buckets = (
            0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1,
            2.5, 5,
        )
        m.new_histogram(
            "app_tpu_program_device_seconds",
            "a dispatched program's device time: its ready stamp minus "
            "its start (its dispatch, or the previous program's ready)",
            device_buckets,
        )
        m.new_histogram(
            "app_tpu_program_queued_seconds",
            "a dispatched program's wait behind the programs ahead of it "
            "on the device: its start minus its dispatch", device_buckets,
        )
        m.new_counter(
            "app_tpu_device_seconds_total",
            "device seconds by state: busy (cause=<program>) or idle, "
            "dry with nothing queued (cause=<the loop's phase when it "
            "ran dry>; idle = the loop waited for work)",
        )
        m.new_histogram(
            "app_tpu_token_handoff_seconds",
            "a window's tokens in the scheduler's hand to the stream's "
            "next SSE chunk written (the read, the decode, the write), "
            "one record a window a stream",
            (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1),
        )
        # Control plane (serving/control_plane.py; docs/advanced-guide/
        # resilience.md "Control plane"): per-signal guard health, the
        # per-tenant brownout ladder (label set bounded by the ladder
        # table cap, not by traffic), advertised scale pressure, and
        # the per-loop action counters — all bounded vocabularies.
        m.new_gauge(
            "app_tpu_control_signal_health",
            "control-plane signal guard health (signal=<registered "
            "name>; 1.0 = fresh+finite, 0.5 = riding last-good value, "
            "0.0 = observe-only: the loop it feeds holds state)",
        )
        m.new_gauge(
            "app_tpu_control_tenant_level",
            "per-tenant brownout ladder level (0 = nominal .. 3 = "
            "full shed for that tenant; bounded by "
            "TPU_CONTROL_TENANT_TABLE)",
        )
        m.new_gauge(
            "app_tpu_control_scale_pressure",
            "control-plane scale pressure advertised to the pool "
            "scaler (source=host|predictive; 1 while the loop holds "
            "sustained pressure)",
        )
        m.new_counter(
            "app_tpu_control_actions_total",
            "control-plane actions (loop=tenant_brownout|"
            "host_pressure|predictive, action=enter|exit|clamp_tokens|"
            "thin_admit|shed|scale_pressure)",
        )

    def push_system_metrics(self) -> None:
        """Per-scrape system gauges (reference ``metrics/handler.go:21-35``)."""
        import gc
        import threading

        self.metrics.set_gauge("app_go_routines", threading.active_count())
        try:
            with open("/proc/self/statm") as fp:
                rss = int(fp.read().split()[1]) * 4096
        except Exception:
            rss = 0
        self.metrics.set_gauge("app_sys_memory_alloc", rss)
        self.metrics.set_gauge("app_go_numGC", sum(s.get("collections", 0) for s in gc.get_stats()))

    # -- health (reference container/health.go:8-28) ----------------------

    def health(self) -> dict:
        out: dict[str, Any] = {
            "name": self.app_name,
            "version": self.app_version,
            "status": "UP",
            "startedAt": getattr(self, "_started_at", ""),
        }
        details: dict[str, Any] = {}
        for name in ("sql", "redis", "pubsub", "tpu", "tpu_embed", "mongo"):
            ds = getattr(self, name)
            if ds is None or not hasattr(ds, "health_check"):
                # health_check is opt-in for injected clients (use_mongo /
                # use_pubsub) — a minimal client must not flip the app to
                # DEGRADED just for lacking one.
                continue
            try:
                check = ds.health_check()
            except Exception as exc:
                check = {"status": "DOWN", "error": str(exc)}
            details[name] = check
            if check.get("status") != "UP":
                out["status"] = "DEGRADED"
        for svc_name, svc in self.services.items():
            try:
                check = svc.health_check()
            except Exception as exc:
                check = {"status": "DOWN", "error": str(exc)}
            details[f"service:{svc_name}"] = check
            if check.get("status") != "UP":
                out["status"] = "DEGRADED"
        out["details"] = details
        return out

    def mark_started(self) -> None:
        self._started_at = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())

    async def close(self) -> None:
        for name in ("sql", "redis", "pubsub", "tpu", "tpu_embed", "mongo"):
            ds = getattr(self, name)
            if ds is not None and hasattr(ds, "close"):
                try:
                    res = ds.close()
                    if hasattr(res, "__await__"):
                        await res
                except Exception:
                    pass
        if self._remote_logger is not None:
            self._remote_logger.stop()
