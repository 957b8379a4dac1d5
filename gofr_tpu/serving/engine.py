"""The TPU inference engine (net-new; SURVEY §2.6).

The container's ``tpu`` member (role of ``gofr.TPU()`` in the north star):
owns the model params on device, the jitted prefill/decode steps, the slot
KV cache, and the scheduler that turns concurrent requests into batched
device executions.

Design:

* **LLM family — continuous batching.** A dedicated scheduler thread admits
  pending prompts into free KV slots (prefill, bucketed padding) and steps
  ALL slots through one fused decode+sample kernel per token. Device-side
  sampling (per-slot temperature array + greedy mask inside the jit) means
  only ``[n_slots] int32`` crosses the host boundary per step. Cache buffers
  are donated so XLA updates them in place.
* **Encoder / vision families — dynamic batching.** Requests coalesce in a
  :class:`DynamicBatcher` (size/deadline flush) and execute as one padded
  batch.
* **Observability** rides the framework metrics registry: queue depth, KV
  slots in use, batch sizes, infer latency, tokens generated, HBM gauges.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import math
import os
import queue
import threading

import time
from functools import partial
from typing import Any, AsyncIterator, Callable, Optional

import numpy as np

from gofr_tpu.analysis import lockcheck
from gofr_tpu import faults
from gofr_tpu.serving.batcher import DynamicBatcher
from gofr_tpu.serving.tokenizer import tokenizer_from_config

from gofr_tpu.serving.lifecycle import (
    AggregateThroughput,
    ClassPriorityQueue,
    CancelToken,
    Deadline,
    coalesce_deadline,
)
from gofr_tpu.serving.lora_runtime import LoRARuntimeMixin
from gofr_tpu.serving.modalities import ModalityMixin
from gofr_tpu.serving.programs import LLMProgramsMixin
from gofr_tpu.serving.scheduler import SchedulerMixin
from gofr_tpu.serving.types import (
    _ActiveSeq,
    _GenRequest,
    _PrefillState,
    GenerationResult,
    LOGIT_BIAS_K,
    next_token,
)
from gofr_tpu.serving.watchdog import Watchdog


class InferenceEngine(
    LLMProgramsMixin, SchedulerMixin, LoRARuntimeMixin, ModalityMixin
):
    """One loaded model + its serving machinery (facade over the
    program-builder, scheduler, adapter-runtime, and modality
    mixins)."""

    def __init__(
        self,
        model_name: str,
        *,
        n_slots: int = 8,
        max_len: int = 1024,
        max_batch: int = 8,
        max_wait_s: float = 0.005,
        window_k: int = 8,
        pipeline_depth: int = 2,
        prefill_chunk: int = 256,
        prefill_batch: int = 8,
        truncate_prompts: bool = False,
        top_k: int = 0,
        enable_top_p: bool = False,
        enable_penalties: bool = False,
        top_logprobs: int = 0,
        kv_block: int = 0,
        kv_pool_blocks: int = 0,
        auto_prefix: bool = False,
        prefix_cache_blocks: int = 0,
        prefix_evict_watermark: int = 0,
        prefix_evict_hbm_frac: float = 0.0,
        admit_min_headroom: float = 0.0,
        hbm_budget_bytes: int = 0,
        mesh: Any = None,
        tp: int = 0,
        devices: Any = None,
        quant: str = "",
        kv_quant: str = "",
        prefix_slots: int = 0,
        lora_slots: int = 0,
        lora_rank: int = 16,
        lora_targets: str = "wq,wk,wv,wo",
        queue_max: int = 1024,
        queue_max_tokens: int = 0,
        class_promote_s: float = 5.0,
        tenant_queue_max: int = 0,
        tenant_ledger: Optional[bool] = None,
        tenant_label_max: int = 8,
        tenant_table_max: int = 256,
        tenant_fair_share: float = 0.0,
        slo_ttft_ms: float = 0.0,
        slo_e2e_ms: float = 0.0,
        slo_availability: float = 0.0,
        slo_tenant_objectives: Optional[dict] = None,
        brownout: Optional[bool] = None,
        brownout_enter: float = 2.0,
        brownout_exit: float = 1.0,
        brownout_sustain_s: float = 10.0,
        brownout_exit_sustain_s: float = 30.0,
        brownout_max_new: int = 256,
        brownout_aimd_cut: float = 0.5,
        brownout_recover_per_s: float = 0.02,
        brownout_min_headroom: float = 0.0,
        control_plane: Optional[bool] = None,
        control_stale_s: float = 10.0,
        control_tenant_enter: float = 2.0,
        control_tenant_exit: float = 1.0,
        control_tenant_sustain_s: float = 10.0,
        control_tenant_exit_sustain_s: float = 30.0,
        control_tenant_max_new: int = 256,
        control_tenant_aimd_cut: float = 0.5,
        control_tenant_recover_per_s: float = 0.02,
        control_tenant_table: int = 64,
        control_host_ratio: float = 0.85,
        control_host_util: float = 0.75,
        control_host_sustain_s: float = 30.0,
        control_predict_window_s: float = 60.0,
        control_predict_horizon_s: float = 30.0,
        control_predict_depth: float = 0.0,
        control_predict_hold_s: float = 30.0,
        queue_prefix_aware: bool = False,
        tenant_slo_class: str = "",
        expected_tps: float = 0.0,
        watchdog_s: float = 0.0,
        replay_exact: bool = True,
        flight_recorder: Optional[bool] = None,
        flight_records: int = 256,
        flight_slow_s: float = 5.0,
        loop_profile: Optional[bool] = None,
        loop_stall_s: float = 1.0,
        loop_stall_factor: float = 10.0,
        loop_anomalies: int = 64,
        loop_trace_ms: int = 0,
        loop_trace_cooldown_s: float = 60.0,
        params: Any = None,
        logger: Any = None,
        metrics: Any = None,
        tokenizer: Any = None,
        seed: int = 0,
    ) -> None:
        import jax
        import jax.numpy as jnp

        from gofr_tpu.models.registry import get_model

        self._jax, self._jnp = jax, jnp
        self.model_name = model_name
        self.spec = get_model(model_name)
        self.family = self.spec.family
        self.cfg = self.spec.config
        self._logger = logger
        self._metrics = metrics
        self._top_k = top_k
        # Nucleus sampling support is a COMPILE choice: the per-step
        # [slots, vocab] sort only exists in the program when enabled.
        self.enable_top_p = bool(enable_top_p)
        # Frequency/presence penalties are a COMPILE choice too: the
        # [slots, vocab] generated-token count plane and its per-step
        # scatter only exist in the program when enabled.
        self.enable_penalties = bool(enable_penalties)
        # OpenAI top_logprobs alternatives: a compile choice — the per-
        # step [slots, vocab] top_k only exists in the program when >0.
        self.top_logprobs = max(0, top_logprobs)
        n_passes = getattr(self.cfg, "n_passes", 1)
        if n_passes > 1 and self.cfg.exit_threshold < 1.0:
            raise ValueError(
                f"{model_name}: exit_threshold={self.cfg.exit_threshold} "
                f"< 1 is not served: slots would leave the {n_passes}-pass "
                "stack at different passes, and the decode window and the "
                "scheduler run every slot to the same depth (only 1.0, "
                "every pass, is implemented)"
            )
        if quant and getattr(self.cfg, "counts_routes", False):
            raise ValueError(
                f"{model_name}: TPU_QUANT={quant} is not served: the grouped "
                "expert product of a layer that holds a share of the experts "
                "(_ffn_moe_grouped) takes bf16 weights (jax.lax.ragged_dot "
                "has no int8 / int4 operand; a stacked all-held expert "
                "layer's products take quantised leaves)"
            )
        self._refuse_for_cache(
            tp=int(tp or 0) > 1 or mesh is not None,
            kv_block=int(kv_block or 0) > 0, auto_prefix=bool(auto_prefix),
            prefix_slots=int(prefix_slots or 0) > 0, kv_quant=kv_quant,
            quant=quant,
            lora_slots=lora_targets if int(lora_slots or 0) > 0 else "",
        )
        self.tokenizer = tokenizer
        # GSPMD-sharded serving (TPU_TP): a caller may hand a pre-built
        # mesh (dryruns, tests composing tp×cp), or just a tp degree —
        # then the engine owns its topology, carving a {"tp": tp} mesh
        # from ``devices`` (the replica-pool pod layout: dp across
        # replicas, tp within each) or the process device list. The
        # shard-init window (mesh build + param sharding + sharded
        # quantization) is timed and emitted as a ``tpu.shard_init``
        # span so slow boots are attributable.
        shard_t0 = time.time_ns()
        if mesh is None and int(tp or 0) > 1:
            from gofr_tpu.parallel.mesh import make_mesh

            mesh = make_mesh({"tp": int(tp)}, devices=devices)
        self.mesh = mesh  # multi-chip: NamedSharding placement over ICI
        from gofr_tpu.parallel.mesh import mesh_axis_sizes

        self.tp = (
            mesh_axis_sizes(mesh).get("tp", 1) if mesh is not None else 1
        )
        # The device(s) this engine lives on — what health, the HBM
        # gauges and the ledger's platform cross-check read (never
        # ``jax.devices()[0]``: replica i of a pool is not on chip 0).
        # A mesh brings its own; an unsharded engine handed ``devices``
        # (replica i of a TPU_REPLICAS pool) is PINNED to ``devices[0]``:
        # params, cache and uploads are committed there and every jit
        # follows its committed operands. Without ``devices`` it lives on
        # the process default device, uncommitted, as before.
        self._device: Any = None
        if mesh is not None:
            self.devices = [
                d for d in mesh.devices.flat
                if d.process_index == jax.process_index()
            ]
        elif devices:
            self._device = devices[0]
            self.devices = [self._device]
        else:
            self.devices = [jax.local_devices()[0]]

        t0 = time.time()
        self.quant = ""
        if params is not None:
            # Pre-built params (e.g. a real-weights checkpoint loaded via
            # serving/hf_loader, possibly already int8/int4).
            from gofr_tpu.serving.hf_loader import params_quant_mode

            self.params = self._commit(params)
            self.quant = params_quant_mode(params)
        elif mesh is not None and self.family == "llm":
            # Sharded init: params materialize directly onto the mesh with
            # their Megatron-style partition specs — never gathered on one
            # chip (an 8B model doesn't fit one v5e).
            from gofr_tpu.models.transformer import transformer_param_specs
            from gofr_tpu.parallel.sharding import named_shardings, prune_specs

            shardings = named_shardings(
                prune_specs(transformer_param_specs(self.cfg), mesh), mesh
            )
            self.params = jax.jit(
                lambda k: self.spec.init(k, self.cfg), out_shardings=shardings
            )(jax.random.PRNGKey(seed))
        elif (quant or "").lower() in ("int8", "int4") and self.family == "llm":
            # Init DIRECTLY quantized, leaf by leaf: peak HBM is the
            # quantized tree plus one bf16 leaf — llama-3-8b's full bf16
            # tree (~16GB) would not fit a single v5e (VERDICT r1 #4).
            self.quant = (quant or "").lower()
            with self._placement():
                self.params = self._commit(self._init_llm_quantized(seed))
        else:
            with self._placement():
                self.params = self._commit(
                    self.spec.init(jax.random.PRNGKey(seed), self.cfg)
                )

        if quant and not self.quant:
            self.apply_quantization(quant)

        if mesh is not None:
            # Mesh topology observability: the per-axis device gauge
            # (dashboards show pod shape per model) and the completed
            # shard-init span covering mesh build + param sharding.
            from gofr_tpu.serving.observability import emit_boot_span

            if metrics is not None:
                for axis, size in mesh_axis_sizes(mesh).items():
                    metrics.set_gauge(
                        "app_tpu_mesh_devices", size,
                        "model", model_name, "axis", axis,
                    )
            emit_boot_span(
                "tpu.shard_init", shard_t0, time.time_ns(),
                attributes={
                    "tpu.model": model_name,
                    "tpu.mesh_axes": ",".join(
                        f"{a}={n}" for a, n in mesh_axis_sizes(mesh).items()
                    ),
                    "tpu.mesh_devices": int(mesh.devices.size),
                },
            )
        elif metrics is not None:
            # Unsharded engines advertise tp=1 so the gauge is uniform
            # across a mixed fleet.
            metrics.set_gauge(
                "app_tpu_mesh_devices", 1, "model", model_name,
                "axis", "tp",
            )

        if logger is not None:
            from gofr_tpu.models.transformer import count_params

            n_params = count_params(self.params)
            logger.infof(
                "model %s initialised: %.2fB params in %.1fs",
                model_name, n_params / 1e9, time.time() - t0,
            )

        self._seed = seed
        self._key = jax.random.PRNGKey(seed + 1)
        self._running = False
        self._draining = False  # graceful stop: reject new, finish live
        self._sched_idle = False  # published by the scheduler, read by drain
        self._fatal: Optional[BaseException] = None  # scheduler death reason
        # Serializes submission against the scheduler's final drain, so a
        # request can never be enqueued after the drain has already run.
        self._submit_lock = lockcheck.make_lock("InferenceEngine._submit_lock")
        self._drained = False
        # Supervision (serving/supervisor.py): the attached supervisor (if
        # any) owns the restart policy; the scheduler epoch brands each
        # scheduler thread so one abandoned mid-wedge can never drain or
        # dispatch against a restarted engine's state; salvaged retryable
        # requests park in _replay until the supervisor requeues them.
        self._supervisor: Optional[Any] = None
        self._epoch = 0
        self._replay: list[_GenRequest] = []
        self._restart_pending = False  # supervisor teardown in progress
        # Replica-tier failover (service/replica_pool.py): when this
        # engine is one replica of a pool, the pool installs a handoff —
        # terminal failure paths offer still-retryable requests to it
        # (the pool requeues them on another replica) before failing
        # them. None outside a pool: failures stay terminal.
        self._handoff: Optional[Any] = None
        # Disaggregated prefill/decode tier (TPU_REPLICA_ROLES): the
        # pool stamps this engine's role and, for prefill-tier
        # replicas, installs an exporter — the scheduler offers it
        # every just-finalized prefill (request + extracted KV-block
        # payload) instead of decoding locally; the pool ships the
        # blocks to a decode replica. "fused" (the default) serves both
        # phases locally, exactly as before this tier existed.
        self.tier_role: str = "fused"
        self._tier_exporter: Optional[Any] = None
        # Sampled-stream replay policy (TPU_REPLAY_EXACT): True (default)
        # regenerates the delivered prefix through the decode path —
        # byte-identical continuation at the cost of re-decoding it;
        # False re-prefills prompt + delivered tokens and restores the
        # sampling COUNTER (the noff plane) — one prefill pass, same
        # sample path, but prefill-kernel bf16 K/V rounding may flip a
        # later token. Greedy replays always take the fast path.
        self.replay_exact = bool(replay_exact)
        # Health state machine (SERVING → DEGRADED → RESTARTING → DOWN),
        # surfaced via health_check / both gRPC Health RPCs and the
        # app_tpu_engine_state gauge. DOWN until start_sync.
        self._state = "DOWN"
        # Set by the scheduler when it publishes "verifiably idle" and on
        # exit; the graceful drain clears it (under the submit lock)
        # before waiting, so a stale set from an earlier idle period
        # cannot satisfy a new drain. It is a drain wake-up only — while
        # the engine is busy it may still be set from before.
        self._idle_evt = threading.Event()
        # Admission control: token-budget accounting over the submit
        # queue (guarded by the submit lock like every other admission
        # flag) plus a throughput estimate for projected-wait shedding.
        self.queue_max = max(1, queue_max)
        self.queue_max_tokens = max(0, queue_max_tokens)
        # Per-SLO-class priority dequeue (TPU_QUEUE_CLASS_PROMOTE_S):
        # interactive pops ahead of queued standard/batch work, with
        # the promotion window as the starvation bound. 0 = strict
        # FIFO, the pre-class order.
        self.class_promote_s = max(0.0, class_promote_s)
        self._queued_tokens = 0
        self._expected_tps = max(0.0, expected_tps)
        # Sliding-window AGGREGATE tokens/sec across the whole batch —
        # the shedding denominator. (The previous per-request EWMA
        # underestimated batched throughput by ~the batch size and shed
        # correspondingly too eagerly.)
        self._tput = AggregateThroughput()
        # Per-tenant admission quota (TPU_TENANT_QUEUE_MAX): queued
        # request count per X-Tenant-Id, guarded by the submit lock.
        self.tenant_queue_max = max(0, tenant_queue_max)
        self._tenant_queued: dict[str, int] = {}
        # Watchdog: latched unhealthy reason, reported by health_check
        # and set (under the submit lock) by the trip callback.
        self._unhealthy_reason: Optional[str] = None
        self._watchdog: Optional[Watchdog] = None
        if watchdog_s > 0:
            self._watchdog = Watchdog(
                watchdog_s,
                on_trip=self._on_watchdog_trip,
                metrics=metrics,
                logger=logger,
                model_name=model_name,
            )

        # Request-lifecycle observability (serving/observability.py):
        # the hub mints per-request timelines, owns the flight recorder,
        # and summarizes phases into histograms/spans at retirement. It
        # deliberately lives OUTSIDE _init_llm_serving_state so the
        # recorder's history survives supervisor warm restarts (the
        # replay/failover annotations are exactly what an operator wants
        # to see after one). TPU_FLIGHT_RECORDER=0 disables the ring —
        # the bench overhead A/B knob.
        if flight_recorder is None:
            flight_recorder = os.environ.get(
                "TPU_FLIGHT_RECORDER", "1"
            ).lower() not in ("0", "false", "no")
        from gofr_tpu.serving.observability import (
            FlightRecorder,
            RequestObservability,
        )

        self._obs = RequestObservability(
            model_name,
            metrics=metrics,
            passes=n_passes,
            model_attrs={
                **({"experts_held": self.cfg.experts_held,
                    "router_width": self.cfg.n_experts}
                   if getattr(self.cfg, "counts_routes", False) else {}),
                **({"cache_row": self.cfg.cache_row}
                   if getattr(self.cfg, "is_latent", False) else {}),
                **({"layer_kinds": " ".join(
                        f"{n}x{kind}" for kind, n in self.cfg.layer_runs),
                    "state_bytes": self.cfg.state_bytes_per_slot,
                    "blocks_chosen": self.cfg.sparse_topk}
                   if getattr(self.cfg, "is_hybrid", False) else {}),
            },
            recorder=(
                FlightRecorder(
                    capacity=max(1, flight_records),
                    slow_s=flight_slow_s,
                )
                if flight_recorder else None
            ),
        )

        # Tenant attribution + SLO burn rates (serving/tenant_ledger.py
        # + serving/slo.py; docs/advanced-guide/observability.md "Tenant
        # attribution & SLOs"). Like the flight recorder, both live
        # OUTSIDE _init_llm_serving_state so attribution and burn state
        # survive supervisor warm restarts. TPU_TENANT_LEDGER=0 removes
        # the whole attribution layer — every scheduler hook degrades to
        # one `is not None`.
        if tenant_ledger is None:
            tenant_ledger = os.environ.get(
                "TPU_TENANT_LEDGER", "1"
            ).lower() not in ("0", "false", "no")
        from gofr_tpu.serving.tenant_ledger import TenantLedger

        self._tenant_ledger: Optional[TenantLedger] = (
            TenantLedger(
                model_name,
                metrics=metrics,
                label_max=tenant_label_max,
                table_max=tenant_table_max,
            )
            if tenant_ledger else None
        )
        # Fairness-aware shedding (TPU_TENANT_FAIR_SHARE, off by
        # default): the fraction of the queue budget one tenant may
        # hold before admission sheds IT (429 reason=tenant_fair_share)
        # instead of letting its burst exhaust the global budget for
        # everyone. Needs the ledger (the share denominator).
        self.tenant_fair_share = max(0.0, min(1.0, tenant_fair_share))
        from gofr_tpu.serving.slo import SLOEngine

        # Control-plane master switch, resolved HERE because the
        # SLOEngine below needs to know whether to auto-track per-tenant
        # burn rings (the per-tenant brownout loop's signal). Off
        # (TPU_CONTROL_PLANE=0) builds nothing: no tracking, no
        # controller, every hook one `is not None`.
        if control_plane is None:
            control_plane = os.environ.get(
                "TPU_CONTROL_PLANE", "1"
            ).lower() not in ("0", "false", "no")
        self._slo: Optional[SLOEngine] = None
        if (
            slo_ttft_ms > 0 or slo_e2e_ms > 0 or slo_availability > 0
            or slo_tenant_objectives
        ):
            self._slo = SLOEngine(
                model_name,
                ttft_ms=slo_ttft_ms,
                e2e_ms=slo_e2e_ms,
                availability=slo_availability,
                tenant_objectives=slo_tenant_objectives,
                track_tenants=(
                    max(0, int(control_tenant_table))
                    if control_plane else 0
                ),
                metrics=metrics,
            )
        # The observability hub feeds every retired timeline's phases
        # into the burn-rate engine (and keeps minting timelines even
        # when recorder/metrics/exporter are all off, so SLOs alone
        # still see every request).
        self._obs.slo = self._slo
        # Closed-loop overload control (serving/brownout.py; docs/
        # advanced-guide/resilience.md "Brownout & overload control"):
        # the burn-rate-driven degradation ladder. Needs the SLOEngine
        # (the burn rate IS the control signal); TPU_BROWNOUT=0 builds
        # no controller — every hook degrades to one `is not None` and
        # today's behavior is byte-identical.
        from gofr_tpu.serving.brownout import (
            BrownoutController,
            normalize_slo_class,
            parse_tenant_class_map,
        )

        self._normalize_slo_class = normalize_slo_class
        self._tenant_class_map = parse_tenant_class_map(tenant_slo_class)
        if brownout is None:
            brownout = os.environ.get(
                "TPU_BROWNOUT", "1"
            ).lower() not in ("0", "false", "no")
        self._brownout: Optional[BrownoutController] = (
            BrownoutController(
                model_name,
                enter_burn=brownout_enter,
                exit_burn=brownout_exit,
                sustain_s=brownout_sustain_s,
                exit_sustain_s=brownout_exit_sustain_s,
                max_new_tokens=brownout_max_new,
                aimd_cut=brownout_aimd_cut,
                recover_per_s=brownout_recover_per_s,
                min_headroom=brownout_min_headroom,
                metrics=metrics,
                logger=logger,
            )
            if brownout and self._slo is not None else None
        )

        # Continuous scheduler-loop profiler (serving/loop_profiler.py;
        # docs/advanced-guide/observability.md "Scheduler-loop
        # signals"): per-phase wall-time attribution for every
        # scheduler pass, the loop-utilization / host-overhead-ratio
        # signals, and the hysteretic stall detector whose anomaly
        # records land on /debug/loop (optionally auto-capturing a
        # bounded device trace through the profiler_capture singleton).
        # Lives OUTSIDE _init_llm_serving_state like the flight
        # recorder — rolling stats and anomaly rings survive supervisor
        # warm restarts. TPU_LOOP_PROFILE=0 builds no profiler: every
        # scheduler phase runs in one shared no-op context and the loop
        # is byte-identical to the pre-profiler scheduler.
        if loop_profile is None:
            loop_profile = os.environ.get(
                "TPU_LOOP_PROFILE", "1"
            ).lower() not in ("0", "false", "no")
        self._loop_prof: Any = None
        if loop_profile and self.family == "llm":
            from gofr_tpu.serving.loop_profiler import LoopProfiler

            trace_capture = None
            if loop_trace_ms > 0:
                from gofr_tpu.serving.profiler_capture import get_capture

                trace_capture = get_capture(
                    cooldown_s=loop_trace_cooldown_s
                )
            self._loop_prof = LoopProfiler(
                model_name,
                stall_s=loop_stall_s,
                stall_factor=loop_stall_factor,
                anomaly_records=loop_anomalies,
                trace_ms=loop_trace_ms,
                capture=trace_capture,
                metrics=metrics,
                logger=logger,
                clock=self._obs.now,
            )
            self._loop_prof.context = self._loop_context

        # Device-resource observability (serving/device_telemetry.py):
        # the compile tracker wraps every jitted serving program built
        # below (so it must exist before the family branch), and the
        # HBM ledger is built with the serving state (its component
        # sizes are fixed per boot). The tracker captures the ambient
        # trace context HERE — warm-up compiles fire on the scheduler
        # thread, but their tpu.compile spans belong to the boot trace.
        from gofr_tpu.serving.device_telemetry import CompileTracker

        self._compiles = CompileTracker(
            model_name, metrics=metrics, logger=logger
        )
        cache_dir = jax.config.jax_compilation_cache_dir
        if cache_dir:
            # Persistent compile-cache provenance for health and
            # /debug/capacity. The engine only REPORTS it: the directory
            # is JAX_COMPILATION_CACHE_DIR or the process entry point's
            # default (gofr_tpu/compile_cache.py), placed before the
            # first jit — JAX keeps whichever directory its first
            # compile saw.
            self._compiles.set_cache_info({
                "dir": cache_dir,
                "enabled": bool(jax.config.jax_enable_compilation_cache),
            })
        if self._loop_prof is not None:
            # A pass during which XLA compiled is the compile tracker's
            # to attribute — the loop profiler's stall detector exempts
            # it (or every boot would open with a pinned anomaly).
            self._loop_prof.compiles = lambda: self._compiles.total
        self._ledger: Any = None
        # Saturation-aware control knobs (docs/advanced-guide/
        # observability.md "Device-resource signals"): the HBM-fraction
        # eviction watermark (TPU_PREFIX_EVICT_WM stays the explicit
        # override), admission's headroom floor, and the operator's
        # explicit per-device HBM budget for backends whose
        # memory_stats() reports nothing.
        self.prefix_evict_hbm_frac = max(0.0, prefix_evict_hbm_frac)
        self.admit_min_headroom = max(0.0, admit_min_headroom)
        self.hbm_budget_bytes = max(0, hbm_budget_bytes)
        self.effective_evict_watermark = 0
        # Prefix-hit-aware admission ordering (TPU_QUEUE_PREFIX_AWARE,
        # off by default): within one SLO class, pop requests with a
        # known radix-prefix hit first. Read by
        # _init_llm_serving_state's queue build (survives warm restart).
        self.queue_prefix_aware = bool(queue_prefix_aware)

        if self.family == "llm":
            self.max_len = min(max_len, self.cfg.max_len)
            self.n_slots = n_slots
            self.window_k = max(1, window_k)
            self.pipeline_depth = max(1, pipeline_depth)
            # Chunked prefill: [rows, prefill_chunk] steps serve every
            # prompt length, rows chosen at each dispatch from the rungs
            # 1 and prefill_batch by how many rows wait (both compiled
            # before the engine serves, programs.py); chunk steps
            # interleave with decode windows so admission never stalls
            # active streams.
            self.prefill_chunk = max(16, min(prefill_chunk, self.max_len))
            self.prefill_batch = max(1, min(prefill_batch, n_slots))
            self.truncate_prompts = truncate_prompts
            reserve = 1 + (self.pipeline_depth + 1) * self.window_k
            if self.max_len <= reserve:
                raise ValueError(
                    f"max_len={self.max_len} too small: need > {reserve} "
                    f"(1 + (pipeline_depth+1)*window_k) so "
                    f"admission can reserve pipelined-window overshoot "
                    f"room; lower window_k/pipeline_depth or "
                    f"raise max_len"
                )
            self.kv_quant = (kv_quant or "").lower()
            # Paged KV (TPU_KV_BLOCK>0): block-pool cache + host allocator
            # — HBM scales with resident tokens, not slots × max_len.
            self.kv_block = max(0, kv_block)
            self.kv_pool_blocks = kv_pool_blocks
            self.prefix_slots = max(0, prefix_slots)
            # Automatic block-level prefix caching (TPU_AUTO_PREFIX):
            # retired prompts' full KV blocks stay indexed in a radix
            # trie and later requests admission-alias them into their
            # block table — zero-copy hits, refcounted sharing, COW'd
            # boundary (serving/radix_cache.py + docs/advanced-guide/
            # prefix-caching.md). Paged-cache only: sharing IS table
            # aliasing.
            self.auto_prefix = bool(auto_prefix)
            self.prefix_cache_blocks = max(0, prefix_cache_blocks)
            # Prefix-cache eviction watermark (TPU_PREFIX_EVICT_WM):
            # keep at least this many pool blocks FREE by sweeping LRU
            # radix entries from the scheduler loop, so admission under
            # pressure stops paying the synchronous pre-evict cost
            # inside its own grow. 0 = off (evict only on shortfall).
            self.prefix_evict_watermark = max(0, prefix_evict_watermark)
            if self.auto_prefix and not self.kv_block:
                raise ValueError(
                    "TPU_AUTO_PREFIX requires the paged KV cache "
                    "(TPU_KV_BLOCK > 0): prefix hits alias pool blocks "
                    "through the block table"
                )
            if self.kv_block:
                if self.max_len % self.kv_block:
                    raise ValueError(
                        f"max_len={self.max_len} must be a multiple of "
                        f"kv_block={self.kv_block}"
                    )
                if prefix_slots > 0:
                    raise ValueError(
                        "prefix-KV reuse and the paged cache are mutually "
                        "exclusive (the pool copies slot rows; use "
                        "TPU_AUTO_PREFIX for paged prefix sharing)"
                    )
            # Prefix-cache observability counters (host-side mirrors of
            # app_tpu_prefix_{lookup,hit_tokens}_total so bench/tests
            # read them without a metrics manager). Cumulative across
            # warm restarts — the INDEX resets with the cache planes,
            # these do not.
            self._prefix_lookups = 0
            self._prefix_hit_tokens = 0
            self._prefill_chunk_steps = 0
            self._sched: Optional[threading.Thread] = None
            # Host→device uploads: on a mesh, place as a REPLICATED global
            # array — on a multi-host (DCN) mesh a bare jnp.asarray would
            # make a process-local array that cannot feed the global-SPMD
            # jits (every process runs this same code with the same host
            # values, so replicated placement is well-defined).
            if mesh is not None:
                from jax.sharding import (
                    NamedSharding as _NS,
                    PartitionSpec as _P,
                )

                _rep = _NS(mesh, _P())
                self._up = lambda x: jax.device_put(x, _rep)  # noqa: E731
            elif self._device is not None:
                self._up = partial(jax.device_put, device=self._device)
            else:
                self._up = jnp.asarray
            # Multi-PROCESS mesh on a non-TPU backend: serialize device
            # programs. A real TPU core executes one program at a time, so
            # identical per-process launch order is enough for its
            # collectives to pair up; the CPU backend's gloo collectives
            # run on a thread pool, and two in-flight programs (pipelined
            # windows, prefill overlapping decode) interleave their
            # collectives nondeterministically across ranks — observed as
            # gloo "Received data size doesn't match expected size".
            self._lockstep = False
            multiproc = False
            if mesh is not None:
                procs = {d.process_index for d in mesh.devices.flat}
                multiproc = len(procs) > 1
                self._lockstep = (
                    multiproc and jax.default_backend() != "tpu"
                )
            # Host-side default-seed source for requests without one: each
            # unseeded request gets a fresh draw (OpenAI semantics), while
            # an explicit seed reproduces exactly. Single-process engines
            # mix in boot entropy so restarts/replicas don't replay; a
            # multi-PROCESS mesh keeps the engine-seed-derived stream —
            # every rank must draw IDENTICAL defaults or the SPMD
            # schedulers diverge (set distinct TPU seeds per replica
            # group for cross-replica variety).
            import random as _random

            self._seed_rng = (
                _random.Random(seed + 3) if multiproc
                else _random.Random(os.urandom(16))
            )
            # Multi-LoRA serving: merge zeroed stacked adapter leaves
            # into params["layers"] (slot 0 = base; load_lora fills
            # slots 1..lora_slots). A COMPILE choice: engines without
            # TPU_LORA_SLOTS carry no adapter gather/einsums at all.
            self.lora_slots = max(0, lora_slots)
            self.lora_rank = max(1, lora_rank)
            self._lora_targets = tuple(
                t.strip() for t in lora_targets.split(",") if t.strip()
            )
            self._lora_names: dict[str, int] = {}
            # Per-adapter-slot load generation: bumped by every load/
            # unload so in-flight prefix registrations against an old
            # generation can be detected and dropped.
            self._lora_gen = [0] * (self.lora_slots + 1)
            if self.lora_slots:
                from gofr_tpu.models.transformer import (
                    init_lora,
                    lora_param_specs,
                )

                leaves = init_lora(
                    self.cfg, 1 + self.lora_slots, self.lora_rank,
                    self._lora_targets,
                )
                if mesh is not None:
                    from gofr_tpu.parallel.sharding import (
                        named_shardings,
                        prune_specs,
                    )

                    lspecs = prune_specs(
                        lora_param_specs(self._lora_targets), mesh
                    )
                    leaves = {
                        k: jax.device_put(
                            v, named_shardings(lspecs[k], mesh)
                        )
                        for k, v in leaves.items()
                    }
                self.params = {
                    **self.params,
                    "layers": {**self.params["layers"], **leaves},
                }
            # Per-boot serving state (KV cache, allocator, queues, device
            # planes) lives in its own method so the supervisor's warm
            # restart can rebuild it without re-initializing params or
            # recompiling programs.
            self._init_llm_serving_state()
            self._build_llm_steps()
        elif self.family == "encoder":
            self.max_len = min(max_len, self.cfg.max_len)
            self._build_encoder_step()
            self._batcher = DynamicBatcher(
                self._execute_embed, max_batch=max_batch, max_wait_s=max_wait_s,
                metrics=metrics, name="embed",
            )
        elif self.family == "vision":
            self._build_vision_step()
            self._batcher = DynamicBatcher(
                self._execute_classify, max_batch=max_batch, max_wait_s=max_wait_s,
                metrics=metrics, name="classify",
            )
        elif self.family == "seq2seq":
            self.max_len = min(max_len, self.cfg.max_len)
            self._build_seq2seq_step()
            self._batcher = DynamicBatcher(
                self._execute_seq2seq, max_batch=max_batch,
                max_wait_s=max_wait_s, metrics=metrics, name="seq2seq",
            )
        else:
            raise ValueError(f"unknown model family {self.family}")
        if self.family != "llm":
            # Non-LLM families have no serving-state rebuild seam: the
            # ledger (params + batcher workspace is negligible) builds
            # once here.
            self._build_hbm_ledger()
        # The fault-tolerant control plane (serving/control_plane.py;
        # docs/advanced-guide/resilience.md "Control plane"): built
        # LAST so its signal closures capture sensors that only exist
        # after _init_llm_serving_state (queue, throughput meter, HBM
        # ledger). LLM-family only — every loop it closes is a
        # scheduler-loop loop. TPU_CONTROL_PLANE=0 builds nothing.
        self._control: Any = None
        if control_plane and self.family == "llm":
            from gofr_tpu.serving.control_plane import ControlPlane

            cp = ControlPlane(
                model_name,
                stale_s=control_stale_s,
                tenant_enter=control_tenant_enter,
                tenant_exit=control_tenant_exit,
                tenant_sustain_s=control_tenant_sustain_s,
                tenant_exit_sustain_s=control_tenant_exit_sustain_s,
                tenant_max_new=control_tenant_max_new,
                tenant_aimd_cut=control_tenant_aimd_cut,
                tenant_recover_per_s=control_tenant_recover_per_s,
                tenant_table_max=control_tenant_table,
                host_ratio=control_host_ratio,
                host_util=control_host_util,
                host_sustain_s=control_host_sustain_s,
                predict_window_s=control_predict_window_s,
                predict_horizon_s=control_predict_horizon_s,
                # The predictive threshold defaults to half the queue
                # bound: fire while the reactive sustained-threshold
                # path still has runway.
                predict_depth=(
                    float(control_predict_depth)
                    if control_predict_depth > 0
                    else max(1.0, 0.5 * float(self.queue_max))
                ),
                predict_hold_s=control_predict_hold_s,
                metrics=metrics,
                logger=logger,
                clock=self._obs.now,
            )
            slo = self._slo
            if slo is not None:
                cp.register(
                    "tenant_burn",
                    lambda: slo.tenant_burns("5m"),
                    kind="map",
                )
            prof = self._loop_prof
            if prof is not None:
                cp.register("host_overhead_ratio", prof.host_overhead_ratio)
                cp.register("loop_utilization", prof.utilization)
            cp.register(
                "queue_depth", lambda: float(self._pending.qsize())
            )
            cp.register(
                "throughput",
                lambda: float(self._tput.rate(self._obs.now())),
            )
            if self._ledger is not None:
                cp.register(
                    "hbm_headroom",
                    lambda: float(self.hbm_headroom_ratio()),
                )
            self._control = cp

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------

    @classmethod
    def from_config(
        cls,
        config: Any,
        logger: Any = None,
        metrics: Any = None,
        devices: Any = None,
    ) -> "InferenceEngine":
        """Container seam: all knobs are TPU_* env keys (the datasource
        config idiom, reference ``sql/sql.go:109-118``).

        ``TPU_TP=N`` serves tensor-parallel over N chips (ICI): params
        Megatron-sharded, the (paged) KV pool's head axis sharded, XLA
        inserts the collectives. (``TPU_MESH_TP`` is the historical
        alias.) Data-parallel serving scale-out is engine replicas
        behind the service tier — with ``TPU_REPLICAS > 1`` each
        in-proc replica becomes one sharded pod carved from a disjoint
        ``devices`` slice (dp across replicas, tp within; see
        ``serving/backend.py``).
        """
        from gofr_tpu.serving.slo import tenant_objectives_from_config

        mesh = None
        tp = int(
            config.get_or_default(
                "TPU_TP", config.get_or_default("TPU_MESH_TP", "1")
            )
        )
        # Serving context parallelism: the KV cache's length axis shards
        # over cp chips, so max_len can exceed one chip's cache HBM
        # (GSPMD turns the sharded softmax reductions into collectives).
        cp = int(config.get_or_default("TPU_MESH_CP", "1"))
        if tp > 1 or cp > 1:
            from gofr_tpu.parallel import make_mesh

            axes = {}
            if tp > 1:
                axes["tp"] = tp
            if cp > 1:
                axes["cp"] = cp
            mesh = make_mesh(axes, devices=devices)
        model_name = config.get_or_default("TPU_MODEL", "llama-tiny")
        ckpt = config.get_or_default("TPU_CHECKPOINT", "")
        quant_cfg = config.get_or_default("TPU_QUANT", "")
        # Retired keys: the programs they selected are gone (PR 30), and
        # a deployment that still sets one is told so, not refused.
        for key, off in (
            ("TPU_SPEC_TOKENS", ("auto", "0")),
            ("TPU_MEGA_WINDOWS", ("0",)),
            ("TPU_PREFILL_DEPTH", ("1",)),
        ):
            val = config.get_or_default(key, off[0]).strip().lower()
            if val not in off and logger is not None:
                logger.warnf(
                    "%s=%s is retired and ignored: the plain prefill step "
                    "and decode window serve", key, val,
                )
        params = None
        if ckpt:
            from gofr_tpu.serving.hf_loader import (
                is_hf_checkpoint,
                load_hf_llama,
            )

            if is_hf_checkpoint(ckpt):
                # Real weights (HF safetensors layout), quantized leaf-wise
                # on device as they land — the bf16 tree never fully
                # materializes (VERDICT r1 #5 + #4) — and placed straight
                # onto the tp mesh when one is configured.
                from gofr_tpu.models.registry import get_model

                spec = get_model(model_name)
                if spec.family == "seq2seq":
                    from gofr_tpu.models.t5 import load_hf_t5

                    if mesh is not None:
                        # Silently serving replicated would defeat the
                        # operator's explicit parallelism settings.
                        raise ValueError(
                            "TPU_MESH_* is not supported for seq2seq "
                            "checkpoints yet"
                        )
                    params = load_hf_t5(
                        ckpt, spec.config, quant=quant_cfg
                    )
                else:
                    params = load_hf_llama(
                        ckpt, spec.config, quant=quant_cfg,
                        mesh=mesh, logger=logger,
                    )
        engine = cls(
            model_name,
            mesh=mesh,
            # Unsharded replicas pin to their own device (sharded ones
            # already carved ``devices`` into the mesh above).
            devices=devices if mesh is None else None,
            params=params,
            quant="" if (params is not None or ckpt) else quant_cfg,
            n_slots=int(config.get_or_default("TPU_KV_SLOTS", "8")),
            max_len=int(config.get_or_default("TPU_MAX_LEN", "1024")),
            max_batch=int(config.get_or_default("TPU_MAX_BATCH", "8")),
            max_wait_s=float(config.get_or_default("TPU_BATCH_WAIT_MS", "5")) / 1e3,
            window_k=int(config.get_or_default("TPU_DECODE_WINDOW", "8")),
            pipeline_depth=int(config.get_or_default("TPU_PIPELINE_DEPTH", "2")),
            kv_quant=config.get_or_default("TPU_KV_QUANT", ""),
            prefix_slots=int(config.get_or_default("TPU_PREFIX_SLOTS", "0")),
            prefill_chunk=int(config.get_or_default("TPU_PREFILL_CHUNK", "256")),
            prefill_batch=int(config.get_or_default("TPU_PREFILL_BATCH", "8")),
            truncate_prompts=config.get_or_default(
                "TPU_TRUNCATE_PROMPTS", "false"
            ).lower() in ("1", "true", "yes"),
            top_k=int(config.get_or_default("TPU_TOP_K", "0")),
            top_logprobs=int(config.get_or_default("TPU_TOP_LOGPROBS", "0")),
            enable_top_p=config.get_or_default("TPU_TOP_P", "false").lower()
            in ("1", "true", "yes"),
            enable_penalties=config.get_or_default(
                "TPU_PENALTIES", "false"
            ).lower() in ("1", "true", "yes"),
            kv_block=int(config.get_or_default("TPU_KV_BLOCK", "0")),
            lora_slots=int(config.get_or_default("TPU_LORA_SLOTS", "0")),
            lora_rank=int(config.get_or_default("TPU_LORA_RANK", "16")),
            lora_targets=config.get_or_default(
                "TPU_LORA_TARGETS", "wq,wk,wv,wo"
            ),
            kv_pool_blocks=int(
                config.get_or_default("TPU_KV_POOL_BLOCKS", "0")
            ),
            # Automatic block-level prefix caching (needs TPU_KV_BLOCK).
            auto_prefix=config.get_or_default(
                "TPU_AUTO_PREFIX", "false"
            ).lower() in ("1", "true", "yes"),
            prefix_cache_blocks=int(
                config.get_or_default("TPU_PREFIX_CACHE_BLOCKS", "0")
            ),
            # Free-block watermark for proactive radix-cache eviction
            # (blocks; 0 = evict only on allocation shortfall).
            prefix_evict_watermark=int(
                config.get_or_default("TPU_PREFIX_EVICT_WM", "0")
            ),
            # Device-resource observability knobs (docs/advanced-guide/
            # observability.md "Device-resource signals"): derive the
            # eviction watermark from HBM headroom instead of a raw
            # block count (the explicit TPU_PREFIX_EVICT_WM wins when
            # both are set), shed admissions below a headroom floor,
            # and state the per-device HBM budget on backends whose
            # memory_stats() reports nothing.
            prefix_evict_hbm_frac=float(
                config.get_or_default("TPU_PREFIX_EVICT_HBM_FRAC", "0")
            ),
            admit_min_headroom=float(
                config.get_or_default("TPU_ADMIT_MIN_HEADROOM", "0")
            ),
            hbm_budget_bytes=int(
                config.get_or_default("TPU_HBM_BYTES", "0")
            ),
            # Request-lifecycle resilience knobs (docs/advanced-guide/
            # resilience.md): bounded submit queue + token budget,
            # throughput prior for projected-wait shedding, and the
            # scheduler watchdog's wall-time bound (0 = disabled).
            queue_max=int(config.get_or_default("TPU_QUEUE_MAX", "1024")),
            queue_max_tokens=int(
                config.get_or_default("TPU_QUEUE_TOKENS", "0")
            ),
            class_promote_s=float(
                config.get_or_default("TPU_QUEUE_CLASS_PROMOTE_S", "5")
            ),
            tenant_queue_max=int(
                config.get_or_default("TPU_TENANT_QUEUE_MAX", "0")
            ),
            # Tenant attribution + SLO layer (docs/advanced-guide/
            # observability.md "Tenant attribution & SLOs"): the ledger
            # master switch (0 = zero scheduler-hook overhead), the
            # metric-label cardinality clamp, the fairness-shed share
            # (0 = off), the declarative objectives, and the persistent
            # XLA compile-cache directory.
            tenant_ledger=config.get_or_default(
                "TPU_TENANT_LEDGER", "1"
            ).lower() not in ("0", "false", "no"),
            tenant_label_max=int(
                config.get_or_default("TPU_TENANT_LABEL_MAX", "8")
            ),
            tenant_table_max=int(
                config.get_or_default("TPU_TENANT_TABLE_MAX", "256")
            ),
            tenant_fair_share=float(
                config.get_or_default("TPU_TENANT_FAIR_SHARE", "0")
            ),
            slo_ttft_ms=float(
                config.get_or_default("TPU_SLO_TTFT_MS", "0")
            ),
            slo_e2e_ms=float(
                config.get_or_default("TPU_SLO_E2E_MS", "0")
            ),
            slo_availability=float(
                config.get_or_default("TPU_SLO_AVAILABILITY", "0")
            ),
            # Per-tenant SLO overrides (TPU_SLO_TENANT_<NAME>_TTFT_MS
            # and kin) and the brownout ladder (docs/advanced-guide/
            # resilience.md "Brownout & overload control"): thresholds
            # on the 5m burn with sustain windows for hysteresis, the
            # L1 generation clamp, the L2 AIMD parameters, and the
            # optional headroom floor that also counts as pressure.
            slo_tenant_objectives=tenant_objectives_from_config(config),
            brownout=config.get_or_default(
                "TPU_BROWNOUT", "1"
            ).lower() not in ("0", "false", "no"),
            brownout_enter=float(
                config.get_or_default("TPU_BROWNOUT_ENTER", "2")
            ),
            brownout_exit=float(
                config.get_or_default("TPU_BROWNOUT_EXIT", "1")
            ),
            brownout_sustain_s=float(
                config.get_or_default("TPU_BROWNOUT_SUSTAIN_S", "10")
            ),
            brownout_exit_sustain_s=float(
                config.get_or_default("TPU_BROWNOUT_EXIT_SUSTAIN_S", "30")
            ),
            brownout_max_new=int(
                config.get_or_default("TPU_BROWNOUT_MAX_NEW", "256")
            ),
            brownout_aimd_cut=float(
                config.get_or_default("TPU_BROWNOUT_AIMD_CUT", "0.5")
            ),
            brownout_recover_per_s=float(
                config.get_or_default("TPU_BROWNOUT_RECOVER_PER_S", "0.02")
            ),
            brownout_min_headroom=float(
                config.get_or_default("TPU_BROWNOUT_MIN_HEADROOM", "0")
            ),
            # The fault-tolerant control plane (docs/advanced-guide/
            # resilience.md "Control plane"): the master switch, the
            # signal staleness window, the per-tenant brownout ladder's
            # thresholds/AIMD, the host-overhead pressure loop, and the
            # predictive-scaling trend fit.
            control_plane=config.get_or_default(
                "TPU_CONTROL_PLANE", "1"
            ).lower() not in ("0", "false", "no"),
            control_stale_s=float(
                config.get_or_default("TPU_CONTROL_STALE_S", "10")
            ),
            control_tenant_enter=float(
                config.get_or_default("TPU_CONTROL_TENANT_ENTER", "2")
            ),
            control_tenant_exit=float(
                config.get_or_default("TPU_CONTROL_TENANT_EXIT", "1")
            ),
            control_tenant_sustain_s=float(
                config.get_or_default("TPU_CONTROL_TENANT_SUSTAIN_S", "10")
            ),
            control_tenant_exit_sustain_s=float(
                config.get_or_default(
                    "TPU_CONTROL_TENANT_EXIT_SUSTAIN_S", "30"
                )
            ),
            control_tenant_max_new=int(
                config.get_or_default("TPU_CONTROL_TENANT_MAX_NEW", "256")
            ),
            control_tenant_aimd_cut=float(
                config.get_or_default("TPU_CONTROL_TENANT_AIMD_CUT", "0.5")
            ),
            control_tenant_recover_per_s=float(
                config.get_or_default(
                    "TPU_CONTROL_TENANT_RECOVER_PER_S", "0.02"
                )
            ),
            control_tenant_table=int(
                config.get_or_default("TPU_CONTROL_TENANT_TABLE", "64")
            ),
            control_host_ratio=float(
                config.get_or_default("TPU_CONTROL_HOST_RATIO", "0.85")
            ),
            control_host_util=float(
                config.get_or_default("TPU_CONTROL_HOST_UTIL", "0.75")
            ),
            control_host_sustain_s=float(
                config.get_or_default("TPU_CONTROL_HOST_SUSTAIN_S", "30")
            ),
            control_predict_window_s=float(
                config.get_or_default("TPU_CONTROL_PREDICT_WINDOW_S", "60")
            ),
            control_predict_horizon_s=float(
                config.get_or_default(
                    "TPU_CONTROL_PREDICT_HORIZON_S", "30"
                )
            ),
            control_predict_depth=float(
                config.get_or_default("TPU_CONTROL_PREDICT_DEPTH", "0")
            ),
            control_predict_hold_s=float(
                config.get_or_default("TPU_CONTROL_PREDICT_HOLD_S", "30")
            ),
            # Prefix-hit-aware admission ordering (off by default —
            # byte-identical pop order when off).
            queue_prefix_aware=config.get_or_default(
                "TPU_QUEUE_PREFIX_AWARE", "0"
            ).lower() not in ("", "0", "false", "no"),
            tenant_slo_class=config.get_or_default(
                "TPU_TENANT_SLO_CLASS", ""
            ),
            expected_tps=float(
                config.get_or_default("TPU_EXPECTED_TPS", "0")
            ),
            watchdog_s=float(config.get_or_default("TPU_WATCHDOG_S", "0")),
            replay_exact=config.get_or_default(
                "TPU_REPLAY_EXACT", "true"
            ).lower() in ("1", "true", "yes"),
            # Observability (docs/advanced-guide/observability.md): the
            # flight recorder's ring size, slow-pin threshold, and the
            # master switch (0 = off, the bench overhead A/B).
            flight_recorder=config.get_or_default(
                "TPU_FLIGHT_RECORDER", "1"
            ).lower() not in ("0", "false", "no"),
            flight_records=int(
                config.get_or_default("TPU_FLIGHT_RECORDS", "256")
            ),
            flight_slow_s=float(
                config.get_or_default("TPU_FLIGHT_SLOW_S", "5")
            ),
            # Scheduler-loop profiler (docs/advanced-guide/
            # observability.md "Scheduler-loop signals"): per-phase
            # pass attribution + stall anomalies on /debug/loop. The
            # master switch (0 = byte-identical pre-profiler loop, the
            # bench overhead A/B), the absolute and p95-relative stall
            # bounds, the anomaly-ring size, and the optional
            # stall-triggered device-trace capture (ms; 0 = off) with
            # its storm cooldown.
            loop_profile=config.get_or_default(
                "TPU_LOOP_PROFILE", "1"
            ).lower() not in ("0", "false", "no"),
            loop_stall_s=float(
                config.get_or_default("TPU_LOOP_STALL_S", "1.0")
            ),
            loop_stall_factor=float(
                config.get_or_default("TPU_LOOP_STALL_FACTOR", "10")
            ),
            loop_anomalies=int(
                config.get_or_default("TPU_LOOP_ANOMALIES", "64")
            ),
            loop_trace_ms=int(
                config.get_or_default("TPU_LOOP_TRACE_MS", "0")
            ),
            loop_trace_cooldown_s=float(
                config.get_or_default("TPU_LOOP_TRACE_COOLDOWN_S", "60")
            ),
            logger=logger,
            metrics=metrics,
            tokenizer=tokenizer_from_config(config, logger),
        )
        if ckpt and params is None:
            # Orbax checkpoint path: restore bf16 params, then quantize.
            from gofr_tpu.serving.checkpoint import maybe_restore_params

            engine.params = engine._commit(
                maybe_restore_params(config, engine.params, logger)
            )
            engine.apply_quantization(quant_cfg)
        # Boot-time LoRA adapters: TPU_LORA_ADAPTERS="name=path,name2=p2"
        # (HF PEFT checkpoint dirs). More can load at runtime via
        # engine.load_lora.
        adapters_cfg = config.get_or_default("TPU_LORA_ADAPTERS", "")
        if adapters_cfg:
            for entry in adapters_cfg.replace(";", ",").split(","):
                entry = entry.strip()
                if not entry:
                    continue
                if "=" not in entry:
                    raise ValueError(
                        f"TPU_LORA_ADAPTERS entry {entry!r} is not "
                        f"name=path"
                    )
                name, path = entry.split("=", 1)
                engine.load_lora(name.strip(), path.strip())
        # Self-healing (docs/advanced-guide/resilience.md): TPU_RESTART_MAX
        # > 0 attaches a supervisor that owns the restart policy — watchdog
        # trips and fatal scheduler exits tear down, back off, warm-restart
        # and replay retryable requests instead of latching DOWN.
        restart_max = int(config.get_or_default("TPU_RESTART_MAX", "0"))
        if restart_max > 0 and engine.family == "llm":
            from gofr_tpu.serving.supervisor import EngineSupervisor

            EngineSupervisor(
                engine,
                max_restarts=restart_max,
                backoff_s=float(
                    config.get_or_default("TPU_RESTART_BACKOFF_S", "0.5")
                ),
                metrics=metrics,
                logger=logger,
            ).start()
        return engine

    def _cache_kind(self) -> str:
        """Which contiguous cache the model is served over: "latent"
        (``LatentKVCache``), "hybrid" (``HybridCache``) or "kv"."""
        if getattr(self.cfg, "is_latent", False):
            return "latent"
        if getattr(self.cfg, "is_hybrid", False):
            return "hybrid"
        return "kv"

    # What cannot run over a cache that is not K and V planes alone, by the
    # cache's kind and the setting that asks for it: the one table of boot
    # refusals (``_refuse_for_cache``). A kind that a row does not name
    # serves that setting.
    _CACHE_WHY = {
        "latent": (
            "latent attention keeps one {cfg.cache_row}-value row a token a "
            "layer in a contiguous cache of its own; "
        ),
        "hybrid": (
            "a stack of sparse and lightning layers keeps K, V and "
            "compressed keys for its {cfg.n_sparse_layers} sparse layers and "
            "a fixed-size state a slot for its {cfg.n_lin_layers} lightning "
            "layers in one contiguous cache of its own; "
        ),
    }
    _CACHE_REFUSALS = {
        "tp": {
            "latent": "TPU_TP > 1 (or a mesh) is not served: the row has no "
                      "kv-head axis to shard and the grouped expert product "
                      "has no expert axis yet",
            "hybrid": "TPU_TP > 1 (or a mesh, pipeline stages among them) is "
                      "not served: the state plane and the two kinds' "
                      "stacked leaves have no partition specs",
        },
        "kv_block": {
            "latent": "TPU_KV_BLOCK > 0 (the paged pool) is not served: the "
                      "pool, its Pallas kernels and the KV export / import "
                      "payloads (tier transfers, KVB1) move K and V planes",
            "hybrid": "TPU_KV_BLOCK > 0 (the paged pool) is not served: a "
                      "state is not block-addressable, and the pool's "
                      "kernels and KV export / import payloads move K and V "
                      "blocks without it",
        },
        "auto_prefix": {
            "latent": "TPU_AUTO_PREFIX (the radix prefix cache) is not "
                      "served: it aliases blocks of the paged pool",
            "hybrid": "TPU_AUTO_PREFIX (the radix prefix cache) is not "
                      "served: it aliases blocks of the paged pool, and a "
                      "prefix's state would have to be kept at every block "
                      "boundary",
        },
        "prefix_slots": {
            "latent": "TPU_PREFIX_SLOTS > 0 (the prefix pool) is not served: "
                      "it copies K and V rows",
            "hybrid": "TPU_PREFIX_SLOTS > 0 (the prefix pool) is not served: "
                      "it copies K and V rows and would leave the state "
                      "behind",
        },
        "kv_quant": {
            "latent": "TPU_KV_QUANT={value} is not served: int8 latent rows "
                      "have no scales plane",
            "hybrid": "TPU_KV_QUANT={value} is not served: the compressed "
                      "keys and the float32 state have no int8 form",
        },
        "quant": {
            "hybrid": "TPU_QUANT={value} is not served: the lightning and gate "
                      "projections (lin_layers, wg) and the per-head norms "
                      "have no quantised path yet",
        },
        "lora_slots": {
            "latent": "TPU_LORA_SLOTS > 0 (targets {value!r}) is not served: "
                      "there is no wq / wk / wv to adapt, and no LoRA on the "
                      "latent projections "
                      "(wq_down, wq_up, wkv_down, wk_up, wv_up) or beside "
                      "routed experts",
            "hybrid": "TPU_LORA_SLOTS > 0 (targets {value!r}) is not served: "
                      "no LoRA on the lightning layers' projections or the "
                      "output gates",
        },
        "tier_export": {
            "latent": "a prefill-tier role (TPU_REPLICA_ROLES) is not "
                      "served: KV export / import payloads move K and V "
                      "blocks of the paged pool, and a latent row has neither",
            "hybrid": "a prefill-tier role (TPU_REPLICA_ROLES) is not "
                      "served: KV export / import payloads move K and V "
                      "blocks of the paged pool and carry no state",
        },
    }

    def _refuse_for_cache(self, **asked: Any) -> None:
        """Refuse, before anything is initialised, each setting in ``asked``
        (by its name in ``_CACHE_REFUSALS``; a value that is true asks for
        it) that cannot run over this model's kind of cache, with a message
        that names the setting."""
        kind = self._cache_kind()
        for setting, value in asked.items():
            message = self._CACHE_REFUSALS[setting].get(kind)
            if value and message:
                raise ValueError(
                    f"{self.model_name}: "
                    + self._CACHE_WHY[kind].format(cfg=self.cfg)
                    + message.format(value=value)
                )

    def _placement(self) -> Any:
        """Context in which NEW arrays land on a pinned engine's own
        device instead of staging on the process default (a 7B tree
        staged on chip 0 for replica 3 would not fit beside replica 0).
        A no-op for mesh and unpinned engines."""
        if self._device is None:
            return contextlib.nullcontext()
        return self._jax.default_device(self._device)

    def _commit(self, tree: Any) -> Any:
        """Commit ``tree`` to a pinned engine's device so every jit that
        takes it runs there; identity for mesh and unpinned engines."""
        if self._device is None:
            return tree
        return self._jax.device_put(tree, self._device)

    def _init_llm_quantized(self, seed: int) -> dict:
        """Random-init the transformer leaf-by-leaf with immediate int8 or
        int4 quantization (``self.quant``) of the matmul weights (same
        fan-in-scaled normal as ``init_transformer``, different key-split
        order — irrelevant for random weights). Each leaf's bf16 tensor is
        transient inside its own jit, so an 8B tree peaks near its
        quantized footprint."""
        jax, jnp = self._jax, self._jnp
        from gofr_tpu.models.transformer import norm_init
        from gofr_tpu.ops.quant import (
            _QUANT_KEYS,
            quantize_array,
            quantize_array4,
        )

        quantize_leaf = (
            quantize_array4 if self.quant == "int4" else quantize_array
        )

        cfg = self.cfg
        shapes = jax.eval_shape(
            lambda k: self.spec.init(k, cfg), jax.random.PRNGKey(0)
        )
        base = jax.random.PRNGKey(seed)
        counter = [0]

        def make(name: str, sds: Any) -> Any:
            counter[0] += 1
            key = jax.random.fold_in(base, counter[0])
            if name.endswith("_norm"):
                return norm_init(name, sds.shape, cfg)
            if name.endswith("_b"):  # QKV biases: zeros, as init_transformer
                return jnp.zeros(sds.shape, cfg.dtype)
            fan_in = (
                sds.shape[-1] if name in ("embed", "pos_embed")
                else sds.shape[-2]
            )

            def init_leaf(k: Any) -> Any:
                w = (
                    jax.random.normal(k, sds.shape, jnp.float32) * fan_in**-0.5
                ).astype(cfg.dtype)
                return quantize_leaf(w) if name in _QUANT_KEYS else w

            return jax.jit(init_leaf)(key)

        # The four every model has, in the order their keys were always
        # drawn, then whatever else the config adds (a looped stack's exit
        # gate).
        order = ["embed", "layers", "final_norm", "lm_head"]
        order += sorted(set(shapes) - set(order))
        return {
            name: (
                {k: make(k, v) for k, v in shapes[name].items()}
                if isinstance(shapes[name], dict) else make(name, shapes[name])
            )
            for name in order
        }

    def _init_llm_serving_state(self) -> None:
        """(Re)build every per-boot LLM serving structure: the KV cache
        (and its paged-pool allocator), the prefix pool, the admission
        queues, and the device-resident slot-state planes.

        Called from ``__init__`` and again from :meth:`restart_sync` —
        the supervisor's warm restart. Params and compiled programs are
        deliberately NOT touched: a restart reuses the already-loaded
        pytree and the jit caches, so recovery costs cache allocation,
        not a model load + compile. Everything rebuilt here is either
        derived state (KV contents are re-prefilled by request replay)
        or bookkeeping a crashed/abandoned scheduler may have left
        inconsistent.
        """
        jax = self._jax
        mesh = self.mesh
        n_slots = self.n_slots
        from gofr_tpu.ops.kv_cache import KVCache

        # Positions a step of the prefill attention's loop over a latent or
        # a hybrid cache scores at once (0: that attention is not blocked).
        self.prefill_attn_block = 0
        if self.kv_block:
            from gofr_tpu.ops.kv_cache import PagedKVCache

            make_cache = lambda: PagedKVCache.create(  # noqa: E731
                self.cfg.n_cache_entries, n_slots, self.max_len,
                self.cfg.n_kv_heads, self.cfg.head_dim, self.cfg.dtype,
                quant=self.kv_quant, block=self.kv_block,
                n_blocks=self.kv_pool_blocks,
            )
        elif getattr(self.cfg, "is_latent", False):
            from gofr_tpu.ops.attention import LATENT_CHUNK_BLOCK
            from gofr_tpu.ops.kv_cache import LatentKVCache

            self.prefill_attn_block = min(LATENT_CHUNK_BLOCK, self.max_len)
            make_cache = lambda: LatentKVCache.create(  # noqa: E731
                self.cfg.n_cache_entries, n_slots, self.max_len,
                self.cfg.cache_row, self.cfg.dtype,
            )
        elif getattr(self.cfg, "is_hybrid", False):
            from gofr_tpu.ops.attention import SPARSE_CHUNK_BLOCK
            from gofr_tpu.ops.kv_cache import HybridCache

            cfg = self.cfg
            unit = (
                SPARSE_CHUNK_BLOCK if self.max_len > SPARSE_CHUNK_BLOCK
                else cfg.sparse_block
            )
            if self.max_len % unit or unit % cfg.sparse_block:
                raise ValueError(
                    f"{self.model_name}: TPU_MAX_LEN={self.max_len} is not "
                    f"served: a sparse layer's prefill attends a slot in "
                    f"whole blocks of {unit} positions (and picks among "
                    f"blocks of {cfg.sparse_block})"
                )
            self.prefill_attn_block = min(SPARSE_CHUNK_BLOCK, self.max_len)
            make_cache = lambda: HybridCache.for_config(  # noqa: E731
                cfg, n_slots, self.max_len
            )
        else:
            make_cache = lambda: KVCache.create(  # noqa: E731
                self.cfg.n_cache_entries, n_slots, self.max_len,
                self.cfg.n_kv_heads, self.cfg.head_dim, self.cfg.dtype,
                quant=self.kv_quant,
            )
        if mesh is not None:
            # KV heads shard over tp, the length axis over cp —
            # same layout prefill and decode.
            from gofr_tpu.models.transformer import kv_cache_specs
            from gofr_tpu.parallel.sharding import (
                named_shardings,
                prune_specs,
            )

            self.cache = jax.jit(
                make_cache,
                out_shardings=named_shardings(
                    prune_specs(
                        kv_cache_specs(
                            quantized=bool(self.kv_quant),
                            paged=bool(self.kv_block),
                            cp="cp" in mesh.axis_names,
                        ),
                        mesh,
                    ),
                    mesh,
                ),
            )()
        else:
            with self._placement():
                self.cache = self._commit(make_cache())
        if self._metrics is not None:
            self._metrics.set_gauge(
                "app_tpu_kv_bytes_per_token", self.kv_bytes_per_token(),
                "model", self.model_name,
            )
            if self.state_bytes_per_slot():
                self._metrics.set_gauge(
                    "app_tpu_state_bytes_per_slot",
                    self.state_bytes_per_slot(), "model", self.model_name,
                )
        self._radix = None
        if self.kv_block:
            # Host-side REFCOUNTED block allocator (ops/kv_cache.py):
            # block 0 is the parking block and never handed out; the
            # table mirror uploads (8 KB) only when an admission/top-up/
            # release dirtied it. Refcounts exist for the automatic
            # prefix cache — aliased blocks are shared by many tables.
            from gofr_tpu.ops.kv_cache import BlockAllocator

            self._allocator = BlockAllocator(self.cache.n_blocks)
            self._slot_blocks: list[list[int]] = [[] for _ in range(n_slots)]
            self._table_host = np.zeros(
                (n_slots, self.max_len // self.kv_block), dtype=np.int32
            )
            self._table_dirty = False
            self._dispatched_tokens = [0] * n_slots
            if self.auto_prefix:
                # The radix index maps token content to PHYSICAL pool
                # blocks, so it is rebuilt WITH the cache planes: after
                # a supervisor warm restart the old blocks' contents are
                # gone and replayed requests re-prefill through normal
                # admission, re-warming the index as they retire.
                from gofr_tpu.serving.radix_cache import RadixPrefixIndex

                self._radix = RadixPrefixIndex(
                    self.kv_block, self._allocator,
                    max_blocks=self.prefix_cache_blocks,
                )
        # Prefix-KV reuse: shared system prompts prefill once into a
        # device pool; admission copies rows in (prefix_cache.py). A
        # restart builds a FRESH pool — the old rows died with the old
        # cache, so callers re-register (register_prefix documents this).
        self._prefix_pool = None
        if self.prefix_slots > 0:
            from gofr_tpu.serving.prefix_cache import PrefixPool

            self._prefix_pool = PrefixPool(
                self.prefix_slots, self.cache, mesh=mesh
            )
        self._slots: list[Optional[_ActiveSeq]] = [None] * n_slots
        self._prefilling: dict[int, _PrefillState] = {}
        # (first_dev, first_lp_dev, row, slot, seq) awaiting async fetch.
        self._prefill_emits: list = []
        # (route counts on device, rows, prompt tokens) of prefill steps
        # whose async copy has not landed (a grouped expert layer only).
        self._moe_counts: Any = collections.deque()
        # Paged mode: requests held back waiting for free pool blocks.
        from collections import deque as _deque

        self._wait_kv: "_deque[_GenRequest]" = _deque()
        # Tier transfers awaiting application: KVBlockPayloads a sibling
        # prefill replica shipped here (handoff_prefilled), applied by
        # the scheduler thread ahead of admission each iteration — the
        # pool blocks they fill belong to THIS boot's allocator, so the
        # deque is rebuilt (emptied) with the rest of the per-boot
        # state; a payload dropped by a restart simply re-prefills.
        self._tier_imports: "_deque[Any]" = _deque()
        # Import-completion latches (import_payload(wait_s=...)): the
        # remote-source pull waits — bounded — until the scheduler has
        # actually applied the payload, so the request submitted right
        # after deterministically admission-aliases the warm blocks
        # instead of racing its own cache warm.
        self._tier_import_done: "dict[int, Any]" = {}
        # Prefill-source export requests (export_cached): (ids, box,
        # event) triples serviced by the scheduler thread next to the
        # import apply — the radix walk and the device→host block pull
        # both touch donated planes, so no other thread may run them.
        self._tier_exports: "_deque[Any]" = _deque()
        # Watermark-sweep fruitless latch (scheduler._radix_watermark_
        # sweep): the (free, cached) signature of the last sweep that
        # found nothing evictable, so the loop skips re-scanning the
        # trie until pressure actually changes.
        self._wm_fruitless: Optional[tuple[int, int]] = None
        # SLO-class-aware admission queue (serving/lifecycle.py): the
        # queue.Queue API subset the scheduler pops through, with
        # interactive-first dequeue and a max-wait starvation bound.
        # With class_promote_s=0 (or uniform-class traffic) the pop
        # order is exactly the old FIFO.
        # Hit-aware admission ordering (TPU_QUEUE_PREFIX_AWARE, off by
        # default): the pop tie-break probes the radix index through
        # the NON-MUTATING peek — no increfs, no LRU perturbation. The
        # closure captures THIS boot's index (both rebuild together on
        # a warm restart). Off → probe None → byte-identical pop order.
        prefix_probe: Optional[Any] = None
        if self.queue_prefix_aware and self._radix is not None:
            _radix_now = self._radix
            prefix_probe = lambda req: _radix_now.peek(  # noqa: E731
                list(req.prompt_ids), getattr(req, "aid", 0)
            ) > 0
        self._pending: ClassPriorityQueue = ClassPriorityQueue(
            maxsize=self.queue_max,
            promote_after_s=self.class_promote_s,
            prefix_probe=prefix_probe,
        )
        self._work = threading.Event()
        self._tokens_dev = self._up(np.zeros((n_slots,), dtype=np.int32))
        self._logps_dev = self._up(np.zeros((n_slots,), dtype=np.float32))
        # Slot state lives ON DEVICE between windows; re-uploaded only
        # when admissions/retirements change it (dirty flag). Steady-
        # state decode then dispatches with zero host→device traffic.
        # Sampling is counter-based (seed, n_sampled) per slot — no
        # PRNG key threads through device state at all.
        self._nsteps_dev = self._up(np.zeros((n_slots,), dtype=np.int32))
        self._seeds_host = np.zeros((n_slots,), dtype=np.int32)
        self._seeds_dev = self._up(self._seeds_host)
        # Per-slot sampling-counter OFFSET at admission: 0 for fresh
        # requests; a replayed request's delivered-token count, so its
        # counter-based sample path continues where the crashed engine
        # left off (seeded-sampling replay continuity). Uploaded with
        # the seeds plane under the same dirty flag.
        self._noff_host = np.zeros((n_slots,), dtype=np.int32)
        self._noff_dev = self._up(self._noff_host)
        self._seeds_dirty = False
        # Multi-LoRA adapter plane: per-slot adapter index into the
        # stacked [L, 1+lora_slots, ...] adapter leaves (0 = base).
        # Allocated unconditionally so every compiled signature is
        # uniform; without adapter leaves in params the operand is
        # dead and XLA drops it.
        self._aids_host = np.zeros((n_slots,), dtype=np.int32)
        self._aids_dev = self._up(self._aids_host)
        self._active_dev = self._up(np.zeros((n_slots,), dtype=bool))
        self._temps_dev = self._up(np.ones((n_slots,), dtype=np.float32))
        self._topp_dev = self._up(np.ones((n_slots,), dtype=np.float32))
        self._greedy_dev = self._up(np.ones((n_slots,), dtype=bool))
        # Penalties state: per-slot generated-token counts (a [1]-wide
        # dummy when the feature is compiled out keeps one signature).
        pv = self.cfg.vocab_size if self.enable_penalties else 1
        self._pcounts_dev = self._up(
            np.zeros((n_slots, pv), dtype=np.int32)
        )
        self._fpen_dev = self._up(np.zeros((n_slots,), dtype=np.float32))
        self._ppen_dev = self._up(np.zeros((n_slots,), dtype=np.float32))
        self._bidx_host = np.full(
            (n_slots, LOGIT_BIAS_K), -1, dtype=np.int32
        )
        self._bval_host = np.zeros(
            (n_slots, LOGIT_BIAS_K), dtype=np.float32
        )
        self._bidx_dev = self._up(self._bidx_host)
        self._bval_dev = self._up(self._bval_host)
        tlk = max(1, self.top_logprobs)
        self._topi_dev = self._up(
            np.zeros((n_slots, tlk), dtype=np.int32)
        )
        self._topl_dev = self._up(
            np.zeros((n_slots, tlk), dtype=np.float32)
        )
        self._slot_state_dirty = True
        # Compile-tracked paged-pool jits: the COW copy (prefix-hit
        # boundary) and the tier-transfer importer are module-level
        # fixed-shape programs; wrapping them per engine makes a mid-
        # steady-state geometry drift show up in the recompile counter
        # like any other program.
        if self.kv_block:
            from gofr_tpu.ops.kv_cache import (
                paged_copy_block,
                paged_extract_block,
                paged_insert_block,
                paged_move_block,
            )

            # shared=True: these jits' XLA caches span every engine in
            # the process — per-wrapper signature tracking keeps the
            # attribution per-engine and race-free.
            self._paged_copy_block = self._compiles.wrap(
                "paged_copy_block", paged_copy_block, shared=True
            )
            self._paged_insert_block = self._compiles.wrap(
                "paged_insert_block", paged_insert_block, shared=True
            )
            # Device-leg tier transfers (ops/kv_cache.py): fixed-shape
            # per-block extract on the exporting engine and move on the
            # importer — one compile per cache-geometry pair, tracked
            # like every other program so a steady-state transfer can
            # never hide a recompile.
            self._paged_extract_block = self._compiles.wrap(
                "paged_extract_block", paged_extract_block, shared=True
            )
            self._paged_move_block = self._compiles.wrap(
                "paged_move_block", paged_move_block, shared=True
            )
            # Placement for INBOUND device-leg block planes
            # ([L, KV, block, hd] / int8-scale [L, KV, 8, block]): on a
            # mesh the head axis shards like the pool's own planes, so
            # a device_put here reshards shard-to-shard; a pinned
            # engine pulls them onto its own chip; unpinned unsharded
            # engines share the default device and need no put.
            self._block_sharding = None
            if self.mesh is not None:
                from jax.sharding import (
                    NamedSharding,
                    PartitionSpec as _P,
                )

                self._block_sharding = NamedSharding(
                    self.mesh, _P(None, "tp", None, None)
                )
            elif self._device is not None:
                from jax.sharding import SingleDeviceSharding

                self._block_sharding = SingleDeviceSharding(self._device)
        # HBM ledger (serving/device_telemetry.py): every component this
        # boot allocated, rebuilt with the serving state so a warm
        # restart's fresh pool re-accounts exactly. The derived eviction
        # watermark is fixed per boot too — geometry and budget don't
        # move between restarts.
        self._build_hbm_ledger()
        self.effective_evict_watermark = self.prefix_evict_watermark
        if (
            self.prefix_evict_watermark <= 0
            and self.prefix_evict_hbm_frac > 0
            and self.kv_block
            and self._ledger is not None
        ):
            self.effective_evict_watermark = (
                self._ledger.derive_block_watermark(
                    self.prefix_evict_hbm_frac
                )
            )

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def apply_quantization(self, mode: str) -> None:
        """Quantize weights in place (call BEFORE start / after restore).

        Weight-only int8: halves the HBM weight stream that bounds decode
        throughput; dequant fuses into the matmuls (``transformer._wein``).
        """
        mode = (mode or "").lower()
        if not mode:
            return
        if self.quant:
            # Idempotency guard (ADVICE r1): re-quantizing Q8 leaves crashes
            # inside jit with an opaque AttributeError.
            if self.quant == mode:
                return
            raise RuntimeError(
                f"params already quantized as {self.quant!r}; cannot "
                f"re-quantize as {mode!r}"
            )
        if mode not in ("int8", "int4"):
            raise ValueError(
                f"unsupported quant mode {mode!r} (int8 or int4)"
            )
        if self.family not in ("llm", "seq2seq"):
            raise ValueError(
                "quantization supports llm and seq2seq models only"
            )
        if getattr(self, "_running", False):  # __init__ calls this pre-flags
            raise RuntimeError("quantize before starting the engine")
        if self.family == "seq2seq":
            if self.mesh is not None:
                raise ValueError(
                    "quantized seq2seq does not compose with a mesh yet"
                )
            from gofr_tpu.models.t5 import quantize_t5_params

            self.params = self._jax.jit(  # graftlint: disable=GL015 — boot path (guarded: raises if the engine is running)
                lambda p: quantize_t5_params(p, mode), donate_argnums=(0,)
            )(self.params)
            self.quant = mode
            return
        from gofr_tpu.ops.quant import quantize_params

        # donate: the bf16 tree frees leaf-by-leaf as the int8 tree
        # materializes — without it peak HBM is ~1.5× the bf16 tree.
        if self.mesh is not None:
            # Sharded quantization: each Q8 leaf gets out-shardings derived
            # from its weight's PartitionSpec (the scale shards with the
            # output-channel axis), so quantized serving composes with a tp
            # mesh instead of gathering anything onto one chip.
            from gofr_tpu.models.transformer import transformer_param_specs
            from gofr_tpu.ops.quant import quantized_param_specs
            from gofr_tpu.parallel.sharding import named_shardings, prune_specs

            specs = quantized_param_specs(
                prune_specs(transformer_param_specs(self.cfg), self.mesh),
                mode,
            )
            self.params = self._jax.jit(  # graftlint: disable=GL015 — boot path (guarded: raises if the engine is running)
                partial(quantize_params, mode=mode), donate_argnums=(0,),
                out_shardings=named_shardings(specs, self.mesh),
            )(self.params)
        else:
            self.params = self._jax.jit(  # graftlint: disable=GL015 — boot path (guarded: raises if the engine is running)
                partial(quantize_params, mode=mode), donate_argnums=(0,)
            )(self.params)
        self.quant = mode
        if getattr(self, "_prefill_steps", None):
            # Called on a built engine (__init__ quantizes before it
            # builds): the rungs were compiled for the old leaves.
            self._compile_prefill_ladder()

    async def start(self) -> None:
        self.start_sync()

    def start_sync(self) -> None:
        if self._running:
            return
        if self.family == "llm" and self._sched is not None:
            # A crashed scheduler may still be mid-drain; let it finish
            # before resetting flags, or its trailing `_drained = True`
            # would permanently reject submissions on the restarted engine.
            self._sched.join(timeout=10)
            self._sched = None
        # Flag resets hold the submit lock: _enqueue and the scheduler's
        # drain read these under it, and a half-visible reset (e.g.
        # _draining=False seen before _drained=False) would let a
        # submission slip into a queue the old drain already failed.
        with self._submit_lock:
            self._running = True
            self._drained = False
            self._draining = False
            self._restart_pending = False
            self._fatal = None
            self._unhealthy_reason = None
            self._queued_tokens = 0
            self._tenant_queued.clear()
            if self._tenant_ledger is not None:
                self._tenant_ledger.reset_queued()
            self._idle_evt.clear()
        self._tput.reset()
        self._set_state("SERVING")
        if self.family == "llm":
            if self._watchdog is not None:
                self._watchdog.reset()
                self._watchdog.start()
            self._sched = threading.Thread(
                target=self._scheduler_loop, name="tpu-scheduler", daemon=True
            )
            self._sched.start()
        else:
            self._batcher.start()

    async def stop(self, drain_s: float = 0.0) -> None:
        if drain_s > 0:
            await asyncio.get_running_loop().run_in_executor(
                None, partial(self.stop_sync, drain_s)
            )
        else:
            self.stop_sync()

    def stop_sync(self, drain_s: float = 0.0) -> None:
        """Stop the engine. ``drain_s > 0`` = GRACEFUL: new submissions
        get 503 while in-flight generations run to completion (up to the
        deadline) — a rolling restart should not fail live requests the
        way a hard stop's drain does."""
        if drain_s > 0 and self.family == "llm" and self._running:
            with self._submit_lock:
                self._draining = True
                self._sched_idle = False
                self._idle_evt.clear()
            deadline = time.monotonic() + drain_s
            while time.monotonic() < deadline:
                # Only the scheduler may declare the engine idle (it does
                # so under the submit lock after verifying every queue and
                # slot is empty, then sets the idle event) — polling the
                # structures from here would race requests in transit
                # between them. The event wait (vs the old 50 ms sleep
                # poll) returns the moment the scheduler publishes idle
                # or dies, so drains end as soon as the work does.
                # (_drained/_fatal also break: the scheduler's exit path
                # sets _running=False before the event today, but the
                # drain must not depend on that ordering.)
                if (
                    self._sched_idle or not self._running
                    or self._drained or self._fatal is not None
                ):
                    break
                self._idle_evt.wait(timeout=deadline - time.monotonic())
        with self._submit_lock:
            self._running = False
        if self.family == "llm":
            if self._watchdog is not None:
                self._watchdog.stop()
            self._work.set()
            if self._sched is not None:
                self._sched.join(timeout=10)
                self._sched = None
        else:
            self._batcher.stop()
        self._set_state("DOWN")

    def close(self) -> None:
        # An attached supervisor must not resurrect an engine the
        # operator is closing (and its thread must not leak).
        sup = self._supervisor
        if sup is not None:
            sup.stop()
        self.stop_sync()
        if sup is not None:
            # Final sweep: a scheduler crash racing this close may have
            # parked requests for replay after stop()'s own drain;
            # nothing will ever requeue them now (idempotent pop-and-
            # fail under the submit lock).
            sup.drain_parked()

    # ------------------------------------------------------------------
    # supervision (serving/supervisor.py)
    # ------------------------------------------------------------------

    def attach_supervisor(self, supervisor: Any) -> None:
        """Hand the restart policy to ``supervisor``: watchdog trips and
        fatal scheduler exits notify it instead of latching DOWN until
        an operator intervenes, and the scheduler's death drain parks
        retryable requests for replay instead of failing them."""
        self._supervisor = supervisor

    def set_replica_handoff(self, handoff: Optional[Any]) -> None:
        """Install a replica-pool handoff: ``handoff(req) -> bool`` is
        offered every still-retryable request this engine would
        otherwise fail terminally (crash-loop DOWN, scheduler death with
        no supervisor). True means the pool adopted it — requeued on
        another replica via :meth:`requeue_replay`, stream and future
        intact — so the client never sees this replica die."""
        self._handoff = handoff

    def try_handoff(self, req: _GenRequest) -> bool:
        """Offer one request to the attached replica-pool handoff.
        False when no handoff is installed, the request is no longer
        retryable, or the pool could not place it (the caller then runs
        its normal terminal error path). Adapter-bound requests carry
        their adapter NAME (``req.adapter``) and the pool routes them
        only to siblings advertising that adapter — the adopting
        replica re-resolves the name to its OWN slot id, so per-engine
        slot numbering never leaks across replicas. Replica-pinned
        requests are never handed off (synthetic probes must measure
        THIS replica)."""
        handoff = self._handoff
        if (
            handoff is None or req.pin_replica
            or not req.retryable()
        ):
            return False
        try:
            return bool(handoff(req))
        except Exception as exc:  # noqa: BLE001 — handoff must not mask the drain
            if self._logger is not None:
                self._logger.errorf("replica handoff failed: %s", exc)
            return False

    def set_tier_exporter(self, exporter: Optional[Any]) -> None:
        """Install the pool's tier-transfer exporter on a prefill-role
        engine: ``exporter(req, payload) -> bool`` is offered every
        just-finalized prefill (payload = the prompt's full KV blocks,
        host-bounced; None when the engine has no paged pool). True
        means the pool placed the request on a decode replica — this
        engine releases the slot and never decodes it. False (no decode
        tier, retries exhausted AND no sibling adopted it, transfer cap
        hit) means the scheduler decodes locally — the fused fallback,
        so a collapsed decode tier degrades service, never drops it."""
        self._refuse_for_cache(tier_export=exporter is not None)
        self._tier_exporter = exporter

    def handoff_prefilled(self, req: _GenRequest, payload: Any) -> Optional[str]:
        """Decode-tier admission seam: adopt a request whose prompt a
        prefill replica already computed, with its KV blocks shipped as
        ``payload`` (``ops.kv_cache.KVBlockPayload``).

        The payload is NOT applied here — this runs on the pool's
        transfer path, and cache planes may only be touched by the
        scheduler thread (pipelined windows donate the live buffers).
        Instead the payload queues for the scheduler, which imports the
        blocks into the radix prefix index ahead of admission; the
        requeued request then admission-aliases them zero-copy, exactly
        like any other prefix hit. Every validation failure (geometry
        mismatch, short/corrupt payload, no paged pool or radix here)
        quietly downgrades to ``"fused"``: the request re-prefills on
        this replica — byte-identical output, just without the saved
        prefill.

        Returns ``"imported"`` (blocks queued + request admitted),
        ``"fused"`` (request admitted, blocks unusable → re-prefill
        here), or ``None`` (request not adoptable: draining, queue
        full, no longer retryable — the pool tries elsewhere)."""
        if self.family != "llm":
            return None
        # Fault seam: a decode replica rejecting the transfer (pool
        # pressure, version mismatch) — the pool retries with backoff
        # then falls back to fused serving.
        faults.fire("tier.import", engine=self, request=req)
        usable = bool(
            payload is not None
            and self.kv_block
            and self._radix is not None
            and payload.compatible_with(self.cache)
            and payload.verify()
        )
        if usable:
            self._tier_imports.append(payload)
        if not self.requeue_replay(req, mode="transfer"):
            if usable:
                try:
                    self._tier_imports.remove(payload)
                except ValueError:
                    pass  # the scheduler already consumed it: harmless cache warm
            return None
        return "imported" if usable else "fused"

    def import_payload(self, payload: Any, wait_s: float = 0.0) -> str:
        """Wire-leg import seam: adopt a KV-block payload WITHOUT a
        request — the remote decode replica's ops-port import endpoint
        (``POST /ops/tier-import``) lands here after decoding the
        length-prefixed body. Validation is exactly
        :meth:`handoff_prefilled`'s (geometry fingerprint + re-computed
        CRC over the received bytes); a usable payload queues for the
        scheduler thread, which imports it into the radix index like
        any in-proc transfer, and the separately-submitted request then
        admission-aliases the blocks zero-copy. ``"imported"`` when the
        blocks queued, ``"fused"`` when they were rejected — the
        request (which travels the ordinary OpenAI wire) re-prefills
        here either way, never a wrong answer, never a 5xx.

        ``wait_s`` > 0 waits — bounded, never past the budget — until
        the scheduler has APPLIED the payload before returning: the
        pool's remote-source pull submits its request immediately after
        the import, and without the latch the admission alias walk
        could race the apply and pay a redundant prefill (correct, just
        slower and nondeterministic for the warm-hit accounting)."""
        if self.family != "llm":
            return "fused"
        faults.fire("tier.import", engine=self, request=None)
        usable = bool(
            payload is not None
            and self.kv_block
            and self._radix is not None
            and payload.compatible_with(self.cache)
            and payload.verify()
        )
        if not usable:
            if self._logger is not None:
                self._logger.warnf(
                    "wire tier import from %s rejected (stale geometry "
                    "or corrupt payload); the request will re-prefill",
                    getattr(payload, "src", "?"),
                )
            return "fused"
        done: Optional[threading.Event] = None
        if wait_s > 0:
            done = threading.Event()
            self._tier_import_done[id(payload)] = done
        self._tier_imports.append(payload)
        # Wake the scheduler so the import applies ahead of the
        # companion request's admission when the engine is idle.
        self._work.set()
        if done is not None:
            done.wait(wait_s)
            self._tier_import_done.pop(id(payload), None)
        return "imported"

    def export_cached(
        self,
        token_ids: Any,
        *,
        timeout_s: float = 2.0,
        deadline: Optional[Any] = None,
    ) -> Optional[Any]:
        """Prefill-source export seam: hand back the longest cached
        prefix of ``token_ids`` as a shippable host payload, or None on
        a miss. This is ``import_payload`` run backwards — the ops-port
        export endpoint (``GET/POST /ops/tier-export``) lands here when
        a remote decode pod asks this prefill pod for blocks it already
        computed.

        The radix walk and block extraction run on the scheduler
        thread (donated planes); this caller-thread façade enqueues the
        request and waits on a latch BOUNDED by ``timeout_s`` (clamped
        to ``deadline`` when given — the pull must never outlive the
        request it warms). A timeout, a stopped scheduler, or any
        export failure is a miss: the asking pod prefills locally,
        never an error."""
        if self.family != "llm" or not self.kv_block or self._radix is None:
            return None
        ids = [int(t) for t in token_ids]
        if len(ids) < self.kv_block:
            return None  # shorter than one block: nothing shippable
        budget = float(timeout_s)
        if deadline is not None:
            budget = min(budget, float(deadline.remaining()))
        if budget <= 0 or not self._running:
            return None
        box: list = []
        done = threading.Event()
        self._tier_exports.append((tuple(ids), box, done))
        self._work.set()
        if not done.wait(budget):
            return None  # scheduler busy past the budget: miss, not error
        return box[0] if box else None

    def synthetic_probe(self, timeout_s: float = 30.0) -> Any:
        """Active health probe: ONE cheap greedy token through the full
        submit → prefill → decode → retire path. Raises (or times out)
        when the serving dataplane is broken in any way a real request
        would observe — the replica pool's prober demotes the replica
        and asks the supervisor to restart on that evidence, and a DOWN
        replica is re-admitted only after this passes."""
        if self.family != "llm":
            return self.health_check()
        # Pinned to THIS engine: a probe the pool fails over to a
        # healthy sibling would report a dead replica as alive.
        req = self.submit_generate(
            [1], max_new_tokens=1, temperature=0.0, stop_on_eos=False,
            pin_replica=True,
        )
        try:
            return req.future.result(timeout=timeout_s)
        finally:
            # A timed-out probe must not decode forever in a live slot.
            if not req.future.done():
                req.cancel_request()

    def _set_state(self, state: str) -> None:
        """Health state machine transition (SERVING → DEGRADED →
        RESTARTING → DOWN), mirrored to the app_tpu_engine_state gauge
        (0=SERVING 1=DEGRADED 2=RESTARTING 3=DOWN)."""
        self._state = state
        if self._metrics is not None:
            order = {"SERVING": 0, "DEGRADED": 1, "RESTARTING": 2, "DOWN": 3}
            self._metrics.set_gauge(
                "app_tpu_engine_state", order.get(state, 3),
                "model", self.model_name,
            )

    @property
    def state(self) -> str:
        return self._state

    def restart_sync(self) -> None:
        """Warm restart (the supervisor's recovery step): rebuild the
        per-boot serving state — KV cache, paged-pool allocator, queues,
        device slot planes — and start a fresh scheduler, REUSING the
        already-loaded params pytree and the compiled programs. A failed
        device dispatch may have consumed donated buffers (cache, token
        planes), so everything donated is rebuilt; params are never
        donated by the serving programs and survive as-is."""
        if self.family != "llm":
            self.stop_sync()
            self.start_sync()
            return
        if self._running:
            self.stop_sync()
        self._init_llm_serving_state()
        self.start_sync()

    def requeue_replay(self, req: _GenRequest, mode: str = "replay") -> bool:
        """Re-admit a salvaged request after a restart, bypassing the
        admission shedders (it was admitted before the crash; shedding
        the replay would fail a client the restart exists to save).
        Returns False when the request stopped being retryable during
        the restart (cancelled / deadline expired) or the fresh queue is
        already full — the caller fails it with the terminal error path.

        ``mode="transfer"`` is the disaggregated-tier admission path
        (:meth:`handoff_prefilled`): the same shedder-bypassing requeue,
        but nothing was delivered yet and nothing is being replayed, so
        the replay counter/metrics/annotations stay untouched — the
        transfer has its own (``app_tpu_tier_transfers_total``,
        ``tpu.transfer``).
        """
        if not req.retryable():
            return False
        transfer = mode == "transfer"
        # Admission-scoped fields reset so the fresh scheduler re-admits
        # from scratch — snapshotted first, because a requeue that FAILS
        # (draining engine, full queue) hands the request back to its
        # caller, whose fallback path (e.g. the tier exporter's local
        # decode) still needs the pre-requeue state intact.
        saved = (
            req.effective_prompt_len, req.replays, req.replay_skip,
            req.replayed_tokens,
        )
        req.effective_prompt_len = 0
        if not transfer:
            req.replays += 1
        if req.temperature > 0 and self.replay_exact:
            # SAMPLED stream → EXACT replay (TPU_REPLAY_EXACT, default):
            # regenerate the delivered prefix from the prompt through
            # the decode path (counter restarts at 0 and
            # deterministically re-walks the same sample path; the
            # scheduler swallows the re-generated prefix). Re-prefilling
            # the delivered tokens would write their K/V through the
            # prefill kernel, whose bf16 rounding differs from the
            # original decode writes by enough to flip a later sampled
            # token.
            req.replay_skip = len(req.token_ids)
            req.replayed_tokens = 0
        else:
            # FAST replay: re-prefill prompt + delivered tokens
            # (prefill_ids) in one pass and resume at the next position;
            # the sampling-counter offset plane restores the PRNG step
            # (ReplayState.n_sampled) so a sampled stream continues on
            # the SAME counter path. Greedy streams always take this
            # path (argmax is robust to the prefill/decode kernel
            # rounding); sampled streams take it under
            # TPU_REPLAY_EXACT=false, trading possible bf16-rounding
            # token flips for not re-decoding a long delivered prefix.
            req.replay_skip = 0
            req.replayed_tokens = len(req.token_ids)
        cost = len(req.prompt_ids) + req.max_new_tokens
        with self._submit_lock:
            if not self._running or self._drained or self._draining:
                (req.effective_prompt_len, req.replays, req.replay_skip,
                 req.replayed_tokens) = saved
                return False
            try:
                self._pending.put_nowait(req)
            except queue.Full:
                (req.effective_prompt_len, req.replays, req.replay_skip,
                 req.replayed_tokens) = saved
                return False
            self._queued_tokens += cost
            if self.tenant_queue_max and req.tenant:
                self._tenant_queued[req.tenant] = (
                    self._tenant_queued.get(req.tenant, 0) + 1
                )
            if self._tenant_ledger is not None:
                # Keep the fair-share numerator balanced (the pop will
                # note_dequeued); replays bypass the SHEDDERS, not the
                # accounting.
                self._tenant_ledger.note_enqueued(req)
            self._sched_idle = False
        self._work.set()
        if transfer:
            return True
        if req.timeline is not None:
            req.timeline.note_replay(
                "regenerate" if req.replay_skip else "re-prefill",
                self._obs.now(),
            )
        if self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_requests_replayed_total", "model", self.model_name
            )
        if self._logger is not None:
            self._logger.infof(
                "replayed request after restart (%d token(s) already "
                "delivered, %d remaining, mode=%s)",
                len(req.token_ids), req.max_new_tokens - len(req.token_ids),
                "regenerate" if req.replay_skip else "re-prefill",
            )
        return True

    def _on_watchdog_trip(self, reason: str) -> None:
        """Watchdog callback: latch unhealthy and start a graceful
        drain — new submissions get 503 (pointing traffic at healthy
        replicas) while any work the stalled device eventually finishes
        still reaches its callers. The flags hold the submit lock like
        every other writer. With a supervisor attached the trip also
        requests a restart instead of staying latched until an operator
        intervenes."""
        with self._submit_lock:
            self._unhealthy_reason = reason
            self._draining = True
        self._set_state("DEGRADED")
        sup = self._supervisor
        if sup is not None:
            sup.notify_trip(reason)

    # ------------------------------------------------------------------
    # public LLM API
    # ------------------------------------------------------------------

    @property
    def _free_blocks(self) -> list:
        """Free-list view of the paged allocator (kept as the historical
        attribute name — tests and scripts/soak.py watch its length).
        Read-only: all mutation goes through the refcounted
        ``BlockAllocator``."""
        return self._allocator.free_blocks

    @property
    def max_prompt_tokens(self) -> int:
        """Longest admissible prompt: one generated token plus pipelined-
        window overshoot must still fit in max_len (the same invariant the
        admission-room clamp in _dispatch_prefill_chunk enforces)."""
        return self.max_len - 2 - (self.pipeline_depth + 1) * self.window_k

    def _throughput_tps(self) -> float:
        """Tokens/sec estimate for projected-wait shedding: the operator
        prior (TPU_EXPECTED_TPS) wins; otherwise the sliding-window
        AGGREGATE rate across the whole batch (lifecycle.
        AggregateThroughput — a per-request rate underestimates batched
        throughput by ~the batch size and sheds too eagerly); 50 tok/s
        as the cold-start floor so a fresh engine never divides by zero
        or sheds everything."""
        if self._expected_tps > 0:
            return self._expected_tps
        rate = self._tput.rate()
        if rate > 0:
            return rate
        return 50.0

    def _projected_wait_s(self, cost_tokens: int) -> float:
        """Seconds of queue ahead of a request costing ``cost_tokens``
        (prompt + generation budget), from the queue's token backlog
        over the throughput estimate. Reads under the submit lock."""
        return (self._queued_tokens + cost_tokens) / self._throughput_tps()

    def _note_dequeued(self, req: _GenRequest) -> None:
        """Return a popped request's tokens (and its tenant-quota seat)
        to the submit budgets."""
        cost = len(req.prompt_ids) + req.max_new_tokens
        with self._submit_lock:
            self._queued_tokens = max(0, self._queued_tokens - cost)
            if req.tenant and req.tenant in self._tenant_queued:
                left = self._tenant_queued[req.tenant] - 1
                if left > 0:
                    self._tenant_queued[req.tenant] = left
                else:  # drop empty entries: the dict stays O(live tenants)
                    del self._tenant_queued[req.tenant]
        if self._tenant_ledger is not None:
            self._tenant_ledger.note_dequeued(req)

    def shed_retry_after_s(
        self, reason: str, cost: int = 0, tenant: str = ""
    ) -> float:
        """THE Retry-After for every admission shed (ISSUE 13 bugfix:
        several 429 paths answered a near-constant projected wait that
        ignored what actually has to recover). One shared, load-
        sensitive estimate:

        * every reason starts from the queue-drain projection
          (backlog + this request over measured throughput);
        * ``hbm_headroom`` / ``brownout`` add the IN-FLIGHT decode
          backlog — headroom and burn recover as live work retires,
          not merely as the queue drains;
        * ``tenant_quota`` / ``tenant_fair_share`` are floored at the
          TENANT's own queued backlog drain (its seats free as its own
          work completes, however empty the global queue is);
        * with the brownout ladder above L0, the controller's projected
          recovery is the floor — a 429 must not invite a retry into a
          still-degraded pod.

        Always positive (the wire form ceils to an integer ≥ 1).
        Called under the submit lock; every read is host arithmetic."""
        tps = self._throughput_tps()
        # THE queue-drain projection (shared with the deadline check):
        # one formula, one place to change it.
        wait = self._projected_wait_s(max(0, cost))
        if reason in ("hbm_headroom", "brownout"):
            inflight = 0
            for seq in self._slots:
                if seq is not None:
                    inflight += max(
                        0,
                        seq.request.remaining_new_tokens
                        - seq.n_generated,
                    )
            wait += inflight / tps
        if (
            reason in ("tenant_quota", "tenant_fair_share", "tenant_brownout")
            and tenant
            and self._tenant_ledger is not None
        ):
            wait = max(
                wait,
                self._tenant_ledger.tenant_queued_tokens(tenant) / tps,
            )
        bc = self._brownout
        if bc is not None and bc.level > 0:
            wait = max(wait, bc.projected_recovery_s())
        # A tenant-brownout 429 is floored at the TENANT's own ladder
        # recovery — a retry must not land while its rungs still stand.
        cp = self._control
        if reason == "tenant_brownout" and cp is not None and tenant:
            wait = max(wait, cp.tenant_recovery_s(tenant))
        return max(wait, 0.5)

    def _shed(self, reason: str, retry_after_s: float) -> None:
        if self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_requests_shed_total",
                "model", self.model_name, "reason", reason,
            )
        if self._logger is not None:
            self._logger.warnf(
                "shedding request (%s); retry in ~%.0fs",
                reason, retry_after_s,
            )

    def _enqueue(self, req: _GenRequest) -> None:
        # Fault seam: a submit-path failure (serialization bug, OOM in
        # bookkeeping) must reject THIS request, not wedge the engine.
        faults.fire("engine.submit", engine=self, request=req)
        cost = len(req.prompt_ids) + req.max_new_tokens
        # Check-and-enqueue under the drain lock: once the scheduler's final
        # drain has run, nothing may land in the queue (it would hang) —
        # and during a GRACEFUL drain nothing may land either (503; the
        # same lock the scheduler's idle confirmation takes, so a request
        # can never slip in after the drain observed the engine idle).
        with self._submit_lock:
            if self._draining:
                from gofr_tpu.errors import ErrorServiceUnavailable

                raise ErrorServiceUnavailable(
                    "engine draining for shutdown"
                    + (
                        f" (watchdog: {self._unhealthy_reason})"
                        if self._unhealthy_reason else ""
                    )
                    + "; retry against another replica"
                )
            if self._fatal is not None:
                raise RuntimeError(f"engine scheduler died: {self._fatal}")
            if not self._running or self._drained:
                raise RuntimeError("engine not started")
            # Load shedding BEFORE admission (Orca/vLLM treat overload as
            # first-class): a bounded token budget over the submit queue
            # answers 429 + Retry-After instead of queueing unboundedly,
            # and a request whose deadline cannot survive the projected
            # queue wait is rejected NOW — burning a KV slot on a
            # generation nobody will wait for helps no one.
            from gofr_tpu.errors import (
                ErrorDeadlineExceeded,
                ErrorTooManyRequests,
            )

            wait_s = self._projected_wait_s(cost)
            # Per-tenant quota FIRST (TPU_TENANT_QUEUE_MAX): one tenant
            # flooding the queue is shed on ITS OWN budget before it can
            # exhaust the global one for everyone else.
            if (
                self.tenant_queue_max
                and req.tenant
                and self._tenant_queued.get(req.tenant, 0)
                >= self.tenant_queue_max
            ):
                retry = self.shed_retry_after_s(
                    "tenant_quota", cost, req.tenant
                )
                self._shed("tenant_quota", retry)
                raise ErrorTooManyRequests(
                    f"tenant {req.tenant!r} has "
                    f"{self._tenant_queued[req.tenant]} queued request(s) "
                    f"(TPU_TENANT_QUEUE_MAX={self.tenant_queue_max})",
                    retry_after_s=retry,
                )
            # Fairness-aware shedding (TPU_TENANT_FAIR_SHARE, ledger-
            # derived, off by default): a tenant already holding more
            # than its share of the queue budget is shed FIRST — its
            # burst degrades that tenant, not the fleet. Checked before
            # the global budgets so the hog's 429s leave room for
            # everyone else's admissions.
            if (
                self._tenant_ledger is not None
                and self.tenant_fair_share > 0
                and req.tenant
                and self._tenant_ledger.over_fair_share(
                    req.tenant, cost, self.tenant_fair_share,
                    self.queue_max_tokens, self.queue_max,
                )
            ):
                retry = self.shed_retry_after_s(
                    "tenant_fair_share", cost, req.tenant
                )
                self._shed("tenant_fair_share", retry)
                raise ErrorTooManyRequests(
                    f"tenant {req.tenant!r} is over its fair share of "
                    f"the queue budget "
                    f"(TPU_TENANT_FAIR_SHARE={self.tenant_fair_share}); "
                    f"reason=tenant_fair_share",
                    retry_after_s=retry,
                )
            # Per-tenant brownout (serving/control_plane.py): the
            # BURNING tenant's own ladder thins (L2, deterministic AIMD
            # credit) or sheds (L3) its admissions while every other
            # tenant's requests fall straight through — below L2 (and
            # with the plane off or its burn sensor degraded) this is
            # byte-identically admit-everything.
            cp = self._control
            if cp is not None and req.tenant and not cp.tenant_admit(
                req.tenant, req.slo_class
            ):
                retry = self.shed_retry_after_s(
                    "tenant_brownout", cost, req.tenant
                )
                cp.note_action(
                    "tenant_brownout", f"shed_{req.slo_class}"
                )
                self._shed("tenant_brownout", retry)
                raise ErrorTooManyRequests(
                    f"tenant {req.tenant!r} is browned out at level "
                    f"{cp.tenant_level(req.tenant)} (its SLO burn, not "
                    f"the pod's); reason=tenant_brownout",
                    retry_after_s=retry,
                )
            if self.admit_min_headroom > 0:
                # Saturation-aware admission (TPU_ADMIT_MIN_HEADROOM):
                # below the HBM headroom floor new work is shed 429 —
                # the honest answer when the paged pool is nearly full
                # is "retry elsewhere", not a mid-stream
                # kv_pool_exhausted failure after a slot was burned.
                # A non-finite ratio (a telemetry backend answering
                # NaN) must read as "no signal", never as pressure.
                headroom = self.hbm_headroom_ratio()
                if math.isfinite(headroom) and (
                    headroom < self.admit_min_headroom
                ):
                    retry = self.shed_retry_after_s("hbm_headroom", cost)
                    self._shed("hbm_headroom", retry)
                    raise ErrorTooManyRequests(
                        f"HBM headroom {headroom:.3f} below the "
                        f"admission floor {self.admit_min_headroom:.3f} "
                        f"(TPU_ADMIT_MIN_HEADROOM); retry against "
                        f"another replica",
                        retry_after_s=retry,
                    )
            # Brownout L2+ (serving/brownout.py): the effective
            # admission budget is the AIMD-cut fraction of the nominal
            # one, consumed priority-aware — batch may only fill its
            # smaller allowance (it sheds first), interactive keeps the
            # whole cut budget (it sheds last). Below L2 the fraction
            # is exactly 1.0, so this block admits byte-identically.
            bc = self._brownout
            if bc is not None and bc.shedding:
                frac = bc.admission_fraction(req.slo_class)
                if self.queue_max_tokens:
                    over = (
                        self._queued_tokens + cost
                        > frac * self.queue_max_tokens
                    )
                else:
                    over = self._pending.qsize() + 1 > frac * self.queue_max
                if over:
                    retry = self.shed_retry_after_s(
                        "brownout", cost, req.tenant
                    )
                    bc.note_action(f"shed_{req.slo_class}")
                    self._shed("brownout", retry)
                    raise ErrorTooManyRequests(
                        f"brownout level {bc.level}: admission budget "
                        f"cut to {frac:.2f} of nominal for SLO class "
                        f"{req.slo_class!r}; reason=brownout",
                        retry_after_s=retry,
                    )
            if (
                self.queue_max_tokens
                and self._queued_tokens + cost > self.queue_max_tokens
            ):
                retry = self.shed_retry_after_s("queue_tokens", cost)
                self._shed("queue_tokens", retry)
                raise ErrorTooManyRequests(
                    f"submit queue token budget exhausted "
                    f"({self._queued_tokens} queued + {cost} requested > "
                    f"{self.queue_max_tokens}; TPU_QUEUE_TOKENS)",
                    retry_after_s=retry,
                )
            if req.deadline is not None and (
                req.deadline.expired()
                or req.deadline.remaining() <= wait_s
            ):
                self._shed("deadline", wait_s)
                raise ErrorDeadlineExceeded(
                    f"projected queue wait {wait_s:.2f}s exceeds the "
                    f"request deadline "
                    f"({max(req.deadline.remaining(), 0.0):.2f}s left)"
                )
            try:
                self._pending.put_nowait(req)
            except queue.Full:
                retry = self.shed_retry_after_s("queue_full", cost)
                self._shed("queue_full", retry)
                raise ErrorTooManyRequests(
                    f"submit queue full ({self._pending.maxsize} requests; "
                    f"TPU_QUEUE_MAX)",
                    retry_after_s=retry,
                ) from None
            self._queued_tokens += cost
            if self.tenant_queue_max and req.tenant:
                self._tenant_queued[req.tenant] = (
                    self._tenant_queued.get(req.tenant, 0) + 1
                )
            if self._tenant_ledger is not None:
                self._tenant_ledger.note_enqueued(req)
            self._sched_idle = False
        self._work.set()

    def submit_generate(
        self,
        prompt: str | list[int],
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        stop_on_eos: bool = True,
        stop: "Optional[list[str]]" = None,
        top_p: float = 1.0,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        seed: "Optional[int]" = None,
        logit_bias: "Optional[dict]" = None,
        top_logprobs: int = 0,
        adapter: str = "",
        deadline: "Optional[Deadline]" = None,
        deadline_s: "Optional[float]" = None,
        cancel: "Optional[CancelToken]" = None,
        tenant: str = "",
        slo_class: str = "",
        pin_replica: bool = False,
        traceparent: "Optional[str]" = None,
        received: "Optional[float]" = None,
    ) -> _GenRequest:
        if self.family != "llm":
            raise RuntimeError(f"model {self.model_name} is not a generative LLM")
        aid = 0
        if adapter:
            from gofr_tpu.errors import ErrorInvalidParam

            if adapter not in self._lora_names:
                raise ErrorInvalidParam([
                    f"unknown LoRA adapter {adapter!r}; loaded: "
                    f"{sorted(self._lora_names)}"
                ])
            aid = self._lora_names[adapter]
        if not 0.0 < top_p <= 1.0:
            from gofr_tpu.errors import ErrorInvalidParam

            raise ErrorInvalidParam(["top_p must be in (0, 1]"])
        if top_p < 1.0 and not self.enable_top_p:
            from gofr_tpu.errors import ErrorInvalidParam

            raise ErrorInvalidParam([
                "top_p requires TPU_TOP_P=true (compiles the nucleus "
                "sort into the sampler)"
            ])
        if frequency_penalty or presence_penalty:
            from gofr_tpu.errors import ErrorInvalidParam

            if not self.enable_penalties:
                raise ErrorInvalidParam([
                    "frequency/presence penalties require TPU_PENALTIES="
                    "true (compiles the per-slot token-count plane into "
                    "the sampler)"
                ])
            if not (-2.0 <= frequency_penalty <= 2.0
                    and -2.0 <= presence_penalty <= 2.0):
                raise ErrorInvalidParam([
                    "penalties must be in [-2, 2]"
                ])
        if top_logprobs:
            from gofr_tpu.errors import ErrorInvalidParam

            if not 0 < int(top_logprobs) <= self.top_logprobs:
                raise ErrorInvalidParam([
                    f"top_logprobs must be in [1, {self.top_logprobs}] "
                    f"(the engine compiles TPU_TOP_LOGPROBS="
                    f"{self.top_logprobs} alternatives)"
                    if self.top_logprobs else
                    "top_logprobs requires TPU_TOP_LOGPROBS>0 (compiles "
                    "the per-step alternatives top_k into the sampler)"
                ])
        bias: dict = {}
        if logit_bias:
            from gofr_tpu.errors import ErrorInvalidParam

            if not isinstance(logit_bias, dict):
                raise ErrorInvalidParam([
                    "logit_bias must be an object mapping token ids to "
                    "numbers"
                ])
            if len(logit_bias) > LOGIT_BIAS_K:
                raise ErrorInvalidParam([
                    f"logit_bias supports at most {LOGIT_BIAS_K} entries"
                ])
            try:
                if any(
                    isinstance(t, float) and t != int(t) for t in logit_bias
                ):
                    raise ValueError("fractional token id")
                bias = {
                    int(t): float(b) for t, b in logit_bias.items()
                }
            except (TypeError, ValueError):
                raise ErrorInvalidParam([
                    "logit_bias must map integral token ids to numbers"
                ]) from None
            if any(
                not 0 <= t < self.cfg.vocab_size for t in bias
            ) or any(not -100.0 <= b <= 100.0 for b in bias.values()):
                raise ErrorInvalidParam([
                    f"logit_bias token ids must be in [0, "
                    f"{self.cfg.vocab_size}) and biases in [-100, 100]"
                ])
        # Fault seam: a tokenizer failure (corrupt vocab, bad merges row)
        # must 500 this request and leave the engine serving.
        faults.fire("engine.tokenize", prompt=prompt)
        ids = (
            self.tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        )
        # Overlong prompts are REJECTED up front (ErrorPromptTooLong → 413)
        # unless truncation was explicitly enabled, in which case the tail
        # is kept and the result is flagged (VERDICT r1 weak #8: never
        # silently drop prompt content).
        max_prompt = self.max_prompt_tokens
        truncated = False
        if len(ids) > max_prompt:
            if not self.truncate_prompts:
                from gofr_tpu.errors import ErrorPromptTooLong

                raise ErrorPromptTooLong(len(ids), max_prompt)
            ids = ids[-max_prompt:]
            truncated = True
            if self._logger is not None:
                self._logger.warnf(
                    "prompt truncated to its last %d tokens "
                    "(TPU_TRUNCATE_PROMPTS)", max_prompt,
                )
        # Brownout SLO class: an explicit, valid X-SLO-Class wins, then
        # the tenant's configured default (TPU_TENANT_SLO_CLASS), then
        # "standard". Request-controlled, so it is clamped to the
        # bounded vocabulary before it can reach shed metrics.
        cls = self._normalize_slo_class(slo_class)
        if not cls:
            # Case-insensitive tenant match, like the per-tenant SLO
            # override keys (the map stores lower-cased keys).
            cls = self._tenant_class_map.get(
                str(tenant or "").lower(), "standard"
            )
        # L1+ generation clamp (TPU_BROWNOUT_MAX_NEW): trade answer
        # LENGTH for admission capacity before trading admissions. The
        # result advertises the deliberate truncation (`brownout` field
        # + finish_reason="length") so clients see policy, not a bug.
        brownout_clamped = False
        bc = self._brownout
        if bc is not None:
            clamped = bc.clamp_max_new(int(max_new_tokens))
            if clamped < int(max_new_tokens):
                max_new_tokens = clamped
                brownout_clamped = True
                bc.note_action("clamp_tokens")
        # Per-tenant L1+ clamp (serving/control_plane.py): the BURNING
        # tenant's generation budget is cut while everyone else's (and
        # every request below its L1) passes through untouched.
        cp = self._control
        if cp is not None and tenant:
            clamped = cp.tenant_clamp_max_new(tenant, int(max_new_tokens))
            if clamped < int(max_new_tokens):
                max_new_tokens = clamped
                brownout_clamped = True
                cp.note_action("tenant_brownout", "clamp_tokens")
        req = _GenRequest(
            prompt_ids=ids,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            stop_on_eos=stop_on_eos,
            truncated=truncated,
            stop_texts=list(stop or []),
            top_p=top_p,
            frequency_penalty=frequency_penalty,
            presence_penalty=presence_penalty,
            # Unseeded requests draw a fresh seed (distinct streams);
            # int32 range for the device plane.
            seed=(
                int(seed) & 0x7FFFFFFF if seed is not None
                else self._seed_rng.getrandbits(31)
            ),
            logit_bias=bias,
            top_logprobs=int(top_logprobs or 0),
            aid=aid,
            adapter=adapter,
            # Stamp the adapter slot's generation: if the slot is
            # reloaded/unloaded while this request is queued, admission
            # fails it instead of silently serving different weights.
            lora_gen=self._lora_gen[aid] if aid else 0,
            deadline=coalesce_deadline(deadline, deadline_s),
            tenant=str(tenant or ""),
            slo_class=cls,
            brownout_clamped=brownout_clamped,
            pin_replica=pin_replica,
        )
        if cancel is not None:
            # Share the transport's token (HTTP disconnect, gRPC cancel)
            # so tripping it retires this sequence mid-decode.
            req.cancel = cancel
        # Observability: mint the request's lifecycle timeline, adopting
        # the caller's trace context (explicit W3C traceparent from the
        # HTTP/gRPC edge, else the submitting task's current span). None
        # when the whole layer is off — the scheduler hooks all guard.
        req.timeline = self._obs.begin(
            prompt_tokens=len(ids), traceparent=traceparent,
            tenant=str(tenant or ""), received=received,
        )
        try:
            self._enqueue(req)
        except Exception as exc:
            # Shed/rejected before a slot: close the timeline with the
            # shed outcome so the flight recorder pins it and the trace
            # shows WHY admission said no — and charge the tenant's
            # shed count (the fairness signal /debug/tenants names the
            # culprit by).
            self._obs.note_shed(req.timeline, type(exc).__name__)
            if self._tenant_ledger is not None:
                self._tenant_ledger.finish_request(req, "shed")
            raise
        return req

    def register_prefix(
        self, prompt: str | list[int], adapter: str = ""
    ) -> _GenRequest:
        """Prefill a shared prompt prefix ONCE and park its KV rows in the
        device prefix pool; later prompts starting with it skip straight
        to their remainder (admission-time row copy). The request's future
        resolves with the pool row index. Requires ``prefix_slots > 0``
        (``TPU_PREFIX_SLOTS``). With ``adapter``, the prefix prefills
        under that LoRA adapter and only same-adapter requests reuse it."""
        if self.family != "llm":
            raise RuntimeError("prefix registration is for llm engines")
        aid = 0
        if adapter:
            if adapter not in self._lora_names:
                raise KeyError(f"no loaded LoRA adapter {adapter!r}")
            aid = self._lora_names[adapter]
        if self._prefix_pool is None:
            raise RuntimeError(
                "prefix pool disabled — construct the engine with "
                "prefix_slots > 0 (TPU_PREFIX_SLOTS)"
            )
        ids = (
            self.tokenizer.encode(prompt) if isinstance(prompt, str)
            else list(prompt)
        )
        if not ids:
            raise ValueError("prefix must be at least one token")
        if len(ids) > self.max_prompt_tokens:
            from gofr_tpu.errors import ErrorPromptTooLong

            raise ErrorPromptTooLong(len(ids), self.max_prompt_tokens)
        req = _GenRequest(
            prompt_ids=ids, max_new_tokens=1, temperature=0.0,
            stop_on_eos=False, prefix_store=True, aid=aid,
            lora_gen=self._lora_gen[aid] if aid else 0,
        )
        self._enqueue(req)
        return req

    def register_prefix_sync(
        self, prompt: Any, timeout: float = 300.0, adapter: str = ""
    ) -> int:
        return self.register_prefix(prompt, adapter=adapter).future.result(
            timeout=timeout
        )

    def generate_sync(
        self, prompt: Any, timeout: float = 300.0, **kw: Any
    ) -> GenerationResult:
        return self.submit_generate(prompt, **kw).future.result(timeout=timeout)

    async def generate(self, prompt: Any, **kw: Any) -> GenerationResult:
        req = self.submit_generate(prompt, **kw)
        return await asyncio.wrap_future(req.future)

    async def generate_stream(
        self, prompt: Any, **kw: Any
    ) -> "AsyncIterator[int]":
        """Async iterator over generated token ids."""
        req = self.submit_generate(prompt, **kw)
        while True:
            tok = await next_token(req.stream)
            if tok is None:
                return
            yield tok


    def mesh_topology(self) -> Optional[dict]:
        """The serving mesh's shape (axes, device count, device names)
        or ``None`` when unsharded — advertised through health probes,
        pool replica descriptors, and ``/debug/flight`` so an operator
        can see each replica's pod layout (dp across replicas, tp
        within) without shelling into it."""
        from gofr_tpu.parallel.mesh import mesh_topology

        return mesh_topology(self.mesh)

    # ------------------------------------------------------------------
    # device-resource observability (serving/device_telemetry.py)
    # ------------------------------------------------------------------

    def _device_memory_stats(self) -> Optional[dict]:
        """The runtime memory accounting of this engine's (first)
        device, None on backends without it (CPU)."""
        try:
            stats = self.devices[0].memory_stats()
            return dict(stats) if stats else None
        except Exception:  # graftlint: disable=GL006 — gauge-only path; memory_stats support varies by backend
            return None

    def _build_hbm_ledger(self) -> None:
        """Account every device-resident component this boot allocated
        into an :class:`HBMLedger`. Sizes are attribute reads on
        already-built arrays — no device traffic — and fixed per boot,
        so this runs once per (re)start."""
        from gofr_tpu.serving.device_telemetry import (
            HBMLedger,
            tree_device_bytes,
        )

        layers = (
            self.params.get("layers", {})
            if isinstance(self.params, dict) else {}
        )
        lora_bytes = sum(
            tree_device_bytes(v) for k, v in layers.items()
            if k.endswith("_lora_a") or k.endswith("_lora_b")
        )
        components: dict[str, int] = {
            "params": tree_device_bytes(self.params) - lora_bytes,
        }
        if lora_bytes:
            components["lora"] = lora_bytes
        block_bytes = n_blocks = 0
        if self.family == "llm":
            cache = self.cache
            # Exactly the pool's own hbm_bytes() — the ledger must
            # agree with the allocator's accounting to the byte
            # (tests pin this at tp=1 AND tp=2).
            components["kv_pool"] = cache.hbm_bytes()
            workspace = tree_device_bytes([
                cache.lengths, getattr(cache, "block_table", None),
                self._tokens_dev, self._logps_dev, self._nsteps_dev,
                self._seeds_dev, self._noff_dev, self._aids_dev,
                self._active_dev, self._temps_dev, self._topp_dev,
                self._greedy_dev, self._pcounts_dev, self._fpen_dev,
                self._ppen_dev, self._bidx_dev, self._bval_dev,
                self._topi_dev, self._topl_dev,
            ])
            components["workspace"] = workspace
            if self._prefix_pool is not None:
                components["prefix_pool"] = self._prefix_pool.hbm_bytes()
            if self.kv_block:
                block_bytes = cache.block_bytes()
                n_blocks = cache.n_blocks
        self._ledger = HBMLedger(
            components,
            mesh_devices=(
                int(self.mesh.devices.size) if self.mesh is not None else 1
            ),
            block_bytes=block_bytes,
            n_blocks=n_blocks,
            budget_bytes=self.hbm_budget_bytes,
            device_stats=self._device_memory_stats,
        )
        self._ledger.publish(self._metrics, self.model_name)

    def hbm_ledger(self) -> dict:
        """The HBM ledger's snapshot (components, totals, budget,
        headroom, platform cross-check) — ``/debug/capacity``'s hbm
        block and the health detail."""
        if self._ledger is None:
            return {}
        return dict(self._ledger.snapshot(self._ledger_free_blocks()))

    def _ledger_free_blocks(self) -> int:
        if self.family == "llm" and self.kv_block:
            return int(self._allocator.n_free)
        return 0

    def _kv_pool_counts(self) -> tuple[int, int, int]:
        """Paged-pool pressure counts ``(total, used, cached)`` —
        allocatable blocks (block 0 parks), blocks held by live tables
        or the radix index, and the radix-cached (reclaimable) subset.
        The ONE accounting both the scheduler's gauge pass and
        ``capacity_report`` read, so Prometheus and /debug/capacity can
        never disagree."""
        total = self.cache.n_blocks - 1
        used = max(0, total - self._allocator.n_free)
        cached = (
            self._radix.n_cached_blocks if self._radix is not None else 0
        )
        return total, used, cached

    def hbm_headroom_ratio(self) -> float:
        """THE saturation signal: fraction of the per-device HBM budget
        currently free (budget slack + free paged-KV blocks). Read by
        admission shedding (TPU_ADMIT_MIN_HEADROOM), the radix eviction
        watermark (TPU_PREFIX_EVICT_HBM_FRAC), and the pool scaler
        (TPU_SCALE_UP_HEADROOM). O(1) host arithmetic."""
        if self._ledger is None:
            return 1.0
        return float(
            self._ledger.headroom_ratio(self._ledger_free_blocks())
        )

    def mark_steady_state(self) -> None:
        """Arm the compile tracker's warm-up fence: every XLA compile
        after this call counts (and warns) as a steady-state recompile
        — always a fixed-shape-discipline bug. Bench calls this after
        its warm-up phase; operators after a canary sweep."""
        self._compiles.mark_warm()

    def compile_stats(self) -> dict:
        """The compile tracker's snapshot: per-program compile counts
        and wall clock, the steady-state recompile count, and whether
        the warm-up fence is armed."""
        return dict(self._compiles.snapshot())

    def tenant_report(self) -> dict:
        """The tenant ledger's full unclamped table (``/debug/tenants``
        on the ops port): per-tenant tokens, KV-block·seconds, outcome
        counts, live queue share, and the conservation anchor.
        ``{"enabled": False}`` when the layer is off
        (``TPU_TENANT_LEDGER=0``)."""
        if self._tenant_ledger is None:
            return {"enabled": False}
        report = dict(self._tenant_ledger.snapshot())
        report["fair_share"] = self.tenant_fair_share
        return report

    def slo_report(self) -> dict:
        """The SLO engine's burn-rate state (``/debug/slo`` on the ops
        port). ``{"enabled": False}`` when no objective is configured."""
        if self._slo is None:
            return {"enabled": False}
        return dict(self._slo.snapshot())

    def brownout_report(self) -> dict:
        """The brownout controller's full state (``/debug/brownout`` on
        the ops port): ladder level, AIMD budget factor, thresholds,
        last control inputs, per-action counters. ``{"enabled": False}``
        with the layer off (``TPU_BROWNOUT=0`` or no SLOs configured —
        the burn rate is the control signal)."""
        if self._brownout is None:
            return {"enabled": False}
        return dict(self._brownout.snapshot())

    def control_report(self) -> dict:
        """The control plane's full state (``/debug/control`` on the
        ops port): per-signal guard state, per-loop mode + hold-down
        timers, the decision ring. ``{"enabled": False}`` when the
        layer is off (``TPU_CONTROL_PLANE=0`` or a non-LLM family)."""
        if self._control is None:
            return {"enabled": False}
        return dict(self._control.snapshot())

    def control_scale_pressure(self) -> Optional[int]:
        """The control plane's scale-up advertisement (1 = the
        host-overhead or predictive loop asserts pressure), ``None``
        when the plane is off — the pool scaler's None-vs-0 distinction
        (signal absent vs armed-and-calm), mirroring
        :meth:`brownout_level`."""
        if self._control is None:
            return None
        return int(self._control.scale_pressure())

    def attach_async_lag(
        self,
        read: "Callable[[], float]",
        *,
        depth: Optional[float] = None,
        sustain_s: Optional[float] = None,
    ) -> bool:
        """Register the async serving plane's consumer-lag sensor with
        the control plane (``serving/async_serving.py`` calls this at
        plane construction): sustained backlog then feeds PoolScaler
        pressure through :meth:`control_scale_pressure` like any other
        scaling loop. ``depth``/``sustain_s`` > 0 re-point the lag
        loop's thresholds; False = control plane off (signal skipped —
        off is off)."""
        cp = self._control
        if cp is None:
            return False
        if (depth is not None and depth > 0) or (
            sustain_s is not None and sustain_s > 0
        ):
            cp.async_loop.configure(
                depth if depth and depth > 0 else cp.async_loop.depth,
                sustain_s if sustain_s and sustain_s > 0
                else cp.async_loop.sustain_s,
            )
        cp.register("async_lag", read)
        return True

    def brownout_level(self) -> Optional[int]:
        """The current degradation level, ``None`` when the layer is
        off (``TPU_BROWNOUT=0`` / no SLOs) — the distinction matters to
        the pool, where None means "signal absent" (never suppress
        hedges/probes or count scaler pressure) while 0 means "armed
        and nominal"."""
        return None if self._brownout is None else self._brownout.level

    def slo_compliant(self) -> Optional[bool]:
        """THE routing signal (ReplicaPool.pick deprioritizes on it,
        closing the ROADMAP item): the SLO engine's compliance bit AND
        the brownout ladder below L3. None when no SLOs are
        configured. Reads the CACHED bit — pick() calls this per
        candidate per request, and a full ring scan there would contend
        with the retirement path under exactly the overload this signal
        exists for; every observation and health/probe pass refreshes
        the cache."""
        if self._brownout is not None and not self._brownout.routable():
            return False
        if self._slo is None:
            return None
        return bool(self._slo.compliant_cached())

    def _loop_context(self) -> dict[str, Any]:
        """The serving state a loop-anomaly record freezes at the stall
        instant (queue depth, occupancy, brownout level, HBM headroom —
        what an operator needs to tell "overloaded" from "wedged").
        Called on the scheduler thread only, host values already in
        hand — no device pulls."""
        in_use = sum(1 for s in self._slots if s is not None)
        ctx: dict[str, Any] = {
            "queue_depth": int(self._pending.qsize()),
            "wait_kv": len(self._wait_kv),
            "prefilling": len(self._prefilling),
            "occupancy": round(in_use / max(1, self.n_slots), 6),
            "hbm_headroom_ratio": round(self.hbm_headroom_ratio(), 6),
            "brownout_level": self.brownout_level(),
        }
        if self.kv_block:
            ctx["kv_blocks_free"] = int(self._allocator.n_free)
        if self._control is not None:
            # Which sensors were degraded at the stall instant — a
            # stall that coincides with a lying sensor is a different
            # investigation than one under healthy signals.
            ctx["control_degraded"] = sorted(
                name
                for name, health in self._control.signal_health().items()
                if health < 1.0
            )
        return ctx

    def loop_report(self) -> dict:
        """The scheduler-loop profiler's full state (``/debug/loop`` on
        the ops port): per-phase rolling stats, utilization /
        host-overhead ratio, stall thresholds, anomaly rings, and the
        profiler's own measured overhead. ``{"enabled": False}`` when
        the layer is off (``TPU_LOOP_PROFILE=0``)."""
        if self._loop_prof is None:
            return {"enabled": False}
        return dict(self._loop_prof.snapshot())

    def capacity_report(self) -> dict:
        """``/debug/capacity``'s per-engine record: the HBM ledger,
        compile counts, paged-pool pressure, and the heaviest tenants
        in one read."""
        report: dict[str, Any] = {
            "model": self.model_name,
            "state": self._state,
            "hbm": self.hbm_ledger(),
            "compiles": self.compile_stats(),
        }
        if self._loop_prof is not None:
            # "Where do the passes go" next to "how full is the
            # device" — the loop-time signal beside the byte signal.
            report["loop"] = self._loop_prof.describe()
        if self._tenant_ledger is not None:
            # "Which tenant filled it" next to "how full is it".
            report["tenants"] = self._tenant_ledger.top_tenants()
        if self._slo is not None:
            report["slo"] = self._slo.describe()
        if self._brownout is not None:
            # "Is this pod browning out" next to "is it breaking its
            # promise" — the actuator's state beside its signal.
            report["brownout"] = self._brownout.describe()
        if self._control is not None:
            # The control plane's headline: scale pressure, degraded
            # sensors, and how many tenants are on their own ladder.
            report["control"] = self._control.describe()
        if self.family == "llm" and self.kv_block:
            total, used, cached = self._kv_pool_counts()
            pool: dict[str, Any] = {
                "block_tokens": self.kv_block,
                "total_blocks": total,
                "free_blocks": total - used,
                "used_blocks": used,
                "occupancy_ratio": round(used / max(1, total), 6),
                "evict_watermark": self.effective_evict_watermark,
                "evict_watermark_source": (
                    "explicit" if self.prefix_evict_watermark > 0
                    else (
                        "hbm_frac" if self.effective_evict_watermark > 0
                        else "off"
                    )
                ),
            }
            if self._radix is not None:
                pool["cached_blocks"] = cached
                pool["fragmentation_ratio"] = round(
                    cached / used, 6
                ) if used else 0.0
            report["kv_pool"] = pool
        return report

    def flight_records(self) -> dict:
        """The flight recorder's current contents (``/debug/flight`` on
        the ops port): the ring of recent request timelines plus the
        pinned slow/errored ones. ``{"enabled": False}`` when the
        recorder is off (TPU_FLIGHT_RECORDER=0)."""
        recorder = self._obs.recorder
        if recorder is None:
            return {"enabled": False}
        out = {
            "enabled": True,
            # The device-resource headline rides every flight read: an
            # operator chasing tail latency sees HBM pressure and
            # steady-state recompiles next to the slow timelines.
            "hbm_headroom_ratio": round(self.hbm_headroom_ratio(), 6),
            "steady_state_recompiles": (
                self._compiles.steady_state_recompiles
            ),
            **recorder.snapshot(),
        }
        if self._tenant_ledger is not None:
            # The attribution headline: slow-timeline readers see WHO
            # holds the pool without a second request.
            out["tenants"] = self._tenant_ledger.top_tenants()
        if self._loop_prof is not None:
            # The loop headline (the headroom idiom): slow timelines
            # next to "was the scheduler itself stalling".
            out["loop"] = self._loop_prof.describe()
        return out

    def kv_bytes_per_token(self) -> int:
        """Bytes of KV cache one token position holds, from the arrays as
        allocated: every cache entry's keys and values, and the scales of
        an int8 cache; for a latent cache every entry's one row; for a
        hybrid cache K, V and the compressed keys (what grows with tokens:
        ``state_bytes_per_slot`` is the rest). Each cache says its own
        (``ops/kv_cache.py`` ``bytes_per_token``)."""
        return self.cache.bytes_per_token()

    def state_bytes_per_slot(self) -> int:
        """Bytes a slot holds whatever its length, from the arrays as
        allocated: a hybrid cache's lightning states; 0 for every other."""
        return int(self.cache.state_bytes_per_slot)

    def health_check(self) -> dict:
        details: dict[str, Any] = {
            "model": self.model_name,
            "family": self.family,
            # The device(s) THIS engine lives on, as JAX reports them —
            # how a caller over HTTP tells a TPU engine from a CPU one.
            "platform": self.devices[0].platform,
            "device_kind": self.devices[0].device_kind,
            "devices": [str(d) for d in self.devices],
            "running": self._running,
            # Supervision state machine (serving/supervisor.py):
            # SERVING → DEGRADED (trip/crash detected) → RESTARTING
            # (supervisor recovering) → DOWN (stopped or restart budget
            # exhausted). Inside details so it rides the typed gRPC
            # HealthReply's details_json too.
            "state": self._state,
        }
        mesh_topo = self.mesh_topology()
        if mesh_topo is not None:
            # Pod shape: a pool probing this replica (in-proc or over
            # HTTP) lifts the mesh from the health payload into its
            # descriptors — dp across replicas, tp within each.
            details["mesh"] = mesh_topo
        sup = self._supervisor
        if sup is not None:
            details["supervisor"] = sup.describe()
        unhealthy = self._unhealthy_reason
        if self._watchdog is not None or unhealthy is not None:
            details["watchdog"] = {
                "tripped": unhealthy is not None,
                "reason": unhealthy or "",
                "bound_s": (
                    self._watchdog.bound_s
                    if self._watchdog is not None else 0.0
                ),
            }
        if self.family == "llm":
            details["kv_slots"] = {
                "total": self.n_slots,
                "in_use": sum(1 for s in self._slots if s is not None),
            }
            details["max_len"] = self.max_len
            details["kv_bytes_per_token"] = self.kv_bytes_per_token()
            details["prefill_temp_bytes"] = self.prefill_temp_bytes
            if self.state_bytes_per_slot():
                details["state_bytes_per_slot"] = self.state_bytes_per_slot()
            details["pending"] = self._pending.qsize()
            details["prefilling"] = len(self._prefilling)
            # Disaggregated-tier role (TPU_REPLICA_ROLES): which serving
            # phase this engine owns in its pool ("fused" = both).
            details["tier_role"] = self.tier_role
            # Advertised capability set: a replica pool fronting this
            # engine over HTTP reads the loaded adapters from the health
            # payload to route LoRA requests only where their weights
            # actually live (service/replica_pool.py).
            details["lora_adapters"] = self.lora_names()
            if self.kv_block:
                details["kv_blocks"] = {
                    "block": self.kv_block,
                    "total": self.cache.n_blocks - 1,  # block 0 parks
                    "free": len(self._free_blocks),
                }
                if self._radix is not None:
                    details["prefix_cache"] = {
                        "cached_blocks": self._radix.n_cached_blocks,
                        "lookups": self._prefix_lookups,
                        "hit_tokens": self._prefix_hit_tokens,
                    }
                    # Prefill-source capability (export_cached): a pool
                    # probing this replica over HTTP reads this to
                    # discover that finished KV blocks can be PULLED
                    # from here through /ops/tier-export — the
                    # multi-host disaggregation seam. "dma" says the
                    # process can stage transfer-server handles (the
                    # cheap control-plane reply) as well as inline wire
                    # bodies.
                    details["tier_source"] = {
                        "export": True,
                        "dma": True,
                    }
        if self._ledger is not None:
            # Device-resource observability: the ledger's compact form
            # (components + headroom) rides health so pool probes —
            # in-proc and over HTTP — lift the saturation signal into
            # their replica descriptors without another endpoint.
            snap = self.hbm_ledger()
            details["hbm_ledger"] = {
                "components": snap.get("components", {}),
                "total_bytes": snap.get("total_bytes", 0),
                "per_device_bytes": snap.get("per_device_bytes", 0),
                "budget_bytes": snap.get("budget_bytes", 0),
                "budget_source": snap.get("budget_source", ""),
                "headroom_ratio": snap.get("headroom_ratio", 1.0),
            }
            details["compiles"] = {
                "total": self._compiles.total,
                "steady_state_recompiles": (
                    self._compiles.steady_state_recompiles
                ),
            }
            if self._compiles.cache_info is not None:
                # Persistent compile-cache provenance: process
                # restarts re-load executables from here instead of
                # recompiling.
                details["compiles"]["compile_cache"] = dict(
                    self._compiles.cache_info
                )
        if self._slo is not None:
            # SLO advertisement: pool probes (in-proc and over HTTP)
            # lift compliance + fast-window burn into their replica
            # descriptors, the same path the HBM headroom rides.
            details["slo"] = self._slo.describe()
        if self._brownout is not None:
            # Brownout advertisement rides the same probe path: remote
            # pools lift the level to suppress hedges/probes against a
            # browning-out replica and to deprioritize it at L3.
            details["brownout"] = self._brownout.describe()
        if self._control is not None:
            # Control-plane advertisement (the same probe path): remote
            # pools lift `scale_pressure` into their descriptors so the
            # scaler sees the host-overhead/predictive loops' verdict
            # without another endpoint.
            details["control"] = self._control.describe()
        if self._loop_prof is not None:
            # Scheduler-loop advertisement (the headroom idiom): probes
            # and health readers see utilization / host-overhead /
            # stall counts without the full /debug/loop read.
            details["loop"] = self._loop_prof.describe()
        if self._tenant_ledger is not None:
            details["tenant_ledger"] = {
                "tenants": len(self._tenant_ledger.snapshot()["tenants"]),
                "fair_share": self.tenant_fair_share,
            }
        # Runtime HBM accounting per device of this engine; backends
        # without it (CPU) return None and the block is omitted.
        hbm = [
            {
                "device": str(d),
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
            for d in self.devices
            if (stats := d.memory_stats())
        ]
        if hbm:
            details["hbm"] = hbm
        status = (
            "UP"
            if self._running and unhealthy is None
            and self._state == "SERVING"
            else "DOWN"
        )
        return {"status": status, "state": self._state, "details": details}
