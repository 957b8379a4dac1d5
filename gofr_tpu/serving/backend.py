"""TPU backend container member (net-new; SURVEY §2.6 maps it onto the
reference's datasource idiom: config-gated init in the container like
``container/container.go:81-83``, health check like ``sql/health.go:27``).

``new_tpu_from_config`` is the container seam. It is gated on ``TPU_MODEL``
so apps that don't serve models never import jax.
"""

from __future__ import annotations

import importlib
import threading
import traceback
from typing import Any, Optional


def new_tpu_from_config(
    config: Any, logger: Any = None, metrics: Any = None
) -> Optional[object]:
    model = config.get_or_default("TPU_MODEL", "")
    if not model:
        return None
    from gofr_tpu.compile_cache import enable_compile_cache
    from gofr_tpu.serving.engine import InferenceEngine

    try:
        # Before the first jit of a serving process: JAX keeps whichever
        # cache directory its first compile saw.
        enable_compile_cache()
        # The attention kernels' modules (jax.experimental.pallas and
        # Mosaic behind them) are about a second of imports that the
        # first trace of a serving program would otherwise make. The
        # device's runtime takes seconds to start, with the interpreter
        # lock released: import them beside it, and start it HERE, before
        # the engine's constructor has Python of its own to run (left to
        # the constructor's first device touch, the import and the
        # constructor share the lock, and a boot costs 1.3 s more:
        # PERF.md, PR 30).
        threading.Thread(
            target=importlib.import_module, args=("gofr_tpu.ops.pallas",),
            name="tpu-kernel-import", daemon=True,
        ).start()
        import jax

        jax.default_backend()
        # Replica tier (docs/advanced-guide/resilience.md): TPU_REPLICAS
        # > 1 and/or TPU_REPLICA_ADDRS front the engine(s) with a
        # health-aware failover router — container.tpu becomes the POOL
        # (engine-shaped facade), so every serving surface routes
        # through it unchanged.
        n_replicas = int(config.get_or_default("TPU_REPLICAS", "1"))
        remote_addrs = [
            a.strip()
            for a in config.get_or_default(
                "TPU_REPLICA_ADDRS", ""
            ).split(",")
            if a.strip()
        ]
        if n_replicas > 1 or remote_addrs:
            return _new_tpu_pool_from_config(
                config, max(1, n_replicas), remote_addrs, logger, metrics
            )
        engine = InferenceEngine.from_config(config, logger=logger, metrics=metrics)
        if logger is not None:
            logger.infof("TPU backend initialised with model %s", model)
        return engine
    except Exception as exc:
        # The datasource idiom: a backend that cannot start is logged
        # and left None, and the app still boots (completions then 400).
        # An OOM at 7B width or a Mosaic refusal lands HERE, so the line
        # carries the exception type and the traceback, not only str().
        if logger is not None:
            logger.errorf(
                "could not initialise TPU backend: %s: %s\n%s",
                type(exc).__name__, exc, traceback.format_exc(),
            )
        return None


def _parse_replica_roles(
    config: Any, n_total: int, logger: Any
) -> list[str]:
    """``TPU_REPLICA_ROLES`` — comma-separated tier roles applied
    positionally across the pool's replicas (in-proc engines first,
    then remote addresses); replicas past the list's end default to
    ``fused``. ``"prefill,decode"`` is the canonical disaggregated
    pair. Unknown role names fail construction loudly — silently
    serving fused under a typo'd topology would defeat the operator's
    explicit disaggregation."""
    raw = config.get_or_default("TPU_REPLICA_ROLES", "")
    roles = [r.strip().lower() for r in raw.split(",") if r.strip()]
    for role in roles:
        if role not in ("fused", "prefill", "decode"):
            raise ValueError(
                f"TPU_REPLICA_ROLES entry {role!r} is not one of "
                f"fused|prefill|decode"
            )
    if roles and len(roles) > n_total and logger is not None:
        logger.warnf(
            "TPU_REPLICA_ROLES names %d role(s) but the pool has %d "
            "replica(s); extras ignored", len(roles), n_total,
        )
    return (roles + ["fused"] * n_total)[:n_total]


def _new_tpu_pool_from_config(
    config: Any,
    n_replicas: int,
    remote_addrs: list,
    logger: Any,
    metrics: Any,
) -> Any:
    """Build the replica pool: N in-process engines (each with its own
    supervisor when TPU_RESTART_MAX is set) plus one HTTPReplica per
    remote address, fronted by a ReplicaPool with the probe/hedge knobs
    (TPU_PROBE_INTERVAL_S / TPU_PROBE_TIMEOUT_S / TPU_HEDGE_DELAY_S /
    TPU_HEDGE_BUDGET). In-proc replicas share the same config — same
    params and engine seed — so cross-replica replay continues streams
    byte-identically.

    TPU_REPLICA_ROLES splits the pool into disaggregated prefill/
    decode tiers (docs/advanced-guide/resilience.md): prefill replicas
    ship finished KV blocks to decode replicas, budgeted by
    TPU_TRANSFER_RETRIES / TPU_TRANSFER_TIMEOUT_S, and every failure
    degrades back to fused serving."""
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.lifecycle import HedgeBudget
    from gofr_tpu.service import new_http_service
    from gofr_tpu.service.pool_scaler import PoolScaler
    from gofr_tpu.service.replica_pool import (
        EngineReplica,
        HTTPReplica,
        ReplicaPool,
    )

    def truthy(key: str, default: str) -> bool:
        return config.get_or_default(key, default).lower() in (
            "1", "true", "yes",
        )

    roles = _parse_replica_roles(
        config, n_replicas + len(remote_addrs), logger
    )
    if any(r != "fused" for r in roles):
        # Tier transfers ship paged blocks into the importer's radix
        # index: without TPU_KV_BLOCK + TPU_AUTO_PREFIX the tier still
        # WORKS (requests re-prefill on the decode replica — fused
        # import), it just never gets the saved prefill. Say so once at
        # boot instead of letting the operator chase a silent perf gap.
        if logger is not None and (
            int(config.get_or_default("TPU_KV_BLOCK", "0")) <= 0
            or not truthy("TPU_AUTO_PREFIX", "false")
        ):
            logger.warnf(
                "TPU_REPLICA_ROLES set without TPU_KV_BLOCK>0 + "
                "TPU_AUTO_PREFIX=true: tier transfers will re-prefill "
                "on the decode tier instead of aliasing shipped KV "
                "blocks"
            )

    # Device layout: dp across replicas, tp (× cp) within each. Every
    # in-proc replica gets its own DISJOINT slice of the device list —
    # a GSPMD pod of tp·cp chips (TPU_TP / TPU_MESH_CP), or ONE chip for
    # an unsharded engine, which pins its params and cache there (four
    # 7B engines stacked on chip 0 do not fit 16 GB). Without enough
    # devices to cover every replica disjointly, overflow replicas
    # share the first slice (correct, just without the parallel
    # speedup, and HBM permitting) and the shortfall is logged once
    # instead of the operator chasing a silent gap.
    import jax

    from gofr_tpu.parallel.mesh import partition_devices

    tp = int(
        config.get_or_default(
            "TPU_TP", config.get_or_default("TPU_MESH_TP", "1")
        )
    )
    cp = int(config.get_or_default("TPU_MESH_CP", "1"))
    pod_size = max(1, tp) * max(1, cp)
    all_devices = list(jax.devices())
    if len(all_devices) < pod_size:
        # Not even ONE pod fits: fail at the seam with the real
        # arithmetic instead of letting make_mesh crash after a log
        # line that promised degraded boot.
        raise ValueError(
            f"sharded pool: one pod needs tp·cp={pod_size} "
            f"device(s) but only {len(all_devices)} are visible — "
            f"lower TPU_TP/TPU_MESH_CP or add devices"
        )
    if len(all_devices) < pod_size * n_replicas and logger is not None:
        logger.warnf(
            "replica pool wants %d devices (%d replica(s) × tp·cp=%d) "
            "but only %d are visible: replicas past the last full slice "
            "share the first slice's devices",
            pod_size * n_replicas, n_replicas, pod_size,
            len(all_devices),
        )
    device_groups = partition_devices(all_devices, pod_size, n_replicas)

    replicas: list = []
    for i in range(n_replicas):
        engine = InferenceEngine.from_config(
            config, logger=logger, metrics=metrics,
            devices=device_groups[i],
        )
        replicas.append(
            EngineReplica(f"engine-{i}", engine, role=roles[i])
        )
    # Remote replicas stream by default (TPU_REMOTE_STREAM): the pool
    # consumes the remote's SSE with the include_tokens extension, so
    # streaming requests route to remote pods and a remote that dies
    # mid-stream fails over to a sibling. They share the in-proc
    # tokenizer (same model across the pool) so string prompts encode
    # locally and the delivered-token prefix is reconstructable.
    remote_stream = truthy("TPU_REMOTE_STREAM", "true")
    shared_tokenizer = next(
        (r.engine.tokenizer for r in replicas), None
    )
    # Wire-leg tier transfers (TPU_REPLICA_OPS_ADDRS, positional like
    # TPU_REPLICA_ADDRS): each remote's OPS/metrics address hosts the
    # POST /ops/tier-import endpoint — with one configured, a remote
    # decode replica can adopt shipped KV blocks over the wire instead
    # of forcing the fused fallback. Empty entries leave that replica
    # wire-import-incapable (unary remotes, older pods).
    ops_addrs = [
        a.strip()
        for a in config.get_or_default("TPU_REPLICA_OPS_ADDRS", "").split(",")
    ] if config.get_or_default("TPU_REPLICA_OPS_ADDRS", "") else []
    for j, addr in enumerate(remote_addrs):
        ops_addr = ops_addrs[j] if j < len(ops_addrs) else ""
        replicas.append(
            HTTPReplica(
                addr,
                new_http_service(addr, logger, metrics),
                stream=remote_stream,
                tokenizer=shared_tokenizer,
                idle_timeout_s=float(
                    config.get_or_default("TPU_REMOTE_STREAM_IDLE_S", "30")
                ),
                role=roles[n_replicas + j],
                import_service=(
                    new_http_service(ops_addr, logger, metrics)
                    if ops_addr else None
                ),
                metrics=metrics,
                logger=logger,
            )
        )
    pool = ReplicaPool(
        replicas,
        hedge_delay_s=float(
            config.get_or_default("TPU_HEDGE_DELAY_S", "2.0")
        ),
        hedge_budget=HedgeBudget(
            burst=float(config.get_or_default("TPU_HEDGE_BUDGET", "8")),
            rate_per_s=float(
                config.get_or_default("TPU_HEDGE_RATE_PER_S", "2")
            ),
        ),
        probe_interval_s=float(
            config.get_or_default("TPU_PROBE_INTERVAL_S", "30")
        ),
        probe_timeout_s=float(
            config.get_or_default("TPU_PROBE_TIMEOUT_S", "30")
        ),
        # Weighted routing: least-estimated-completion-time over the
        # per-replica measured tokens/sec; false = raw queue length.
        weighted=config.get_or_default(
            "TPU_ROUTE_WEIGHTED", "true"
        ).lower() in ("1", "true", "yes"),
        # Tier-transfer budget: extra import attempts past the first
        # and the transfer-wide wall-clock bound.
        transfer_retries=int(
            config.get_or_default("TPU_TRANSFER_RETRIES", "2")
        ),
        transfer_timeout_s=float(
            config.get_or_default("TPU_TRANSFER_TIMEOUT_S", "10")
        ),
        # Leg pin (default: automatic dma → device → wire → host
        # ladder).
        transfer_leg=config.get_or_default("TPU_TRANSFER_LEG", ""),
        # Remote prefill-source pull budget (0 disables the pull
        # plane).
        source_timeout_s=float(
            config.get_or_default("TPU_SOURCE_TIMEOUT_S", "2.0")
        ),
        metrics=metrics,
        logger=logger,
    )
    # Load-adaptive scaling (docs/advanced-guide/resilience.md):
    # TPU_POOL_MAX_REPLICAS above the configured fleet arms a PoolScaler
    # that spawns in-proc engine replicas under sustained queue pressure
    # and drains them (stop-routing → bounded completion → retire) when
    # idle. Bounds: TPU_POOL_MIN_REPLICAS / TPU_POOL_MAX_REPLICAS;
    # sustain windows: TPU_SCALE_UP_WAIT_S / TPU_SCALE_DOWN_WAIT_S.
    max_replicas = int(config.get_or_default("TPU_POOL_MAX_REPLICAS", "0"))
    if max_replicas > len(replicas):
        counter = [len(replicas)]

        def spawn_engine_replica() -> Any:
            # Scaled pods land on a device slice no LIVE in-proc
            # replica currently holds (remote replicas consume no local
            # devices, and a drained replica's slice frees for reuse) —
            # a spawn counter would double-occupy slice 0 while free
            # slices sat idle. Only past the last free slice does a
            # spawn share slice 0, mirroring the boot-time fallback.
            slices = partition_devices(
                all_devices, pod_size,
                max(1, len(all_devices) // pod_size),
            )
            held = set()
            for replica in pool.replicas:
                held_by = getattr(replica, "engine", None)
                if held_by is not None:
                    held.add(frozenset(str(d) for d in held_by.devices))
            spawn_devices = next(
                (
                    s for s in slices
                    if frozenset(str(d) for d in s) not in held
                ),
                slices[0],
            )
            engine = InferenceEngine.from_config(
                config, logger=logger, metrics=metrics,
                devices=spawn_devices,
            )
            engine.start_sync()
            counter[0] += 1
            return EngineReplica(f"engine-scaled-{counter[0]}", engine)

        pool.scaler = PoolScaler(
            pool,
            spawn_engine_replica,
            min_replicas=int(config.get_or_default(
                "TPU_POOL_MIN_REPLICAS", str(len(replicas))
            )),
            max_replicas=max_replicas,
            up_load_per_replica=float(config.get_or_default(
                "TPU_SCALE_UP_LOAD", "4"
            )),
            down_load_per_replica=float(config.get_or_default(
                "TPU_SCALE_DOWN_LOAD", "0.5"
            )),
            # Saturation-aware scale-up (device_telemetry headroom):
            # a serving replica below this HBM headroom ratio counts
            # as pressure even with a shallow queue. 0 = off.
            up_headroom_floor=float(config.get_or_default(
                "TPU_SCALE_UP_HEADROOM", "0"
            )),
            # Brownout-aware scale-up (serving/brownout.py): a replica
            # holding L2+ is shedding admissions — that is demand, not
            # idleness. Default on; the signal only exists when the
            # brownout layer is armed.
            up_on_brownout=config.get_or_default(
                "TPU_SCALE_UP_BROWNOUT", "1"
            ).lower() not in ("0", "false", "no"),
            # Control-plane scale-up (serving/control_plane.py): a
            # replica whose host-overhead or predictive loop holds
            # scale pressure is asking for capacity BEFORE the queue
            # shows it. Default on; the signal only exists when
            # TPU_CONTROL_PLANE is armed.
            up_on_control=config.get_or_default(
                "TPU_SCALE_UP_CONTROL", "1"
            ).lower() not in ("0", "false", "no"),
            scale_up_wait_s=float(config.get_or_default(
                "TPU_SCALE_UP_WAIT_S", "10"
            )),
            scale_down_wait_s=float(config.get_or_default(
                "TPU_SCALE_DOWN_WAIT_S", "60"
            )),
            interval_s=float(config.get_or_default(
                "TPU_SCALE_INTERVAL_S", "5"
            )),
            metrics=metrics,
            logger=logger,
        )
    if logger is not None:
        logger.infof(
            "TPU replica pool initialised: %d in-proc engine(s), %d "
            "remote replica(s)%s", n_replicas, len(remote_addrs),
            (
                f", scaler armed ({pool.scaler.min_replicas}-"
                f"{pool.scaler.max_replicas} replicas)"
                if pool.scaler is not None else ""
            ),
        )
    return pool


def new_tpu_embed_from_config(
    config: Any, logger: Any = None, metrics: Any = None
) -> Optional[object]:
    """Secondary encoder engine (``TPU_EMBED_MODEL``) so one app can serve
    chat from the primary engine AND /v1/embeddings from an encoder —
    the same config-gated datasource idiom as the primary."""
    model = config.get_or_default("TPU_EMBED_MODEL", "")
    if not model:
        return None
    from gofr_tpu.models.registry import get_model
    from gofr_tpu.serving.engine import InferenceEngine
    from gofr_tpu.serving.tokenizer import ByteTokenizer, tokenizer_from_config

    try:
        spec = get_model(model)
        if spec.family != "encoder":
            raise ValueError(
                f"TPU_EMBED_MODEL={model!r} is family {spec.family!r}, "
                f"need an encoder (e.g. bert-base)"
            )
        # The encoder needs its OWN vocabulary — the chat model's
        # TPU_TOKENIZER would feed llama-range ids into the BERT
        # embedding table (XLA clamps the gather silently → garbage).
        tok_path = config.get_or_default("TPU_EMBED_TOKENIZER", "")
        if tok_path:
            tok_config = _Overlay(config, {"TPU_TOKENIZER": tok_path})
            tokenizer = tokenizer_from_config(tok_config, logger)
        else:
            tokenizer = ByteTokenizer()
        engine = InferenceEngine(
            model,
            max_batch=int(config.get_or_default("TPU_MAX_BATCH", "8")),
            max_wait_s=float(
                config.get_or_default("TPU_BATCH_WAIT_MS", "5")
            ) / 1e3,
            max_len=int(config.get_or_default("TPU_MAX_LEN", "1024")),
            logger=logger,
            metrics=metrics,
            tokenizer=tokenizer,
        )
        ckpt = config.get_or_default("TPU_EMBED_CHECKPOINT", "")
        if ckpt:
            from gofr_tpu.serving.checkpoint import restore_checkpoint

            engine.params = restore_checkpoint(ckpt, like=engine.params)
            if logger is not None:
                logger.infof("restored embed params from %s", ckpt)
        if logger is not None:
            logger.infof("TPU embed backend initialised with model %s", model)
        return engine
    except Exception as exc:
        if logger is not None:
            logger.errorf(
                "could not initialise TPU embed backend: %s: %s\n%s",
                type(exc).__name__, exc, traceback.format_exc(),
            )
        return None


class _Overlay:
    """Config view with a few keys overridden (keeps the Config protocol)."""

    def __init__(self, base: Any, overrides: dict) -> None:
        self._base, self._overrides = base, overrides

    def get(self, key: str) -> Any:
        if key in self._overrides:
            return self._overrides[key]
        return self._base.get(key)

    def get_or_default(self, key: str, default: str) -> Any:
        if key in self._overrides:
            return self._overrides[key]
        return self._base.get_or_default(key, default)
