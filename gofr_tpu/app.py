"""The App (reference ``pkg/gofr/gofr.go:35-170``).

Owns config, container, router, middleware, and all servers. Lifecycle:

* ``App()`` — load ``configs/`` dotenv, create the container (datasources by
  config), initialise tracing (reference ``New()``, ``gofr.go:62-96``);
* route verbs ``get/post/put/patch/delete`` usable directly or as
  decorators (reference ``gofr.go:202-219``);
* ``run()`` — start metrics server (:2121), HTTP server (:8000), gRPC server
  (:9000, only when a service is registered), and subscriber loops, then
  block until SIGINT/SIGTERM and shut down gracefully — the drain the
  reference lacks (``gofr.go:169`` blocks forever; SURVEY §3.1).

Default ports mirror the reference's ``default.go:3-7``.
"""

from __future__ import annotations

import asyncio
import signal
from typing import Callable, Optional

from gofr_tpu.config.env import new_env_file
from gofr_tpu.container import Container
from gofr_tpu.handler import alive_handler, favicon_handler, health_handler, wrap_handler
from gofr_tpu.http.middleware import (
    apikey_auth_middleware,
    basic_auth_middleware,
    cors_middleware,
    logging_middleware,
    metrics_middleware,
    oauth_middleware,
    tracer_middleware,
)
from gofr_tpu.http.router import Router
from gofr_tpu.http.server import HTTPServer
from gofr_tpu.logging import Logger, level_from_string
from gofr_tpu.tracing import Tracer, exporter_from_config, set_tracer

DEFAULT_HTTP_PORT = 8000
DEFAULT_GRPC_PORT = 9000
DEFAULT_METRICS_PORT = 2121


class App:
    def __init__(self, config_dir: str = "./configs", config=None) -> None:
        bootstrap_logger = Logger()
        self.config = config if config is not None else new_env_file(config_dir, bootstrap_logger)
        self.container = Container.create(self.config)
        self.logger = self.container.logger
        self.logger.change_level(
            level_from_string(self.config.get("LOG_LEVEL"), self.logger.level)
        )

        tracer = Tracer(
            service_name=self.container.app_name,
            exporter=exporter_from_config(self.config, self.logger),
        )
        set_tracer(tracer)
        self._tracer = tracer

        self.router = Router(logger=self.logger)
        # Default chain, reference http/router.go:23-28.
        self.router.use_middleware(
            tracer_middleware(tracer),
            logging_middleware(self.logger),
            cors_middleware(),
            metrics_middleware(self.container.metrics),
        )

        self.http_port = int(self.config.get_or_default("HTTP_PORT", str(DEFAULT_HTTP_PORT)))
        self.metrics_port = int(
            self.config.get_or_default("METRICS_PORT", str(DEFAULT_METRICS_PORT))
        )
        self.grpc_port = int(self.config.get_or_default("GRPC_PORT", str(DEFAULT_GRPC_PORT)))

        from gofr_tpu.subscriber import SubscriptionManager

        self._subscriptions = SubscriptionManager(self.container)
        # The durable async serving plane (serving/async_serving.py;
        # TPU_ASYNC=1). Built in start() AFTER the engine so its
        # consumer loop never races engine warm-up; None when off.
        self._async_plane = None
        self._grpc_services: list = []
        self._grpc_server = None
        self._http_server: Optional[HTTPServer] = None
        self._metrics_server: Optional[HTTPServer] = None
        self._stop_event: Optional[asyncio.Event] = None

    # -- routing (reference gofr.go:202-227) -------------------------------

    def add_route(self, method: str, path: str, handler: Callable) -> None:
        self.router.add(method, path, wrap_handler(handler, self.container))

    def _verb(self, method: str, path: str, handler: Optional[Callable]):
        if handler is not None:
            self.add_route(method, path, handler)
            return handler

        def decorator(fn: Callable):
            self.add_route(method, path, fn)
            return fn

        return decorator

    def get(self, path: str, handler: Optional[Callable] = None):
        return self._verb("GET", path, handler)

    def post(self, path: str, handler: Optional[Callable] = None):
        return self._verb("POST", path, handler)

    def put(self, path: str, handler: Optional[Callable] = None):
        return self._verb("PUT", path, handler)

    def patch(self, path: str, handler: Optional[Callable] = None):
        return self._verb("PATCH", path, handler)

    def delete(self, path: str, handler: Optional[Callable] = None):
        return self._verb("DELETE", path, handler)

    def use_middleware(self, *mws) -> None:
        """Custom middleware (reference ``gofr.go:372``)."""
        self.router.use_middleware(*mws)

    def use_mongo(self, client) -> None:
        """Inject a Mongo driver (reference ``gofr.go:376-378``)."""
        self.container.use_mongo(client)

    def use_pubsub(self, client) -> None:
        """Inject a pub/sub client for brokers without bundled drivers."""
        self.container.use_pubsub(client)

    # -- auth enablers (reference gofr.go:310-344) -------------------------

    def enable_basic_auth(self, users: dict[str, str]) -> None:
        self.router.use_middleware(basic_auth_middleware(users=users))

    def enable_basic_auth_with_validator(self, validate_func) -> None:
        self.router.use_middleware(
            basic_auth_middleware(validate_func=validate_func, container=self.container)
        )

    def enable_api_key_auth(self, *keys: str) -> None:
        self.router.use_middleware(apikey_auth_middleware(keys=keys))

    def enable_api_key_auth_with_validator(self, validate_func) -> None:
        self.router.use_middleware(
            apikey_auth_middleware(validate_func=validate_func, container=self.container)
        )

    def enable_oauth(self, jwks_url: str, refresh_interval_s: float = 300.0) -> None:
        from gofr_tpu.http.middleware import JWKSProvider

        provider = JWKSProvider(jwks_url, refresh_interval_s, logger=self.logger)
        provider.start()
        self.router.use_middleware(oauth_middleware(jwks=provider))

    # -- pubsub / services / migrations ------------------------------------

    def subscribe(self, topic: str, handler: Optional[Callable] = None):
        """Register a subscription handler (reference ``gofr.go:346-354``)."""
        if handler is not None:
            self._subscriptions.register(topic, handler)
            return handler

        def decorator(fn: Callable):
            self._subscriptions.register(topic, fn)
            return fn

        return decorator

    def add_http_service(self, name: str, address: str, *options) -> None:
        """Register a downstream service client (reference ``gofr.go:189-199``)."""
        from gofr_tpu.service import new_http_service

        if name in self.container.services:
            self.logger.warnf("service %s already registered; overwriting", name)
        self.container.services[name] = new_http_service(
            address,
            self.logger,
            self.container.metrics,
            *options,
        )

    def migrate(self, migrations: dict) -> None:
        """Run versioned migrations (reference ``gofr.go:243-248``)."""
        from gofr_tpu.migration import run as run_migrations

        try:
            run_migrations(migrations, self.container)
        except Exception:
            import traceback

            self.logger.errorf("migration panicked:\n%s", traceback.format_exc())

    def add_rest_handlers(self, entity) -> None:
        """Auto-register CRUD routes for a dataclass entity
        (reference ``gofr.go:356-369``)."""
        from gofr_tpu.crud import register_crud_handlers

        register_crud_handlers(self, entity)

    def register_service(self, add_servicer_fn, servicer) -> None:
        """Register a gRPC service (reference ``gofr.go:55-59``). The server
        starts only if at least one service is registered
        (``gofr.go:150-157``)."""
        self._grpc_services.append((add_servicer_fn, servicer))

    # -- lifecycle ----------------------------------------------------------

    def _install_wellknown(self) -> None:
        self.add_route("GET", "/.well-known/health", health_handler(self.container))
        self.add_route("GET", "/.well-known/alive", alive_handler)
        self.add_route("GET", "/favicon.ico", favicon_handler)

    async def start(self) -> None:
        """Bind all servers (ephemeral-port friendly); used by run() and tests."""
        self._install_wellknown()
        self.container.mark_started()

        self._metrics_server = HTTPServer(
            self._metrics_handler(), port=self.metrics_port, logger=self.logger
        )
        await self._metrics_server.start()
        self.metrics_port = self._metrics_server.port
        self.logger.infof("metrics server started on :%d/metrics", self.metrics_port)

        self._http_server = HTTPServer(self.router, port=self.http_port, logger=self.logger)
        await self._http_server.start()
        self.http_port = self._http_server.port

        if self._grpc_services:
            from gofr_tpu.grpc.server import GRPCServer

            self._grpc_server = GRPCServer(
                self.grpc_port, self.logger, self.container
            )
            for add_fn, servicer in self._grpc_services:
                self._grpc_server.register(add_fn, servicer)
            await self._grpc_server.start()
            self.grpc_port = self._grpc_server.port

        for engine in (self.container.tpu, self.container.tpu_embed):
            if engine is not None and hasattr(engine, "start"):
                await engine.start()

        if self.container.tpu is not None:
            from gofr_tpu.serving.async_serving import (
                new_async_plane_from_config,
            )

            self._async_plane = new_async_plane_from_config(
                self.config, self.container.tpu,
                metrics=self.container.metrics, logger=self.logger,
            )
            if self._async_plane is not None:
                self._async_plane.start()
                self.logger.infof(
                    "async serving plane consuming %r -> %r (dlq %r)",
                    self._async_plane.request_topic,
                    self._async_plane.reply_topic,
                    self._async_plane.dlq_topic,
                )

        self._subscriptions.start()

    async def stop(self) -> None:
        await self._subscriptions.stop()
        # TPU_DRAIN_S > 0: graceful engine drain — in-flight generations
        # complete (up to the deadline) while new submissions get 503,
        # so a rolling restart doesn't fail live requests.
        drain_s = float(self.config.get_or_default("TPU_DRAIN_S", "0"))
        if self._async_plane is not None:
            # Drain BEFORE the engine stops: finished async work still
            # publishes its replies, and unfinished leases are nacked
            # back to the broker (budget refunded) instead of dropped.
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: self._async_plane.stop(drain_s)
            )
            self._async_plane.broker.close()
        for engine in (self.container.tpu, self.container.tpu_embed):
            if engine is not None and hasattr(engine, "stop"):
                import inspect

                params = inspect.signature(engine.stop).parameters
                if "drain_s" in params:
                    await engine.stop(drain_s=drain_s)
                else:  # injected engines without the kwarg
                    await engine.stop()
        if self._grpc_server is not None:
            await self._grpc_server.stop()
        for server in (self._http_server, self._metrics_server):
            if server is not None:
                await server.shutdown()
        await self.container.close()
        self._tracer.shutdown()

    async def _run_async(self) -> None:
        await self.start()
        self._stop_event = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, self._stop_event.set)
            except NotImplementedError:
                pass
        await self._stop_event.wait()
        self.logger.info("shutting down gracefully")
        await self.stop()

    def run(self) -> None:
        """Blocking entrypoint (reference ``gofr.go:114-170``)."""
        try:
            asyncio.run(self._run_async())
        except KeyboardInterrupt:
            pass

    # -- metrics endpoint ---------------------------------------------------

    def _static_lockgraph(self) -> dict:
        """GL021's static may-acquire-while-holding model over this
        installed package, built once per process and cached (it
        re-parses every module): ``{"edges": {(held, acquired):
        (path, line)}, "witnesses": {...}}``. Empty on any failure —
        /debug/lockgraph degrades to runtime-only, never 500s."""
        cached = getattr(self, "_static_lockgraph_cache", None)
        if cached is not None:
            return cached
        graph: dict = {"edges": {}, "witnesses": {}}
        try:
            import os as _os

            import gofr_tpu as _pkg
            from gofr_tpu.analysis.core import build_index
            from gofr_tpu.analysis.rules import may_acquire_while_holding

            pkg_dir = _os.path.dirname(_os.path.abspath(_pkg.__file__))
            index = build_index([pkg_dir], root=_os.path.dirname(pkg_dir))
            if index is not None:
                witness = may_acquire_while_holding(index)
                graph = {
                    "edges": {
                        pair: (path, line)
                        for pair, (path, line, _) in witness.items()
                    },
                    "witnesses": {
                        f"{a} -> {b}": (
                            f"{path}:{line} via {' -> '.join(chain)}"
                        )
                        for (a, b), (path, line, chain)
                        in sorted(witness.items())
                    },
                }
        except Exception:  # noqa: BLE001 — debug surface, never 500
            pass
        self._static_lockgraph_cache = graph
        return graph

    def _metrics_handler(self):
        from gofr_tpu.http.proto import Response
        from gofr_tpu.metrics import render_prometheus

        container = self.container

        def engine_report(method: str) -> Response:
            """One JSON ops read per engine (`tpu`, `tpu_embed`) — or
            per replica when `container.tpu` is a ReplicaPool — from an
            engine-shaped `method()` report. The shared shape of
            /debug/flight, /debug/capacity, /debug/tenants,
            /debug/slo, /debug/brownout, and /debug/loop."""
            import json as _json

            reports: dict = {}
            for name, eng in (
                ("tpu", container.tpu), ("tpu_embed", container.tpu_embed)
            ):
                if eng is None:
                    continue
                fn = getattr(eng, method, None)
                if not callable(fn):
                    continue
                try:
                    reports[name] = fn()
                except Exception as exc:  # noqa: BLE001 — debug surface
                    reports[name] = {"error": str(exc)}
            return Response(
                status=200,
                headers={"Content-Type": "application/json"},
                body=_json.dumps(reports).encode(),
            )

        async def handler(raw) -> Response:
            path = raw.target.split("?")[0]
            if path == "/metrics":
                container.push_system_metrics()
                body = render_prometheus(container.metrics, app_name=container.app_name)
                return Response(
                    status=200,
                    headers={"Content-Type": "text/plain; version=0.0.4"},
                    body=body.encode(),
                )
            if path == "/.well-known/alive":
                return Response(
                    status=200,
                    headers={"Content-Type": "application/json"},
                    body=b'{"status":"UP"}',
                )
            # /debug/* — ops surface on the metrics port (net-new: the
            # closest Go analog is pprof-on-metrics-port, which the
            # reference does not ship; TPU serving makes the equivalents
            # indispensable: a hung device step shows up as a thread
            # parked in a jit dispatch, and device traces answer "where
            # does the step go" without a redeploy).
            if path == "/debug/threads":
                import sys as _sys
                import threading as _threading
                import traceback as _traceback

                names = {
                    t.ident: t.name for t in _threading.enumerate()
                }
                lines = []
                for ident, frame in _sys._current_frames().items():
                    lines.append(
                        f"Thread {names.get(ident, '?')} (ident {ident}):"
                    )
                    lines.extend(
                        ln.rstrip()
                        for ln in _traceback.format_stack(frame)
                    )
                    lines.append("")
                return Response(
                    status=200,
                    headers={"Content-Type": "text/plain"},
                    body="\n".join(lines).encode(),
                )
            if path == "/debug/engine":
                import json as _json

                stats = {}
                for name, eng in (
                    ("tpu", container.tpu), ("tpu_embed", container.tpu_embed)
                ):
                    if eng is None:
                        continue
                    try:
                        stats[name] = eng.health_check()
                    except Exception as exc:  # noqa: BLE001 — debug surface
                        stats[name] = {"error": str(exc)}
                return Response(
                    status=200,
                    headers={"Content-Type": "application/json"},
                    body=_json.dumps(stats).encode(),
                )
            if path == "/debug/flight":
                # The serving flight recorder (docs/advanced-guide/
                # observability.md): per-request lifecycle timelines —
                # phase durations, token counts, prefix-cache hits,
                # shed/cancel/replay/failover annotations, trace ids —
                # from a fixed-size ring with slow/errored requests
                # pinned so a burst can't evict the interesting ones.
                return engine_report("flight_records")
            if path == "/debug/capacity":
                # Device-resource capacity (docs/advanced-guide/
                # observability.md "Device-resource signals"): the HBM
                # ledger (per-component bytes, budget, headroom), XLA
                # compile counts with the steady-state recompile
                # counter, and paged-KV pool pressure — the operator's
                # one read for "is this pod running out of the
                # resources that actually bound it".
                return engine_report("capacity_report")
            if path == "/debug/tenants":
                # Tenant attribution (docs/advanced-guide/
                # observability.md "Tenant attribution and SLOs"): the
                # FULL unclamped per-tenant table — tokens by phase,
                # KV-block·seconds, outcome counts, live queue share —
                # next to the clamped Prometheus export. The operator's
                # one read for "which tenant is eating the pod".
                return engine_report("tenant_report")
            if path == "/debug/slo":
                # SLO burn-rate state (docs/advanced-guide/
                # observability.md): per-objective multi-window burn
                # rates and the compliance bit — the "is the service
                # breaking its promise right now" read.
                return engine_report("slo_report")
            if path == "/debug/brownout":
                # Brownout-ladder state (docs/advanced-guide/
                # resilience.md "Brownout & overload control"): the
                # degradation level, AIMD budget factor, thresholds,
                # per-action counters — what the burn-rate actuator is
                # DOING about the /debug/slo signal right now.
                return engine_report("brownout_report")
            if path == "/debug/loop":
                # Scheduler-loop profiler (docs/advanced-guide/
                # observability.md "Scheduler-loop signals"): per-phase
                # pass-time attribution, loop utilization, the
                # host-overhead ratio ("is host bookkeeping starving
                # the TPU"), and the pinned stall-anomaly records —
                # where a scheduler pass's wall time goes, without an
                # operator having to know when to run /debug/tpu-trace.
                return engine_report("loop_report")
            if path == "/debug/control":
                # Control-plane state (docs/advanced-guide/
                # resilience.md "Control plane"): per-signal guard
                # status (ok / last_good / observe_only), each loop's
                # state — the per-tenant brownout table, host-pressure
                # and predictive hold-down timers — and the last
                # decisions ring. The operator's one read for "which
                # loop acted, on what evidence, and which sensors is
                # it no longer trusting".
                return engine_report("control_report")
            if path == "/debug/async":
                # Async serving plane state (docs/advanced-guide/
                # resilience.md "Async serving & delivery semantics"):
                # topics + delivery knobs, consumer lag, in-flight
                # leases, the delivery counters (consumed / published /
                # redelivered / dead-lettered), and the dedup ledger's
                # occupancy — the operator's one read for "is async
                # traffic flowing, backing up, or dead-lettering".
                import json as _json

                plane = self._async_plane
                body_async = (
                    {"enabled": False} if plane is None
                    else plane.report()
                )
                return Response(
                    status=200,
                    headers={"Content-Type": "application/json"},
                    body=_json.dumps(body_async).encode(),
                )
            if path == "/debug/lockgraph":
                # Lock-order graphs (docs/advanced-guide/
                # resilience.md): the RUNTIME order graph TPU_LOCKCHECK
                # learned this process, the STATIC may-acquire-while-
                # holding model graftlint's GL021 derives from the AST,
                # and their diff — a runtime edge the static model
                # lacks means the model under-approximates (or a lock
                # bypassed make_lock); a static edge never observed is
                # untested ordering, not a bug. The static half is
                # built once and cached (it parses the package).
                import json as _json

                from gofr_tpu.analysis import lockcheck as _lockcheck

                runtime = _lockcheck.order_graph()
                static = self._static_lockgraph()
                run_edges = {
                    (a, b)
                    for a, bs in runtime["edges"].items() for b in bs
                }
                static_edges = set(static["edges"])
                body = {
                    "runtime": runtime,
                    "static": {
                        "edges": sorted(
                            f"{a} -> {b}" for a, b in static_edges
                        ),
                        "witnesses": static["witnesses"],
                    },
                    "diff": {
                        "runtime_only": sorted(
                            f"{a} -> {b}"
                            for a, b in run_edges - static_edges
                        ),
                        "static_only": sorted(
                            f"{a} -> {b}"
                            for a, b in static_edges - run_edges
                        ),
                    },
                    "violations": [
                        {
                            "kind": v.kind,
                            "thread": v.thread,
                            "message": v.message,
                        }
                        for v in _lockcheck.violations()
                    ],
                }
                return Response(
                    status=200,
                    headers={"Content-Type": "application/json"},
                    body=_json.dumps(body).encode(),
                )
            if path == "/ops/tier-import":
                # Wire-leg tier transfers (docs/advanced-guide/
                # resilience.md "Disaggregated prefill/decode"): a
                # remote prefill pod POSTs a finished prompt's KV
                # blocks here (length-prefixed binary payload) so the
                # separately-submitted request admission-aliases them
                # zero-copy. Validation mirrors the in-proc handoff
                # (geometry fingerprint + re-computed CRC); every
                # rejection is a 2xx/4xx "the request will re-prefill"
                # — never a 5xx, never a wrong answer. Lives on the
                # ops port: block payloads are operator-tier traffic,
                # not dataplane requests.
                import json as _json

                if raw.method != "POST":
                    return Response(
                        status=405,
                        headers={"Allow": "POST"},
                        body=b'{"error": "POST a KVB1 payload"}',
                    )
                from gofr_tpu.ops.kv_cache import (
                    HANDLE_MAGIC,
                    handle_from_wire,
                    payload_from_wire,
                )

                body_bytes = raw.body or b""
                try:
                    if body_bytes[:4] == HANDLE_MAGIC:
                        # The dma leg: the exporter POSTs a claim
                        # TICKET; this side pulls the bytes directly
                        # from the exporter's transfer server. Every
                        # redemption failure is a 200 "stale" — the
                        # exporter's ladder bans the dma rung and
                        # reships the same blocks inline via wire.
                        from gofr_tpu.service.dma import (
                            DmaError,
                            dma_fetch,
                        )
                        from gofr_tpu.serving.lifecycle import Deadline

                        handle = handle_from_wire(body_bytes)
                        fetch_s = float(self.config.get_or_default(
                            "TPU_DMA_FETCH_TIMEOUT_S", "5.0"
                        ))
                        try:
                            payload = dma_fetch(
                                handle,
                                deadline=Deadline.after(fetch_s),
                            )
                        except DmaError as exc:
                            return Response(
                                status=200,
                                headers={
                                    "Content-Type": "application/json"
                                },
                                body=_json.dumps({
                                    "result": "stale",
                                    "kind": exc.kind,
                                    "error": str(exc),
                                }).encode(),
                            )
                    else:
                        payload = payload_from_wire(body_bytes)
                except Exception as exc:  # noqa: BLE001 — ANY malformed body is a 400 rejection, never a 5xx
                    return Response(
                        status=400,
                        headers={"Content-Type": "application/json"},
                        body=_json.dumps({
                            "result": "rejected", "error": str(exc),
                        }).encode(),
                    )
                eng = container.tpu
                fn = getattr(eng, "import_payload", None)
                result = fn(payload) if callable(fn) else "rejected"
                return Response(
                    status=200,
                    headers={"Content-Type": "application/json"},
                    body=_json.dumps({
                        "result": result,
                        "blocks": payload.n_blocks,
                    }).encode(),
                )
            if path == "/ops/tier-export":
                # The tier-import codec in REVERSE: a remote decode pod
                # asks THIS pod for the KV blocks of a prompt prefix it
                # is about to prefill (docs/advanced-guide/
                # resilience.md "Multi-host disaggregation"). POST a
                # JSON body {"token_ids": [...], "mode": "dma"|"wire",
                # "timeout_s": n} (or GET with ?token_ids=1,2,3&mode=)
                # and the reply is a KVH1 claim ticket (mode=dma, dma
                # available), a KVB1 inline payload (mode=wire or dma
                # unavailable), or JSON {"result": "miss"} — misses and
                # unsupported engines are 200s: "prefill it yourself"
                # is a normal answer, not an error.
                import json as _json

                if raw.method == "POST":
                    try:
                        spec = _json.loads(raw.body or b"{}")
                        ids = [int(t) for t in spec["token_ids"]]
                    except Exception:  # noqa: BLE001 — ANY malformed body is a 400, never a 5xx
                        return Response(
                            status=400,
                            headers={"Content-Type": "application/json"},
                            body=b'{"error": "POST JSON with '
                                 b'token_ids: [int, ...]"}',
                        )
                elif raw.method == "GET":
                    import urllib.parse

                    q = urllib.parse.parse_qs(
                        raw.target.partition("?")[2]
                    )
                    try:
                        ids = [
                            int(t)
                            for t in q.get("token_ids", [""])[0].split(",")
                            if t
                        ]
                    except ValueError:
                        return Response(
                            status=400,
                            headers={"Content-Type": "application/json"},
                            body=b'{"error": "token_ids must be '
                                 b'comma-separated integers"}',
                        )
                    spec = {"mode": q.get("mode", ["wire"])[0]}
                else:
                    return Response(
                        status=405,
                        headers={"Allow": "GET, POST"},
                        body=b'{"error": "GET or POST"}',
                    )
                mode = str(spec.get("mode", "wire"))
                try:
                    timeout_s = min(
                        10.0, max(0.1, float(spec.get("timeout_s", 2.0)))
                    )
                except (TypeError, ValueError):
                    timeout_s = 2.0
                eng = container.tpu
                fn = getattr(eng, "export_cached", None)
                if not ids or not callable(fn):
                    return Response(
                        status=200,
                        headers={"Content-Type": "application/json"},
                        body=b'{"result": "unsupported"}',
                    )
                payload = fn(ids, timeout_s=timeout_s)
                if payload is None:
                    return Response(
                        status=200,
                        headers={"Content-Type": "application/json"},
                        body=b'{"result": "miss"}',
                    )
                from gofr_tpu.ops.kv_cache import (
                    handle_to_wire,
                    payload_to_wire,
                )

                if mode == "dma":
                    # Stage the bytes on this pod's transfer server and
                    # reply with the tiny claim ticket; the caller
                    # fetches the body over the dedicated data socket.
                    # Staging trouble degrades to the inline wire body
                    # — same bytes, one rung down.
                    try:
                        from gofr_tpu.service.dma import (
                            get_transfer_server,
                        )

                        handle = get_transfer_server().offer(
                            payload, src=str(getattr(
                                eng, "model_name", ""
                            )),
                        )
                        return Response(
                            status=200,
                            headers={
                                "Content-Type":
                                    "application/octet-stream",
                            },
                            body=handle_to_wire(handle),
                        )
                    except Exception:  # noqa: BLE001 — dma staging failure degrades to the wire body
                        pass
                return Response(
                    status=200,
                    headers={
                        "Content-Type": "application/octet-stream",
                    },
                    body=payload_to_wire(payload),
                )
            if path == "/debug/tpu-trace":
                import asyncio as _aio
                import json as _json
                import urllib.parse

                q = urllib.parse.parse_qs(raw.target.partition("?")[2])
                try:
                    ms = min(int(q.get("ms", ["1000"])[0]), 30_000)
                except ValueError:
                    return Response(
                        status=400,
                        headers={"Content-Type": "application/json"},
                        body=b'{"error": "ms must be an integer"}',
                    )
                # The process-wide capture singleton (serving/
                # profiler_capture.py): ONE reusable trace dir (each
                # capture overwrites the last — an unauthenticated loop
                # of trace requests must not fill the disk) and ONE
                # lock, both created at singleton construction under a
                # module lock — the old lazy `hasattr` init here let
                # two concurrent first requests mint two dirs/locks and
                # trace concurrently. Shared with the scheduler-loop
                # profiler's stall-triggered captures, so a manual
                # capture and an anomaly capture can never overlap.
                from gofr_tpu.serving.profiler_capture import get_capture

                cap = get_capture()
                if not cap.try_acquire():
                    return Response(
                        status=409,
                        headers={"Content-Type": "application/json"},
                        body=b'{"error": "a trace capture is already '
                             b'running"}',
                    )
                try:
                    loop = _aio.get_running_loop()
                    try:
                        # start/stop serialize trace data to disk — keep
                        # them off the event loop that also serves
                        # /metrics and liveness probes.
                        await loop.run_in_executor(None, cap.start_trace)
                        await _aio.sleep(ms / 1e3)
                        await loop.run_in_executor(None, cap.stop_trace)
                        cap.note_manual_capture()
                        body = {
                            "trace_dir": cap.trace_dir,
                            "captured_ms": ms,
                        }
                        status = 200
                    except Exception as exc:  # noqa: BLE001 — debug surface
                        body = {"error": str(exc)}
                        status = 500
                finally:
                    cap.release()
                return Response(
                    status=status,
                    headers={"Content-Type": "application/json"},
                    body=_json.dumps(body).encode(),
                )
            return Response(status=404, headers={}, body=b"404 page not found")

        return handler


def new_cmd(config_dir: str = "./configs"):
    """CLI app factory (reference ``gofr.go:99-111``)."""
    from gofr_tpu.cli import CMDApp

    return CMDApp(config_dir=config_dir)
