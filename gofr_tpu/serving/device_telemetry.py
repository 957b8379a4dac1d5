"""Device-resource observability: the HBM ledger and the XLA compile
tracker (ISSUE 11).

The serving stack is bounded by two device resources that, until this
module, were invisible: **HBM bytes** (params, adapter slots, the paged
KV pool, workspace planes) and **XLA compilations** (a steady-state
recompile silently serializes the whole dispatch pipeline behind a
multi-second trace+compile). Both failure modes today surface only as
mysterious tail latency in the phase histograms. This module gives each
a first-class accounting layer the control paths (admission shedding,
radix eviction watermark, pool scaling) can act on:

* :class:`HBMLedger` — a per-engine byte ledger over the components the
  engine actually allocated: ``params`` (quantized weight tree minus
  adapter leaves), ``lora`` (the stacked adapter planes), ``kv_pool``
  (the slot or paged cache, exactly ``cache.hbm_bytes()``), optional
  ``prefix_pool``, and ``workspace`` (block table, lengths, and the
  per-slot device state planes). All byte counts are **global logical
  bytes** — identical at ``tp=1`` and ``tp=2`` (a sharded array's
  ``size × itemsize`` is its global footprint) — with a
  ``per_device_bytes`` estimate that divides the mesh-sharded
  components by the mesh size. The ledger resolves an HBM **budget**
  (operator ``TPU_HBM_BYTES`` > platform ``device.memory_stats()``
  ``bytes_limit`` > the ledger's own per-device total) and derives the
  **headroom ratio** — budget slack plus free paged-KV blocks over the
  budget — the one saturation signal admission, eviction, and scaling
  all read. Exported as ``app_tpu_hbm_bytes{component}`` gauges plus
  ``app_tpu_hbm_headroom_ratio``.

* :class:`CompileTracker` — wraps every jitted serving program (the
  ``serving/programs.py`` builders, the paged-KV importer/COW jits, the
  modality steps) and counts actual XLA cache growth per call
  (``fn._cache_size()`` deltas; a shape-signature set is the fallback
  on backends without the introspection). Every compile increments
  ``app_tpu_compiles_total{program}``, records the call's wall clock in
  ``app_tpu_compile_seconds`` (first-call trace+compile time — the
  latency a request actually pays), and emits a deferred ``tpu.compile``
  span via the PR 6 ``Tracer.emit_span`` idiom (parented under the
  trace that was ambient at engine construction, so a traced boot owns
  its warm-up compiles even though they fire on the scheduler thread).
  After :meth:`CompileTracker.mark_warm` — the warm-up fence — any
  further compile bumps ``app_tpu_steady_state_recompiles_total`` and
  logs a warning: a recompile in steady state is **always** a
  fixed-shape-discipline bug (graftlint GL015 is the static twin).

Overhead contract: the wrapper adds two cache-size reads and two clock
reads per *dispatch* (window/chunk granularity, never per token); the
ledger's component bytes are computed once per boot (sizes are static)
and the headroom ratio is O(1) arithmetic over the allocator's free
count.

Determinism: clocks are injectable and nothing here sleeps or touches
device state — tests drive compiles with real programs and read exact
counts.
"""

from __future__ import annotations

import math

import time
from typing import Any, Callable, Optional

from gofr_tpu.analysis import lockcheck
from gofr_tpu.serving.observability import tracer_active
from gofr_tpu.tracing import get_tracer
from gofr_tpu.tracing.tracer import _rand_hex, current_span


def tree_device_bytes(tree: Any) -> int:
    """Total bytes of every array leaf in a (possibly nested) pytree-ish
    structure — duck-typed on ``.size``/``.dtype`` so it never imports
    jax and costs attribute reads only (no host↔device traffic)."""
    total = 0
    stack = [tree]
    while stack:
        node = stack.pop()
        if node is None:
            continue
        size = getattr(node, "size", None)
        dtype = getattr(node, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * int(getattr(dtype, "itemsize", 1))
        elif isinstance(node, dict):
            stack.extend(node.values())
        elif isinstance(node, (list, tuple)):
            stack.extend(node)
    return total


class HBMLedger:
    """Byte accounting of one engine's device-resident components plus
    the derived headroom signal. Component sizes are fixed per boot
    (buffers are preallocated); the only dynamic input is the paged
    pool's free-block count, passed into :meth:`headroom_ratio` by the
    caller so the ledger itself holds no engine reference."""

    #: Components sharded across the mesh (params Megatron-style, the
    #: KV pool's head axis, adapter leaves, the prefix pool); workspace
    #: planes are replicated.
    SHARDED = ("params", "lora", "kv_pool", "prefix_pool")

    def __init__(
        self,
        components: dict[str, int],
        *,
        mesh_devices: int = 1,
        block_bytes: int = 0,
        n_blocks: int = 0,
        budget_bytes: int = 0,
        budget_source: str = "",
        device_stats: Optional[Callable[[], Optional[dict]]] = None,
    ) -> None:
        self.components = {k: int(v) for k, v in components.items()}
        self.mesh_devices = max(1, int(mesh_devices))
        #: Global bytes of ONE paged pool block across every layer's
        #: K/V (and scale) planes — the unit the eviction watermark
        #: converts HBM fractions into.
        self.block_bytes = int(block_bytes)
        self.n_blocks = int(n_blocks)
        self._device_stats = device_stats
        self.budget_bytes, self.budget_source = self._resolve_budget(
            int(budget_bytes)
        )

    # -- totals --------------------------------------------------------

    @property
    def total_bytes(self) -> int:
        return sum(self.components.values())

    @property
    def per_device_bytes(self) -> int:
        """Estimated bytes resident on ONE mesh device: sharded
        components divide by the mesh size, workspace planes are
        replicated. Exact at ``tp=1``; an estimate under GSPMD (XLA may
        replicate small leaves)."""
        if self.mesh_devices <= 1:
            return self.total_bytes
        total = 0
        for name, size in self.components.items():
            if name in self.SHARDED:
                total += -(-size // self.mesh_devices)
            else:
                total += size
        return total

    def _resolve_budget(self, explicit: int) -> tuple[int, str]:
        """The per-device HBM budget headroom is measured against:
        the operator's explicit bytes, else the platform's
        ``memory_stats()['bytes_limit']``, else the ledger's own
        per-device total (headroom then reads as "free paged blocks
        over own footprint" — still a usable pressure signal on
        backends that report nothing)."""
        if explicit > 0:
            return explicit, "env"
        stats = self.device_memory_stats()
        limit = (stats or {}).get("bytes_limit")
        if limit:
            return int(limit), "memory_stats"
        return self.per_device_bytes, "ledger"

    def device_memory_stats(self) -> Optional[dict]:
        """The platform's own per-device accounting when it provides
        one (TPU runtimes do; the CPU backend returns None) — the
        cross-check against the ledger's estimate."""
        if self._device_stats is None:
            return None
        try:
            stats = self._device_stats()
        except Exception:  # noqa: BLE001  # graftlint: disable=GL006 — gauge-only cross-check; memory_stats support varies by backend
            return None
        return dict(stats) if stats else None

    # -- the saturation signal -----------------------------------------

    def headroom_ratio(self, free_blocks: int = 0) -> float:
        """Fraction of the per-device budget currently free: budget
        slack beyond the ledger's allocations plus the bytes of free
        paged-KV blocks (preallocated but holding no live tokens).
        In [0, 1]; with no paged pool and an unknown budget this reads
        0.0 — honest: nothing is known to be free."""
        budget = self.budget_bytes
        if budget <= 0:
            return 1.0
        slack = max(0, budget - self.per_device_bytes)
        free = slack + (
            free_blocks * self.block_bytes // self.mesh_devices
        )
        return max(0.0, min(1.0, free / budget))

    def derive_block_watermark(self, hbm_frac: float) -> int:
        """``TPU_PREFIX_EVICT_HBM_FRAC`` → a free-block watermark: the
        number of paged pool blocks that must stay free so total free
        HBM (budget slack + free blocks) covers ``hbm_frac`` of the
        budget. Clamped to the pool size minus the parking block; 0
        when the fraction is unset or the pool has no blocks."""
        if hbm_frac <= 0 or self.block_bytes <= 0 or self.n_blocks <= 1:
            return 0
        budget = self.budget_bytes
        slack = max(0, budget - self.per_device_bytes)
        want = hbm_frac * budget - slack
        per_device_block = max(1, self.block_bytes // self.mesh_devices)
        blocks = math.ceil(want / per_device_block)
        return max(0, min(blocks, self.n_blocks - 1))

    # -- rendering -----------------------------------------------------

    def snapshot(self, free_blocks: int = 0) -> dict[str, Any]:
        """The ``/debug/capacity`` / health-detail form: components,
        totals, budget provenance, headroom, and the platform
        cross-check when one exists."""
        out: dict[str, Any] = {
            "components": dict(self.components),
            "total_bytes": self.total_bytes,
            "per_device_bytes": self.per_device_bytes,
            "mesh_devices": self.mesh_devices,
            "budget_bytes": self.budget_bytes,
            "budget_source": self.budget_source,
            "headroom_ratio": round(self.headroom_ratio(free_blocks), 6),
        }
        stats = self.device_memory_stats()
        if stats is not None:
            # Platform cross-check: what the runtime itself thinks is
            # resident vs the ledger's per-device estimate (the delta
            # is XLA workspace + fragmentation the ledger can't see).
            out["device"] = {
                "bytes_in_use": stats.get("bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            }
        if self.block_bytes:
            out["block_bytes"] = self.block_bytes
        return out

    def publish(self, metrics: Any, model_name: str) -> None:
        """Export the per-component gauges (once per boot — sizes are
        static; the headroom gauge refreshes per window from the
        scheduler's gauge pass)."""
        if metrics is None:
            return
        for component, size in self.components.items():
            metrics.set_gauge(
                "app_tpu_hbm_bytes", float(size),
                "model", model_name, "component", component,
            )
        metrics.set_gauge(
            "app_tpu_hbm_headroom_ratio", self.headroom_ratio(),
            "model", model_name,
        )


class CompileTracker:
    """Counts XLA compiles per jitted serving program and polices the
    steady-state fixed-shape contract. See the module docstring."""

    def __init__(
        self,
        model_name: str,
        *,
        metrics: Any = None,
        logger: Any = None,
        clock: Callable[[], float] = time.perf_counter,
        wall_ns: Callable[[], int] = time.time_ns,
    ) -> None:
        self.model_name = model_name
        self._metrics = metrics
        self._logger = logger
        self._clock = clock
        self._wall_ns = wall_ns
        self._lock = lockcheck.make_lock("CompileTracker._lock")
        self._programs: dict[str, dict[str, Any]] = {}
        self.total = 0
        self.steady_state_recompiles = 0
        self._warm = False
        # Persistent-compile-cache provenance: set by the engine at
        # boot when jax's compilation cache has a directory
        # (gofr_tpu/compile_cache.py); rides health details and
        # /debug/capacity so "did this restart recompile" is answerable.
        self.cache_info: Optional[dict[str, Any]] = None
        # Boot trace context: compiles fire on the scheduler thread
        # (no ambient span there), so the trace that was ambient when
        # the ENGINE was constructed parents the warm-up compile spans
        # — a traced boot owns its compile timeline.
        span = current_span()
        self._boot_ctx: Optional[tuple[str, str]] = (
            (span.trace_id, span.span_id) if span is not None else None
        )

    # -- warm-up fence -------------------------------------------------

    def mark_warm(self) -> None:
        """Arm the steady-state fence: every compile after this call is
        a fixed-shape-discipline bug and counts (and warns) as such.
        Callers (bench after its warm-up phase, operators after a
        canary request sweep) decide when the program set is complete."""
        self._warm = True

    @property
    def warm(self) -> bool:
        return self._warm

    # -- instrumentation -----------------------------------------------

    def wrap(self, program: str, fn: Any, shared: bool = False) -> Any:
        """Wrap a jitted callable: each call that grows the program's
        XLA cache counts as one compile of ``program``. Transparent to
        callers (same signature, same return).

        ``shared=True`` is for module-level jits whose XLA cache is
        shared by every engine in the process (the paged-pool COW and
        import programs): ``_cache_size()`` on those is GLOBAL, so a
        concurrent compile by a sibling engine would be mis-attributed
        to whichever wrapper happened to be mid-call — including a
        false steady-state recompile. Shared wraps use the per-wrapper
        shape-signature set instead: exact per-engine attribution (one
        count per variant per boot), no cross-engine race."""
        with self._lock:
            self._programs.setdefault(
                program, {"compiles": 0, "seconds_total": 0.0}
            )
        signatures: set = set()
        sig_lock = lockcheck.make_lock("CompileTracker.sig_lock")

        def cache_size() -> Optional[int]:
            if shared:
                return None
            probe = getattr(fn, "_cache_size", None)
            if probe is None:
                return None
            try:
                return int(probe())
            except Exception:  # noqa: BLE001  # graftlint: disable=GL006 — best-effort introspection; the signature fallback takes over
                return None

        def wrapped(*args: Any, **kwargs: Any) -> Any:
            before = cache_size()
            w0 = self._wall_ns()
            t0 = self._clock()
            out = fn(*args, **kwargs)
            after = cache_size()
            if before is not None and after is not None:
                compiled = after > before
            else:
                # Shared jits, fake backends, exotic jax versions: a
                # shape/dtype signature never seen by THIS wrapper is
                # the first trace of that program variant here.
                sig = _call_signature(args, kwargs)
                with sig_lock:
                    compiled = sig not in signatures
                    signatures.add(sig)
            if compiled:
                self._note_compile(program, self._clock() - t0, w0)
            return out

        wrapped.__wrapped__ = fn  # type: ignore[attr-defined]  # the jitted program, e.g. to lower it
        return wrapped

    def compile_ahead(self, program: str, build: Callable[[], Any]) -> Any:
        """Run ``build`` — the lowering and compile of one variant of
        ``program`` ahead of its first use — and count it as a compile
        of ``program``: :meth:`wrap` only sees a compile that a call
        sets off."""
        w0 = self._wall_ns()
        t0 = self._clock()
        compiled = build()
        self._note_compile(program, self._clock() - t0, w0)
        return compiled

    def _note_compile(
        self, program: str, duration_s: float, start_wall_ns: int
    ) -> None:
        steady = False
        with self._lock:
            entry = self._programs.setdefault(
                program, {"compiles": 0, "seconds_total": 0.0}
            )
            entry["compiles"] += 1
            entry["seconds_total"] += duration_s
            self.total += 1
            if self._warm:
                steady = True
                self.steady_state_recompiles += 1
        if self._metrics is not None:
            self._metrics.increment_counter(
                "app_tpu_compiles_total",
                "model", self.model_name, "program", program,
            )
            self._metrics.record_histogram(
                "app_tpu_compile_seconds", duration_s,
                "model", self.model_name,
            )
            if steady:
                self._metrics.increment_counter(
                    "app_tpu_steady_state_recompiles_total",
                    "model", self.model_name, "program", program,
                )
        if steady and self._logger is not None:
            self._logger.warnf(
                "STEADY-STATE RECOMPILE of %s (%.2fs): a compile after "
                "the warm-up fence is a fixed-shape-discipline bug — "
                "some operand's shape/dtype or a static arg changed "
                "(graftlint GL015 is the static twin of this counter)",
                program, duration_s,
            )
        self._emit_span(program, duration_s, start_wall_ns, steady)

    def _emit_span(
        self,
        program: str,
        duration_s: float,
        start_wall_ns: int,
        steady: bool,
    ) -> None:
        """Deferred ``tpu.compile`` span (PR 6 ``emit_span`` idiom:
        already-completed, explicit wall timestamps, never touches the
        ambient contextvar). Joins the calling thread's ambient trace
        when one exists, else the boot trace captured at construction,
        else mints its own."""
        tracer = get_tracer()
        if not tracer_active(tracer):
            return
        span = current_span()
        if span is not None:
            trace_id: str = span.trace_id
            parent_id: Optional[str] = span.span_id
        elif self._boot_ctx is not None:
            trace_id, parent_id = self._boot_ctx
        else:
            trace_id, parent_id = _rand_hex(16), None
        tracer.emit_span(
            "tpu.compile",
            trace_id=trace_id,
            parent_span_id=parent_id,
            start_ns=start_wall_ns,
            end_ns=start_wall_ns + int(duration_s * 1e9),
            attributes={
                "tpu.model": self.model_name,
                "tpu.program": program,
                "tpu.steady_state": steady,
            },
            status="ERROR" if steady else "OK",
        )

    # -- rendering -----------------------------------------------------

    def set_cache_info(self, info: dict[str, Any]) -> None:
        """Record the persistent compile cache's provenance (dir,
        enabled, error) — shown by :meth:`snapshot` with a live entry
        count where the directory is readable."""
        self.cache_info = dict(info)

    def _cache_snapshot(self) -> Optional[dict[str, Any]]:
        if self.cache_info is None:
            return None
        out = dict(self.cache_info)
        try:
            import os

            out["entries"] = len(os.listdir(str(out.get("dir", ""))))
        except OSError:
            # Not created yet (jax writes lazily on first compile) or
            # unreadable — provenance still reports.
            pass
        return out

    def snapshot(self) -> dict[str, Any]:
        cache = self._cache_snapshot()
        with self._lock:
            out: dict[str, Any] = {
                "total": self.total,
                "steady_state_recompiles": self.steady_state_recompiles,
                "warm": self._warm,
                "programs": {
                    name: {
                        "compiles": entry["compiles"],
                        "seconds_total": round(entry["seconds_total"], 6),
                    }
                    for name, entry in sorted(self._programs.items())
                },
            }
        if cache is not None:
            out["compile_cache"] = cache
        return out


def _call_signature(args: tuple, kwargs: dict) -> tuple:
    """Shape/dtype signature of a call's operands (the shared-jit /
    fallback compile detector): array-likes key by (shape, dtype),
    dict/tuple pytrees recurse, scalars by value — mirroring what
    distinguishes XLA cache entries under fixed-shape discipline.
    Attribute reads only: nothing here may repr() an array (that
    materializes it on host) or the detector itself would become a
    hot-path sync."""

    def sig(x: Any) -> Any:
        shape = getattr(x, "shape", None)
        if shape is not None:
            return (tuple(shape), str(getattr(x, "dtype", "")))
        if isinstance(x, dict):
            return tuple(sorted((k, sig(v)) for k, v in x.items()))
        if isinstance(x, (list, tuple)):
            return tuple(sig(i) for i in x)
        if x is None or isinstance(x, (bool, int, float, str)):
            return x
        return type(x).__name__

    return (
        tuple(sig(a) for a in args),
        tuple(sorted((k, sig(v)) for k, v in kwargs.items())),
    )
