"""The graftlint rule set (GL001–GL024).

Each rule encodes one class of TPU-serving bug that generic linters
cannot see because it is a *semantic* property of the jax programming
model, not a syntax smell. The heuristics are deliberately conservative:
a rule should only fire where a human reviewer would at least pause —
anything intentional gets an inline ``# graftlint: disable=RULE`` with
its justification, which doubles as documentation at the call site.

GL001–GL019, GL023 and GL024 are per-file :class:`Rule`\\ s;
GL020–GL022 are :class:`ProjectRule`\\ s running against the cross-file
:class:`~gofr_tpu.analysis.project.ProjectIndex` (call graph, lock
model, thread roots) built by the two-phase runner.
"""

from __future__ import annotations

import ast
from dataclasses import replace
from typing import Iterator, Optional, Sequence

from gofr_tpu.analysis.core import (
    FileContext,
    Finding,
    LintConfig,
    ProjectRule,
    Rule,
)
from gofr_tpu.analysis.project import AttrAccess, ProjectIndex, lock_regions

# ----------------------------------------------------------------------
# shared AST helpers
# ----------------------------------------------------------------------


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _jit_call(node: ast.AST) -> Optional[ast.Call]:
    """The ``jax.jit(...)``/``pjit(...)``/``partial(jax.jit, ...)`` Call
    carrying static-arg kwargs, if ``node`` is a jit wrapper expression."""
    if not isinstance(node, ast.Call):
        return None
    name = dotted_name(node.func) or ""
    short = name.rsplit(".", 1)[-1]
    if short in ("jit", "pjit"):
        return node
    if short == "partial" and node.args:
        inner = dotted_name(node.args[0]) or ""
        if inner.rsplit(".", 1)[-1] in ("jit", "pjit"):
            return node
    return None


def is_jit_decorated(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
    for dec in fn.decorator_list:
        name = dotted_name(dec) or ""
        if name.rsplit(".", 1)[-1] in ("jit", "pjit"):
            return True
        if _jit_call(dec) is not None:
            return True
    return False


def jit_static_names(
    fn: ast.FunctionDef | ast.AsyncFunctionDef,
) -> set[str]:
    """Parameter names declared static via static_argnums/static_argnames
    on the function's jit decorator (constant specs only)."""
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    static: set[str] = set()
    for dec in fn.decorator_list:
        call = _jit_call(dec)
        if call is None:
            continue
        for kw in call.keywords:
            value = _const_value(kw.value)
            if kw.arg == "static_argnums" and value is not None:
                nums = value if isinstance(value, (tuple, list)) else (value,)
                for n in nums:
                    if isinstance(n, int) and 0 <= n < len(params):
                        static.add(params[n])
            elif kw.arg == "static_argnames" and value is not None:
                names = value if isinstance(value, (tuple, list)) else (value,)
                static.update(str(n) for n in names)
    return static


def _const_value(node: ast.AST):
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def _contains_shape_attr(node: ast.AST) -> bool:
    return any(
        isinstance(sub, ast.Attribute) and sub.attr == "shape"
        for sub in ast.walk(node)
    )


_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}


# ----------------------------------------------------------------------
# GL001 — host↔device sync on the hot path
# ----------------------------------------------------------------------


class HostDeviceSyncRule(Rule):
    """``.item()`` / ``float()`` / ``int()`` / ``np.asarray()`` on a
    device array forces a blocking device→host transfer. On the decode
    hot path one stray sync serializes the pipelined windows and costs a
    full host↔device round trip per call.

    Device values are recognized by this codebase's ``*_dev`` naming
    convention (the engine's device-resident planes) plus names assigned
    from ``jnp.*``/``jax.device_put`` expressions in the same scope.
    """

    rule_id = "GL001"
    name = "host-device-sync"
    rationale = (
        "blocking device→host syncs on the dispatch path serialize the "
        "window pipeline; fetch asynchronously or keep the value on device"
    )

    def __init__(self, hot_path_dirs: Sequence[str] = ("serving", "ops")) -> None:
        self._dirs = tuple(hot_path_dirs)

    def applies_to(self, path: str) -> bool:
        return any(f"/{d}/" in f"/{path}" for d in self._dirs)

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        device_names = self._infer_device_names(tree)

        def is_device(node: ast.AST) -> bool:
            while isinstance(node, ast.Subscript):
                node = node.value
            name = dotted_name(node)
            if name is None:
                return False
            leaf = name.rsplit(".", 1)[-1]
            return leaf.endswith("_dev") or leaf in device_names

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            # x.item() — always a sync.
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "item"
                and not node.args
            ):
                yield self.finding(
                    ctx, node,
                    "`.item()` blocks on a device→host transfer; fetch via "
                    "an async copy (`copy_to_host_async`) or batch the read",
                )
                continue
            fname = dotted_name(node.func) or ""
            leaf = fname.rsplit(".", 1)[-1]
            if not node.args:
                continue
            arg = node.args[0]
            if fname in ("float", "int", "bool") and is_device(arg):
                yield self.finding(
                    ctx, node,
                    f"`{fname}()` on a device array is a blocking "
                    "device→host sync on the hot path",
                )
            elif leaf in ("asarray", "array") and fname.split(".")[0] in (
                "np", "numpy", "onp"
            ) and is_device(arg):
                yield self.finding(
                    ctx, node,
                    f"`{fname}()` on a device array blocks until the "
                    "transfer completes; overlap it with "
                    "`copy_to_host_async` + `is_ready` instead",
                )

    @staticmethod
    def _infer_device_names(tree: ast.Module) -> set[str]:
        """Names assigned from obviously-device-producing expressions."""
        out: set[str] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign) or not isinstance(
                node.value, ast.Call
            ):
                continue
            src = dotted_name(node.value.func) or ""
            root, leaf = src.split(".")[0], src.rsplit(".", 1)[-1]
            if root in ("jnp", "jax") or leaf in ("device_put",):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        out.add(tgt.id)
        return out


# ----------------------------------------------------------------------
# GL002 — Python branching on tracer values inside jit
# ----------------------------------------------------------------------


class TracerBranchRule(Rule):
    """Inside a ``@jax.jit`` function the array arguments are tracers:
    ``if x > 0:`` raises ``TracerBoolConversionError`` at trace time (or
    silently bakes one branch in if the value is concrete on the first
    call). Data-dependent control flow belongs in ``lax.cond`` /
    ``lax.while_loop`` / ``jnp.where``.

    Shape/dtype reads (``x.shape``, ``x.ndim``, ``len(x)``) are static
    under tracing and never flagged; parameters named in
    ``static_argnums``/``static_argnames`` are exempt.
    """

    rule_id = "GL002"
    name = "tracer-branch"
    rationale = (
        "Python `if`/`while` on a traced value either crashes at trace "
        "time or freezes one branch into the compiled program"
    )

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and is_jit_decorated(node):
                yield from self._check_fn(node, ctx)

    def _check_fn(
        self, fn: ast.FunctionDef | ast.AsyncFunctionDef, ctx: FileContext
    ) -> Iterator[Finding]:
        static = jit_static_names(fn)
        tainted = {
            a.arg
            for a in (
                fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
            )
            if a.arg not in static and a.arg not in ("self", "cls")
        }
        for stmt in ast.walk(fn):
            if isinstance(stmt, ast.Assign):
                # One-pass taint propagation through simple assignments.
                if self._expr_tainted(stmt.value, tainted):
                    for tgt in stmt.targets:
                        for name in ast.walk(tgt):
                            if isinstance(name, ast.Name):
                                tainted.add(name.id)
            elif isinstance(stmt, (ast.If, ast.While)):
                if self._expr_tainted(stmt.test, tainted):
                    kind = "if" if isinstance(stmt, ast.If) else "while"
                    yield self.finding(
                        ctx, stmt.test,
                        f"Python `{kind}` on a traced value inside "
                        f"`{fn.name}` (jitted); use `lax.cond`/"
                        "`lax.while_loop`/`jnp.where`, or declare the "
                        "argument static",
                    )

    def _expr_tainted(self, expr: ast.AST, tainted: set[str]) -> bool:
        """Does ``expr``'s *runtime value* depend on a tracer?

        Attribute reads of static metadata (``.shape``, ``.dtype``, …)
        and ``len()``/``isinstance()`` calls launder the taint — they
        are Python-level constants under tracing."""
        if isinstance(expr, ast.Name):
            return expr.id in tainted
        if isinstance(expr, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops
        ):
            # `x is None` / `x is not None` are Python identity checks —
            # resolved at trace time, never a tracer bool.
            return False
        if isinstance(expr, ast.Attribute):
            if expr.attr in _STATIC_ATTRS:
                return False
            return self._expr_tainted(expr.value, tainted)
        if isinstance(expr, ast.Call):
            name = dotted_name(expr.func) or ""
            if name in ("len", "isinstance", "getattr", "hasattr", "type"):
                return False
            parts: list[ast.AST] = list(expr.args) + [
                kw.value for kw in expr.keywords
            ]
            if isinstance(expr.func, ast.Attribute):
                # x.sum() on a tracer yields a tracer.
                parts.append(expr.func.value)
            return any(self._expr_tainted(p, tainted) for p in parts)
        return any(
            self._expr_tainted(child, tainted)
            for child in ast.iter_child_nodes(expr)
        )


# ----------------------------------------------------------------------
# GL003 — recompilation hazards
# ----------------------------------------------------------------------


class RecompilationHazardRule(Rule):
    """Every distinct static-arg value (and every unhashable one) is a
    fresh XLA compile; on TPU a recompile is seconds of wall clock in
    the serving path. Flags:

    * mutable literals (list/dict/set) passed in a static position of a
      module-local ``jax.jit(fn, static_arg...)`` wrapper — unhashable,
      crashes at call time;
    * dict/cache keys or subscripts built from ``.shape`` f-strings —
      the signature of a hand-rolled compile cache keyed on shapes,
      which grows without bound under bucketed padding drift.
    """

    rule_id = "GL003"
    name = "recompilation-hazard"
    rationale = (
        "unhashable/mutable static args fail or recompile per call; "
        "shape-keyed caches churn compiles under padding drift"
    )

    _MUTABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                ast.SetComp, ast.GeneratorExp)

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        jitted = self._collect_jit_wrappers(tree)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                yield from self._check_call(node, jitted, ctx)
                continue
            # d[f"{x.shape}"] / {x.shape: ...} — shape-keyed cache.
            if isinstance(node, ast.Subscript) and self._shape_key(node.slice):
                yield self.finding(
                    ctx, node,
                    "subscript keyed on a `.shape`-derived value: a "
                    "hand-rolled compile cache keyed on shapes recompiles "
                    "per padding bucket; key on the bucket id instead",
                )
            elif isinstance(node, ast.Dict):
                for key in node.keys:
                    if key is not None and self._shape_key(key):
                        yield self.finding(
                            ctx, key,
                            "dict key built from `.shape`: shape-keyed "
                            "caches churn compiles; key on the padded "
                            "bucket instead",
                        )

    def _check_call(
        self,
        node: ast.Call,
        jitted: dict[str, tuple[set[int], set[str]]],
        ctx: FileContext,
    ) -> Iterator[Finding]:
        name = dotted_name(node.func)
        if name is None or name not in jitted:
            return
        static_nums, static_names = jitted[name]
        for i, arg in enumerate(node.args):
            if i in static_nums and isinstance(arg, self._MUTABLE):
                yield self.finding(
                    ctx, arg,
                    f"mutable literal passed as static arg {i} of jitted "
                    f"`{name}`: unhashable static args raise at call "
                    "time — pass a tuple or mark the arg non-static",
                )
        for kw in node.keywords:
            if kw.arg in static_names and isinstance(kw.value, self._MUTABLE):
                yield self.finding(
                    ctx, kw.value,
                    f"mutable literal passed as static kwarg "
                    f"`{kw.arg}` of jitted `{name}`",
                )

    @staticmethod
    def _shape_key(node: ast.AST) -> bool:
        if isinstance(node, ast.JoinedStr):
            return any(
                _contains_shape_attr(v.value)
                for v in node.values
                if isinstance(v, ast.FormattedValue)
            )
        return isinstance(node, ast.Attribute) and node.attr == "shape"

    @staticmethod
    def _collect_jit_wrappers(
        tree: ast.Module,
    ) -> dict[str, tuple[set[int], set[str]]]:
        """``g = jax.jit(f, static_argnums=(1,))`` → {"g": ({1}, set())}."""
        out: dict[str, tuple[set[int], set[str]]] = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            call = _jit_call(node.value)
            if call is None:
                continue
            nums: set[int] = set()
            names: set[str] = set()
            for kw in call.keywords:
                value = _const_value(kw.value)
                if value is None:
                    continue
                seq = value if isinstance(value, (tuple, list)) else (value,)
                if kw.arg == "static_argnums":
                    nums.update(int(v) for v in seq if isinstance(v, int))
                elif kw.arg == "static_argnames":
                    names.update(str(v) for v in seq)
            if not nums and not names:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out[tgt.id] = (nums, names)
        return out


# ----------------------------------------------------------------------
# GL004 — blocking calls in async / hot-path code
# ----------------------------------------------------------------------


class BlockingCallRule(Rule):
    """``time.sleep`` (and friends) inside an ``async def`` stalls the
    whole event loop; inside the batcher/scheduler/engine hot path it
    turns an event wait into a latency floor — a 50 ms poll loop is
    50 ms of p50 added to every drain. Waits belong on
    ``threading.Event``/``Condition`` (or ``asyncio.sleep`` in async
    code) where a state change wakes the waiter immediately.
    """

    rule_id = "GL004"
    name = "blocking-call"
    rationale = (
        "blocking sleeps/IO stall the event loop or add poll-interval "
        "latency to the batch hot path; wait on events/conditions"
    )

    _BLOCKING = {
        "time.sleep": "blocks the thread",
        "os.system": "synchronous subprocess",
        "subprocess.run": "synchronous subprocess",
        "subprocess.call": "synchronous subprocess",
        "subprocess.check_call": "synchronous subprocess",
        "subprocess.check_output": "synchronous subprocess",
        "subprocess.Popen": "spawns a process (fork latency)",
        "requests.get": "synchronous HTTP",
        "requests.post": "synchronous HTTP",
        "urllib.request.urlopen": "synchronous HTTP",
        "socket.create_connection": "synchronous connect",
    }

    def __init__(
        self,
        hot_path_files: Sequence[str] = (
            "serving/batcher.py",
            "serving/scheduler.py",
            "serving/engine.py",
        ),
    ) -> None:
        self._hot_files = tuple(hot_path_files)

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        hot_file = any(ctx.path.endswith(f) for f in self._hot_files)
        # Collect the line spans of async defs so sync helpers nested in
        # them are covered too.
        async_spans = [
            (n.lineno, n.end_lineno or n.lineno)
            for n in ast.walk(tree)
            if isinstance(n, ast.AsyncFunctionDef)
        ]
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            desc = self._BLOCKING.get(name)
            if desc is None:
                continue
            in_async = any(
                lo <= node.lineno <= hi for lo, hi in async_spans
            )
            if in_async:
                yield self.finding(
                    ctx, node,
                    f"`{name}` ({desc}) inside an `async def` stalls the "
                    "event loop; use the asyncio equivalent or "
                    "`run_in_executor`",
                )
            elif hot_file and name == "time.sleep":
                yield self.finding(
                    ctx, node,
                    "`time.sleep` on the batcher/scheduler hot path adds "
                    "its full poll interval to tail latency; wait on a "
                    "`threading.Event`/`Condition` instead",
                )


# ----------------------------------------------------------------------
# GL005 — lock discipline over shared mutable state
# ----------------------------------------------------------------------


class LockDisciplineRule(Rule):
    """If a class protects an attribute with a lock *somewhere*, every
    write to that attribute outside ``__init__`` must hold the lock —
    mixed discipline is how torn reads ship. Attributes written at least
    once inside ``with self.<lock>:`` are 'guarded'; any other write to
    them outside a with-lock block is flagged. (The race-detector-CI
    spirit of the reference framework, approximated statically.)

    The hot-path files compose ONE runtime object (mixins over
    ``InferenceEngine``), so guarded-attribute knowledge is unioned
    across all of them — a write in ``scheduler.py`` is checked against
    locks taken in ``engine.py`` and vice versa; a per-class analysis
    would be blind across exactly the seam it was written for.
    """

    rule_id = "GL005"
    name = "lock-discipline"
    rationale = (
        "an attribute written both under and outside a lock has no "
        "consistent happens-before edge; hold the lock everywhere"
    )

    def __init__(
        self,
        hot_path_files: Sequence[str] = (
            "serving/batcher.py",
            "serving/scheduler.py",
            "serving/engine.py",
        ),
    ) -> None:
        self._hot_files = tuple(hot_path_files)
        self._sibling_guarded: dict[str, set[str]] = {}

    def applies_to(self, path: str) -> bool:
        return any(path.endswith(f) for f in self._hot_files)

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        composed = self._composed_guarded(tree, ctx)
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(node, ctx, composed)

    def _composed_guarded(
        self, tree: ast.Module, ctx: FileContext
    ) -> set[str]:
        """Locked-write attributes across the whole composed object:
        every class in this file plus every class in the sibling
        hot-path files (parsed once per run)."""
        guarded: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                guarded |= self._class_writes(node)[0]
        abs_path = ctx.abs_path
        suffix = next(
            (f for f in self._hot_files if ctx.path.endswith(f)), None
        )
        if abs_path and suffix and abs_path.endswith(suffix):
            base = abs_path[: -len(suffix)]
            for sib in self._hot_files:
                if sib == suffix:
                    continue
                sib_path = base + sib
                if sib_path not in self._sibling_guarded:
                    self._sibling_guarded[sib_path] = (
                        self._guarded_in_file(sib_path)
                    )
                guarded |= self._sibling_guarded[sib_path]
        return guarded

    def _guarded_in_file(self, path: str) -> set[str]:
        try:
            with open(path, "r", encoding="utf-8") as fp:
                tree = ast.parse(fp.read())
        except (OSError, SyntaxError, UnicodeDecodeError):
            return set()
        out: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                out |= self._class_writes(node)[0]
        return out

    def _class_writes(
        self, cls: ast.ClassDef
    ) -> tuple[set[str], list[tuple[str, ast.AST]]]:
        """(locked-write attrs, unlocked writes) for one class body,
        skipping ``__init__`` (construction precedes sharing)."""
        guarded: set[str] = set()
        unlocked: list[tuple[str, ast.AST]] = []
        for method in cls.body:
            if not isinstance(method, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if method.name == "__init__":
                continue
            # Shared with the GL020 index path: lock_regions() subtracts
            # manual release()/acquire() windows, so a write in the
            # except/finally of a released window counts as UNLOCKED —
            # the lexical with-span alone used to mis-classify exactly
            # that shape as guarded. Nested defs keep their own regions
            # (lock_regions stops at scope boundaries, so union them).
            regions = list(lock_regions(method))
            for sub in ast.walk(method):
                if isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)
                ) and sub is not method:
                    regions.extend(lock_regions(sub))
            for stmt in ast.walk(method):
                attr = self._self_attr_write(stmt)
                if attr is None:
                    continue
                line = stmt.lineno
                if any(r.holds_at(line) for r in regions):
                    guarded.add(attr)
                else:
                    unlocked.append((attr, stmt))
        return guarded, unlocked

    def _check_class(
        self, cls: ast.ClassDef, ctx: FileContext, composed: set[str]
    ) -> Iterator[Finding]:
        _, unlocked = self._class_writes(cls)
        for attr, stmt in unlocked:
            if attr in composed:
                yield self.finding(
                    ctx, stmt,
                    f"`self.{attr}` is written under a lock elsewhere in "
                    "the composed serving core but not here; hold the "
                    "same lock (or document why this write cannot race)",
                )

    @staticmethod
    def _self_attr_write(stmt: ast.AST) -> Optional[str]:
        """`self.x = ...` / `self.x += ...` (plain flags, not containers:
        `self._slots[i] = ...` mutates through a reference the scheduler
        thread owns — a different discipline, out of scope here)."""
        targets: list[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        for tgt in targets:
            if (
                isinstance(tgt, ast.Attribute)
                and isinstance(tgt.value, ast.Name)
                and tgt.value.id == "self"
            ):
                return tgt.attr
        return None


# ----------------------------------------------------------------------
# GL006 — swallowed exceptions in request paths
# ----------------------------------------------------------------------


class ExceptionSwallowRule(Rule):
    """A bare/overbroad `except` that neither logs, re-raises, nor
    records the error swallows jax's rich failure modes
    (``XlaRuntimeError``, OOM, donation errors) exactly where the caller
    most needs them — a request silently returns garbage instead of a
    500. Handlers that log, raise, or set an exception on a future are
    fine; ``pass``-bodies must narrow the exception type or carry a
    suppression with their justification.
    """

    rule_id = "GL006"
    name = "swallowed-exception"
    rationale = (
        "broad except+pass hides XlaRuntimeError/OOM from request "
        "callers; narrow the type, log, or re-raise"
    )

    _BROAD = {"Exception", "BaseException"}

    def __init__(
        self, request_path_dirs: Sequence[str] = ("serving", "ops", "grpc")
    ) -> None:
        self._dirs = tuple(request_path_dirs)

    def applies_to(self, path: str) -> bool:
        return any(f"/{d}/" in f"/{path}" for d in self._dirs)

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield self.finding(
                    ctx, node,
                    "bare `except:` catches SystemExit/KeyboardInterrupt "
                    "too; name the exception type",
                )
                continue
            if not self._is_broad(node.type):
                continue
            if not self._swallows(node):
                continue
            yield self.finding(
                ctx, node,
                f"broad `except {ast.unparse(node.type)}` whose body "
                "neither logs, re-raises, nor records the error would "
                "swallow jax runtime failures in the request path",
            )

    def _is_broad(self, type_node: ast.AST) -> bool:
        names = []
        if isinstance(type_node, ast.Tuple):
            names = [dotted_name(e) or "" for e in type_node.elts]
        else:
            names = [dotted_name(type_node) or ""]
        return any(n.rsplit(".", 1)[-1] in self._BROAD for n in names)

    @staticmethod
    def _swallows(handler: ast.ExceptHandler) -> bool:
        """True when the body is a pure no-op (pass/continue/break, a
        constant expression, or a bare/constant return) — a handler that
        assigns a fallback, logs, raises, or records the error is
        *handling*, not swallowing."""
        for stmt in handler.body:
            if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
                continue
            if isinstance(stmt, ast.Expr) and isinstance(
                stmt.value, ast.Constant
            ):
                continue
            if isinstance(stmt, ast.Return) and (
                stmt.value is None or isinstance(stmt.value, ast.Constant)
            ):
                continue
            return False
        return True


# ----------------------------------------------------------------------
# GL007 — donated-buffer reuse after donate_argnums
# ----------------------------------------------------------------------


class DonatedBufferReuseRule(Rule):
    """``donate_argnums`` tells XLA it may overwrite the argument's
    buffer in place — after the call, the donated array is INVALID.
    Reading it again returns a "buffer has been deleted or donated"
    error at best and silent garbage through an aliased view at worst.
    The idiomatic pattern rebinds the result to the donated name
    (``cache = step(cache, ...)``); this rule flags reads of a donated
    name after a call that did NOT rebind it.

    Recognized donors: module/class-level ``g = jax.jit(f,
    donate_argnums=...)`` wrappers (including ``self.attr`` targets)
    and immediately-invoked ``jax.jit(f, donate_argnums=...)(x)``.
    Reassigning the name between the call and the read clears the
    taint.
    """

    rule_id = "GL007"
    name = "donated-buffer-reuse"
    rationale = (
        "donate_argnums invalidates the argument's buffer at the call; "
        "reading it afterwards crashes or returns garbage — rebind the "
        "result to the donated name"
    )

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        donors = self._collect_donating_wrappers(tree)
        for scope in self._scopes(tree):
            yield from self._check_scope(scope, donors, ctx)

    @staticmethod
    def _scopes(tree: ast.Module) -> Iterator[ast.AST]:
        yield tree  # module body is a scope too
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node

    @staticmethod
    def _donate_nums(call: ast.Call) -> set[int]:
        """donate_argnums of a jit Call (constant specs only)."""
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                value = _const_value(kw.value)
                if value is None:
                    return set()
                seq = value if isinstance(value, (tuple, list)) else (value,)
                return {int(v) for v in seq if isinstance(v, int)}
        return set()

    def _collect_donating_wrappers(
        self, tree: ast.Module
    ) -> dict[str, set[int]]:
        """``g = jax.jit(f, donate_argnums=(0,))`` → {"g": {0}} (also
        ``self._step = ...`` attribute targets)."""
        out: dict[str, set[int]] = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            call = _jit_call(node.value)
            if call is None:
                continue
            nums = self._donate_nums(call)
            if not nums:
                continue
            for tgt in node.targets:
                name = dotted_name(tgt)
                if name is not None:
                    out[name] = nums
        return out

    def _check_scope(
        self,
        scope: ast.AST,
        donors: dict[str, set[int]],
        ctx: FileContext,
    ) -> Iterator[Finding]:
        # One recursive pass over the scope (NOT descending into nested
        # function/class bodies — separate scopes, separate lifetimes),
        # carrying the enclosing assignment's targets so `x = g(x)`
        # counts as a rebind, not a reuse.
        donations: list[tuple[str, int, int]] = []  # (name, line, col)
        assigns: dict[str, list[int]] = {}
        loads: list[tuple[str, ast.AST]] = []
        # Reads lexically inside a donating call evaluate BEFORE the
        # donation happens — never flag them.
        pre_call: set[int] = set()

        def visit(node: ast.AST, targets: list[str]) -> None:
            if isinstance(node, ast.Assign):
                names = [
                    n
                    for tgt in node.targets
                    for sub in ast.walk(tgt)
                    for n in [dotted_name(sub)]
                    if n is not None
                ]
                for n in names:
                    assigns.setdefault(n, []).append(node.lineno)
                targets = names
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                n = dotted_name(node.target)
                if n is not None:
                    assigns.setdefault(n, []).append(node.lineno)
                    targets = [n]
            if isinstance(node, ast.Call):
                nums: set[int] = set()
                fname = dotted_name(node.func)
                if fname is not None and fname in donors:
                    nums = donors[fname]
                elif isinstance(node.func, ast.Call):
                    jit = _jit_call(node.func)
                    if jit is not None:
                        nums = self._donate_nums(jit)
                if nums:
                    for sub in ast.walk(node):
                        pre_call.add(id(sub))
                for i in nums:
                    if i < len(node.args):
                        arg = node.args[i]
                        donated = dotted_name(arg)
                        if donated is not None and donated not in targets:
                            donations.append(
                                (donated, node.lineno, node.col_offset)
                            )
            if isinstance(node, (ast.Name, ast.Attribute)) and isinstance(
                getattr(node, "ctx", None), ast.Load
            ):
                name = dotted_name(node)
                if name is not None:
                    loads.append((name, node))
            for child in ast.iter_child_nodes(node):
                if isinstance(
                    child,
                    (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
                ):
                    continue
                visit(child, targets)

        for child in ast.iter_child_nodes(scope):
            if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                continue
            visit(child, [])

        for name, node in loads:
            if id(node) in pre_call:  # evaluated before the donation
                continue
            for donated, call_line, call_col in donations:
                if name != donated:
                    continue
                if (node.lineno, node.col_offset) < (call_line, call_col):
                    continue
                # A reassignment between donation and read clears it.
                if any(
                    call_line < a <= node.lineno
                    for a in assigns.get(name, ())
                ):
                    continue
                yield self.finding(
                    ctx, node,
                    f"`{name}` was donated to a jitted call on line "
                    f"{call_line} (donate_argnums) — its buffer is gone; "
                    "rebind the call's result to it or drop the donation",
                )
                break


# ----------------------------------------------------------------------
# GL008 — jnp.asarray / jnp.array inside lax.scan bodies
# ----------------------------------------------------------------------


class ScanBodyAsarrayRule(Rule):
    """``jnp.asarray`` / ``jnp.array`` inside a ``jax.lax.scan`` body
    materializes its operand as a fresh constant (or convert op) in the
    LOOP BODY: the tracer runs the body once, but the embedded constant
    is baked per-compile and host data re-converts inside the hottest
    region of the program — on TPU a large baked constant bloats the
    executable and a per-iteration convert defeats the reason the layer
    stack was scanned in the first place. Hoist the conversion out of
    the body (close over a device array, or thread it through the scan
    carry/xs).

    Recognized bodies: a named function or lambda passed as the first
    argument (or ``f=`` keyword) of ``lax.scan`` / ``jax.lax.scan``.
    Factory calls (``scan(make_body(...), ...)``) are out of reach
    statically and deliberately skipped — conservative by design.
    """

    rule_id = "GL008"
    name = "scan-body-asarray"
    rationale = (
        "jnp.asarray/jnp.array in a lax.scan body bakes a constant or "
        "re-converts host data inside the scanned region; hoist it out "
        "of the body"
    )

    _CONVERTERS = {
        "jnp.asarray", "jnp.array", "jax.numpy.asarray", "jax.numpy.array",
    }

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        defs: dict[str, ast.FunctionDef | ast.AsyncFunctionDef] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[node.name] = node
        seen: set[int] = set()  # one body scanned twice reports once
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted_name(node.func) or ""
            if fname not in ("lax.scan", "jax.lax.scan"):
                continue
            body_expr: Optional[ast.AST] = None
            if node.args:
                body_expr = node.args[0]
            else:
                for kw in node.keywords:
                    if kw.arg == "f":
                        body_expr = kw.value
                        break
            body: Optional[ast.AST] = None
            if isinstance(body_expr, ast.Lambda):
                body = body_expr
            elif isinstance(body_expr, ast.Name):
                body = defs.get(body_expr.id)
            if body is None or id(body) in seen:
                continue
            seen.add(id(body))
            yield from self._check_body(body, ctx)

    def _check_body(self, body: ast.AST, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(body):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted_name(node.func) or ""
            if fname in self._CONVERTERS:
                yield self.finding(
                    ctx, node,
                    f"`{fname}` inside a `lax.scan` body bakes a "
                    "constant / re-converts host data in the scanned "
                    "region; hoist it out of the body (close over a "
                    "device array or thread it through the carry)",
                )


# ----------------------------------------------------------------------
# GL009 — per-request jit-cache growth
# ----------------------------------------------------------------------


class JitCacheGrowthRule(Rule):
    """A hand-rolled compile cache keyed on per-request values grows
    without bound: every distinct prompt length / shape / tensor size
    adds ANOTHER compiled executable that is never evicted, and on TPU
    each entry is seconds of compile time plus resident program memory.
    Two signatures are flagged:

    * ``functools.lru_cache`` / ``functools.cache`` on a callable whose
      body builds a jitted program, when the cache key can grow per
      request — an unbounded decorator (``cache`` or
      ``lru_cache(maxsize=None)``), a shape/length-named parameter, or
      a method (``self`` in the key also pins every engine instance
      alive);
    * dict-cached jit builders — ``cache[seq_len] = jax.jit(...)``
      (or ``.setdefault``) where the key is a shape/length-derived
      value.

    The fix is the codebase's bucketed-padding idiom: compile one
    fixed-shape program per PADDING BUCKET (a small closed set) and pad
    requests into it, instead of one program per observed request
    shape. GL003 flags ``.shape``-f-string keys; this rule catches the
    lru_cache/method and bare length-key forms it cannot see.
    """

    rule_id = "GL009"
    name = "jit-cache-growth"
    rationale = (
        "shape-keyed lru_cache/dict caches of jitted programs compile "
        "and retain one executable per observed request shape; key on a "
        "closed set of padding buckets instead"
    )

    _SHAPE_HINTS = ("shape", "len", "length", "size", "tokens", "dim")

    @classmethod
    def _shapeish(cls, name: str) -> bool:
        lowered = name.lower()
        return any(hint in lowered for hint in cls._SHAPE_HINTS)

    @staticmethod
    def _cache_decorator(dec: ast.AST) -> Optional[tuple[str, bool]]:
        """(decorator name, unbounded?) for lru_cache/cache decorators."""
        call = dec if isinstance(dec, ast.Call) else None
        target = call.func if call is not None else dec
        name = dotted_name(target) or ""
        short = name.rsplit(".", 1)[-1]
        if short == "cache":
            return name, True
        if short != "lru_cache":
            return None
        if call is None:
            return name, False  # bare @lru_cache: default maxsize=128
        for kw in call.keywords:
            if kw.arg == "maxsize":
                value = _const_value(kw.value)
                return name, value is None
        if call.args:
            return name, _const_value(call.args[0]) is None
        return name, False

    @staticmethod
    def _builds_jit(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and _jit_call(node) is not None:
                return True
        return False

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_cached_fn(node, ctx)
            elif isinstance(node, ast.Assign):
                yield from self._check_dict_cache(
                    node.targets, node.value, ctx
                )
            elif isinstance(node, ast.Call):
                yield from self._check_setdefault(node, ctx)

    def _check_cached_fn(
        self, fn: ast.FunctionDef | ast.AsyncFunctionDef, ctx: FileContext
    ) -> Iterator[Finding]:
        cached = None
        for dec in fn.decorator_list:
            cached = self._cache_decorator(dec)
            if cached is not None:
                break
        if cached is None or not self._builds_jit(fn):
            return
        dec_name, unbounded = cached
        params = [
            a.arg for a in fn.args.posonlyargs + fn.args.args
            + fn.args.kwonlyargs
        ]
        is_method = bool(params) and params[0] in ("self", "cls")
        shape_params = [p for p in params if self._shapeish(p)]
        if not (unbounded or is_method or shape_params):
            return  # bounded cache over a closed key set: the fix itself
        if unbounded:
            why = f"`@{dec_name}` is unbounded"
        elif is_method:
            why = (
                f"`@{dec_name}` on a method keys on `{params[0]}` too — "
                "the cache pins every instance AND grows per shape"
            )
        else:
            why = (
                f"key includes per-request value(s) "
                f"{', '.join(repr(p) for p in shape_params)}"
            )
        yield self.finding(
            ctx, fn,
            f"`{fn.name}` builds a jitted program under `@{dec_name}` "
            f"and {why}: the compile cache grows per request — key on a "
            "closed set of padding buckets (bounded maxsize, "
            "module-level function)",
        )

    def _check_dict_cache(
        self, targets: list[ast.AST], value: ast.AST, ctx: FileContext
    ) -> Iterator[Finding]:
        if not isinstance(value, ast.Call) or _jit_call(value) is None:
            return
        for tgt in targets:
            if isinstance(tgt, ast.Subscript) and self._growing_key(
                tgt.slice
            ):
                yield self.finding(
                    ctx, tgt,
                    "jitted program stored under a shape/length-derived "
                    "dict key: the cache compiles and retains one "
                    "executable per observed request shape; key on a "
                    "padding bucket instead",
                )

    def _check_setdefault(
        self, node: ast.Call, ctx: FileContext
    ) -> Iterator[Finding]:
        if not (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "setdefault"
            and len(node.args) >= 2
        ):
            return
        if _jit_call(node.args[1]) is not None and self._growing_key(
            node.args[0]
        ):
            yield self.finding(
                ctx, node,
                "jitted program `setdefault`-cached under a shape/"
                "length-derived key grows the compile cache per request; "
                "key on a padding bucket instead",
            )

    def _growing_key(self, key: ast.AST) -> bool:
        """A key expression that can take unboundedly many per-request
        values: a shape attribute, a shape/length-named name, or a
        tuple/f-string containing one."""
        for sub in ast.walk(key):
            if isinstance(sub, ast.Attribute) and sub.attr == "shape":
                return True
            if isinstance(sub, ast.Name) and self._shapeish(sub.id):
                return True
            if isinstance(sub, ast.Attribute) and self._shapeish(sub.attr):
                return True
            if isinstance(sub, ast.Call):
                fname = dotted_name(sub.func) or ""
                if fname == "len":
                    return True
        return False


# ----------------------------------------------------------------------
# GL010 — repeated host pull of the same device value in a loop
# ----------------------------------------------------------------------


class RepeatedHostPullRule(Rule):
    """``np.asarray(x)`` / ``jax.device_get(x)`` re-materializes the
    ENTIRE device array on the host every call. Doing it repeatedly for
    the same value inside one loop body — the typical shape is indexing
    one row per iteration, ``np.asarray(x_dev)[row]`` — pays the full
    device→host copy once per iteration for data that does not change
    across iterations. (The scheduler's prefill-emit loop did exactly
    this: every emitting row re-pulled the whole fetched block, per row,
    per window.) The fix is one hoisted host copy before (or memoized
    across) the loop, indexed per iteration.

    Conservative by design: only *literally identical* name/attribute
    arguments count, a rebind of the argument anywhere in the loop body
    disqualifies it (each iteration may legitimately pull a different
    array under the same name), and nested function bodies are skipped
    (a closure is not executed per iteration by the loop itself).
    ``jnp.asarray`` is the host→device direction and is GL008's
    business, not this rule's.
    """

    rule_id = "GL010"
    name = "repeated-host-pull"
    rationale = (
        "pulling the same device value to host more than once in a loop "
        "re-copies the full array per iteration; hoist one host copy "
        "before the loop and index it"
    )

    @staticmethod
    def _pull_arg(node: ast.AST) -> Optional[str]:
        """The pulled value's dotted name for ``np.asarray(x)`` /
        ``numpy.asarray(x)`` / ``jax.device_get(x)`` calls; None for
        anything else (including ``jnp.asarray`` — that is an upload)."""
        if not isinstance(node, ast.Call) or not node.args:
            return None
        fname = dotted_name(node.func) or ""
        short = fname.rsplit(".", 1)[-1]
        if short == "asarray":
            if fname.rsplit(".", 1)[0] not in ("np", "numpy"):
                return None
        elif short != "device_get":
            return None
        return dotted_name(node.args[0])

    @staticmethod
    def _loop_walk(loop: ast.AST) -> Iterator[ast.AST]:
        """Every node lexically inside the loop's body/orelse, skipping
        nested function/lambda bodies (not run per iteration by this
        loop) but descending into nested loops/ifs/withs."""
        stack = list(getattr(loop, "body", [])) + list(
            getattr(loop, "orelse", [])
        )
        while stack:
            node = stack.pop()
            yield node
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            stack.extend(ast.iter_child_nodes(node))

    @staticmethod
    def _rebound_disqualifies(arg: str, rebound: set[str]) -> bool:
        """A pull of ``arg`` is disqualified when any rebound target is
        a dotted prefix of it (rebinding ``self``/``self.buf`` changes
        what ``self.buf.x`` resolves to) or vice versa (storing through
        ``self.buf.x`` may mutate the object ``self.buf`` holds)."""
        parts = arg.split(".")
        prefixes = {".".join(parts[: i + 1]) for i in range(len(parts))}
        if prefixes & rebound:
            return True
        return any(r.startswith(arg + ".") for r in rebound)

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        seen: set[tuple[int, str]] = set()
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            rebound: set[str] = set()
            if isinstance(loop, (ast.For, ast.AsyncFor)):
                for t in ast.walk(loop.target):
                    if isinstance(t, ast.Name):
                        rebound.add(t.id)
            pulls: dict[str, list[ast.Call]] = {}
            for node in self._loop_walk(loop):
                if isinstance(node, ast.Name) and isinstance(
                    node.ctx, (ast.Store, ast.Del)
                ):
                    rebound.add(node.id)
                elif isinstance(
                    node, (ast.Attribute, ast.Subscript)
                ) and isinstance(node.ctx, (ast.Store, ast.Del)):
                    # self.buf = ... / self.buf[i] = ... inside the loop:
                    # pulls of self.buf (or anything reached through it)
                    # may see a different array each iteration, same as
                    # a bare-name rebind.
                    target = (
                        node if isinstance(node, ast.Attribute)
                        else node.value
                    )
                    dn = dotted_name(target)
                    if dn:
                        rebound.add(dn)
                arg = self._pull_arg(node)
                if arg is not None:
                    pulls.setdefault(arg, []).append(node)  # type: ignore[arg-type]
            for arg, calls in pulls.items():
                if len(calls) < 2:
                    continue
                if self._rebound_disqualifies(arg, rebound):
                    continue  # per-iteration value: each pull differs
                calls.sort(key=lambda c: (c.lineno, c.col_offset))
                anchor = calls[1]
                key = (anchor.lineno, arg)
                if key in seen:  # nested loops see the same pair twice
                    continue
                seen.add(key)
                yield self.finding(
                    ctx, anchor,
                    f"`{arg}` is pulled to host {len(calls)} times in "
                    f"this loop — each call copies the full device "
                    f"array; hoist one host copy before the loop and "
                    f"index it per iteration",
                )


# ----------------------------------------------------------------------
# GL011 — per-row clock reads in scheduler emit/decode loops
# ----------------------------------------------------------------------


class PerRowClockRule(Rule):
    """``time.time()`` / ``time.monotonic()`` inside the per-row body of
    a scheduler emit/decode loop stamps per TOKEN: at window size k over
    S slots that is k×S clock syscalls per window of pure host overhead
    on the dispatch path, for timestamps whose consumers (ttft fields,
    phase timelines, histograms) cannot tell apart anyway — every row
    processed in one window/flush landed together. Timestamps belong at
    WINDOW granularity: read the clock once before the loop and share
    the value (exactly what ``_process_window``/``_flush_prefill_emits``
    do).

    Scope and conservatism: hot-path files only (the composed scheduler
    object), ``for`` loops only — ``while`` loops re-reading the clock
    are deadline/poll loops whose *condition* is the time, not per-row
    stamping — and nested function/lambda bodies are skipped (not run
    per iteration by this loop). ``while`` subtrees inside a flagged
    ``for`` are skipped for the same reason.
    """

    rule_id = "GL011"
    name = "per-row-clock"
    rationale = (
        "clock reads inside per-row emit/decode loop bodies are "
        "per-token host overhead; read the clock once per window/flush "
        "and share the timestamp"
    )

    _CLOCKS = frozenset((
        "time.time", "time.monotonic", "time.perf_counter",
        "time.time_ns", "time.monotonic_ns", "time.perf_counter_ns",
    ))

    def __init__(
        self,
        hot_path_files: Sequence[str] = (
            "serving/batcher.py",
            "serving/scheduler.py",
            "serving/engine.py",
        ),
    ) -> None:
        self._hot_files = tuple(hot_path_files)

    def applies_to(self, path: str) -> bool:
        return any(path.endswith(f) for f in self._hot_files)

    @staticmethod
    def _loop_walk(loop: ast.AST) -> Iterator[ast.AST]:
        """Nodes lexically inside the loop's body/orelse, skipping
        nested function/lambda bodies and ``while`` subtrees (poll
        loops legitimately re-read the clock per check)."""
        stack = list(getattr(loop, "body", [])) + list(
            getattr(loop, "orelse", [])
        )
        while stack:
            node = stack.pop()
            yield node
            if isinstance(
                node,
                (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                 ast.While),
            ):
                continue
            stack.extend(ast.iter_child_nodes(node))

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        seen: set[tuple[int, int]] = set()
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor)):
                continue
            for node in self._loop_walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                if name not in self._CLOCKS:
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:  # nested for-loops see the call twice
                    continue
                seen.add(key)
                yield self.finding(
                    ctx, node,
                    f"`{name}()` inside a per-row loop body stamps per "
                    "token — host overhead on the dispatch path; read "
                    "the clock once per window/flush before the loop "
                    "and share the value",
                )


# ----------------------------------------------------------------------
# GL012 — blocking network I/O without an explicit timeout
# ----------------------------------------------------------------------


class BlockingIONoTimeoutRule(Rule):
    """A socket/HTTP-client call without an explicit timeout in the
    serving or service tier blocks a worker FOREVER when the peer
    blackholes (SYN dropped by a dead pod's floating IP, a remote that
    accepts and never answers). In the replica data plane that is not a
    hung request — it is a leaked thread per hang, an in-flight count
    that never drains, and a replica the pool cannot drain or retire.
    Every outbound call must state its budget: library defaults are
    either infinite (``socket``, ``urllib``) or owned by someone else's
    upgrade (``httpx``).

    Flagged (in ``serving/`` and ``service/`` only):

    * ``httpx.Client(...)`` / ``httpx.AsyncClient(...)`` constructed
      without a ``timeout=`` argument (per-request overrides exist, but
      the constructor default is the safety net every call inherits);
    * ``requests.get/post/…/request(...)`` without ``timeout=`` —
      requests' default is no timeout at all;
    * ``urllib.request.urlopen(...)`` without ``timeout`` (keyword or
      second positional);
    * ``socket.create_connection(addr)`` without a timeout (keyword or
      second positional) — inherits the global default, usually None.

    Conservative: only fully-dotted library entry points are matched
    (a method call on an already-configured client object carries its
    constructor's budget and is not re-flagged).
    """

    rule_id = "GL012"
    name = "blocking-io-no-timeout"
    rationale = (
        "outbound network calls in the serving/service tier must carry "
        "an explicit timeout; a blackholed peer otherwise parks the "
        "worker thread forever and the replica can never drain"
    )

    #: Constructors whose ``timeout=`` kwarg is the budget.
    _CLIENT_CTORS = frozenset(("httpx.Client", "httpx.AsyncClient"))
    #: requests' module-level verbs (timeout kwarg only).
    _REQUESTS_VERBS = frozenset(
        f"requests.{verb}" for verb in (
            "get", "post", "put", "patch", "delete", "head", "options",
            "request",
        )
    )
    #: Calls where the timeout may also be a positional argument:
    #: name → index of the timeout positional.
    _POSITIONAL_TIMEOUT = {
        "urllib.request.urlopen": 2,
        "socket.create_connection": 1,
    }

    def __init__(
        self, scoped_dirs: Sequence[str] = ("serving", "service")
    ) -> None:
        self._dirs = tuple(scoped_dirs)

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return any(f"/{d}/" in norm or norm.startswith(f"{d}/")
                   for d in self._dirs)

    @staticmethod
    def _has_timeout_kwarg(call: ast.Call) -> bool:
        return any(
            kw.arg == "timeout" or kw.arg is None  # **kwargs may carry it
            for kw in call.keywords
        )

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            if name in self._CLIENT_CTORS or name in self._REQUESTS_VERBS:
                if not self._has_timeout_kwarg(node):
                    yield self.finding(
                        ctx, node,
                        f"`{name}(...)` without an explicit `timeout=`: "
                        "a blackholed peer blocks this call forever; "
                        "state the budget at the call site",
                    )
            elif name in self._POSITIONAL_TIMEOUT:
                n_pos = self._POSITIONAL_TIMEOUT[name]
                if (
                    len(node.args) < n_pos + 1
                    and not self._has_timeout_kwarg(node)
                ):
                    yield self.finding(
                        ctx, node,
                        f"`{name}(...)` without a timeout (keyword or "
                        f"positional #{n_pos + 1}): inherits an infinite "
                        "default; state the budget at the call site",
                    )


# ----------------------------------------------------------------------
# GL013 — retry loops without backoff
# ----------------------------------------------------------------------


class RetryNoBackoffRule(Rule):
    """A retry loop that re-attempts I/O with NO delay between attempts
    is a thundering-herd amplifier: every client that failed at t₀
    retries at exactly t₀+ε, re-spiking the replica/service it just
    helped knock over — the failure mode the serving tier's own
    machinery (``RetryConfig``, the hedge budget, the tier-transfer
    backoff) exists to prevent. In ``serving/`` and ``service/`` every
    retry loop must back off (jittered, via ``RetryConfig.delay_s`` or
    an explicit sleep between attempts).

    Heuristics (deliberately conservative — plain iteration loops and
    adoption walks must not trip it):

    * a ``for`` loop counting attempts — target or ``range()`` argument
      names matching ``retry``/``retries``/``attempt`` — or a ``while``
      loop whose condition reads such a name;
    * whose body contains a ``try`` with at least one handler that
      swallows the failure (no ``raise`` anywhere in the handler — the
      retry-semantics marker: failures are absorbed so the next
      iteration re-attempts);
    * and whose body contains NO backoff: no call to anything named
      ``sleep``/``*.sleep``, no ``delay_s(...)``, and no ``RetryConfig``
      reference inside the loop.
    """

    rule_id = "GL013"
    name = "retry-no-backoff"
    rationale = (
        "retry loops in the serving/service tier must back off "
        "(jittered) between attempts; immediate re-attempts amplify "
        "the very overload they are retrying through"
    )

    _RETRYISH = ("retry", "retries", "attempt")

    def __init__(
        self, scoped_dirs: Sequence[str] = ("serving", "service")
    ) -> None:
        self._dirs = tuple(scoped_dirs)

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return any(f"/{d}/" in norm or norm.startswith(f"{d}/")
                   for d in self._dirs)

    @classmethod
    def _retryish(cls, name: Optional[str]) -> bool:
        low = (name or "").lower()
        return any(marker in low for marker in cls._RETRYISH)

    @classmethod
    def _names_in(cls, node: ast.AST) -> Iterator[str]:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                yield sub.id
            elif isinstance(sub, ast.Attribute):
                yield sub.attr

    @classmethod
    def _is_retry_loop(cls, loop: ast.AST) -> bool:
        if isinstance(loop, ast.For):
            if any(cls._retryish(n) for n in cls._names_in(loop.target)):
                return True
            it = loop.iter
            if (
                isinstance(it, ast.Call)
                and (dotted_name(it.func) or "") == "range"
            ):
                return any(
                    cls._retryish(n)
                    for arg in it.args for n in cls._names_in(arg)
                )
            return False
        if isinstance(loop, ast.While):
            return any(cls._retryish(n) for n in cls._names_in(loop.test))
        return False

    @staticmethod
    def _loop_body(loop: ast.AST) -> Iterator[ast.AST]:
        """Nodes lexically inside the loop body, skipping nested
        function/lambda bodies (not run per attempt by this loop)."""
        stack = list(getattr(loop, "body", [])) + list(
            getattr(loop, "orelse", [])
        )
        while stack:
            node = stack.pop()
            yield node
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            stack.extend(ast.iter_child_nodes(node))

    @classmethod
    def _swallows(cls, loop: ast.AST) -> bool:
        """True when some ``except`` handler in the loop body absorbs
        the failure (no raise in it) — the marker that the loop's next
        iteration is a RE-ATTEMPT, not plain iteration."""
        for node in cls._loop_body(loop):
            if not isinstance(node, ast.Try):
                continue
            for handler in node.handlers:
                if not any(
                    isinstance(sub, ast.Raise)
                    for stmt in handler.body for sub in ast.walk(stmt)
                ):
                    return True
        return False

    @classmethod
    def _has_backoff(cls, loop: ast.AST) -> bool:
        for node in cls._loop_body(loop):
            if isinstance(node, ast.Call):
                name = dotted_name(node.func) or ""
                short = name.rsplit(".", 1)[-1].lstrip("_")
                if short in ("sleep", "delay_s"):
                    return True
            if isinstance(node, ast.Name) and node.id == "RetryConfig":
                return True
            if isinstance(node, ast.Attribute) and node.attr == "RetryConfig":
                return True
        return False

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            if not self._is_retry_loop(loop):
                continue
            if not self._swallows(loop):
                continue
            if self._has_backoff(loop):
                continue
            yield self.finding(
                ctx, loop,
                "retry loop re-attempts with no backoff between "
                "attempts — failed peers get re-hit immediately and in "
                "lockstep; sleep a jittered delay (RetryConfig.delay_s) "
                "before each re-attempt",
            )


# ----------------------------------------------------------------------
# GL014 — cross-mesh host pulls / sharding-annotation drift
# ----------------------------------------------------------------------


class CrossMeshHostPullRule(Rule):
    """GSPMD-sharded serving (``TPU_TP``) puts the KV pool and params on
    a mesh; the serving hot path must stay device-count-agnostic. Two
    drift patterns break that silently:

    * **Cross-mesh host pull**: ``jax.device_get`` / ``np.asarray`` /
      ``np.array`` applied to the KV cache's planes (any expression
      mentioning ``cache``) gathers a SHARDED array to host — on a tp
      mesh that is an all-gather of pool HBM per call, and on a
      multi-host mesh it deadlocks outright. Block extraction must go
      through the export seam (``ops/kv_cache.export_blocks`` — one
      deliberate, documented bounce at prefill finalize), so host-pull
      calls inside ``export``-named functions are exempt.

    * **Sharding-annotation drift**: a bare one-argument
      ``jax.device_put(x)`` carries NO placement. In the mesh-aware hot
      modules every host→device upload must say where it lands (the
      engine's ``_up`` places replicated ``NamedSharding``s); an
      unannotated put commits to the default device and every sharded
      dispatch then drags the operand cross-mesh.

    Scope: the serving hot-path modules (scheduler/engine/programs/
    batcher) — boot/loader code may bounce deliberately.
    """

    rule_id = "GL014"
    name = "cross-mesh-host-pull"
    rationale = (
        "sharded serving must not host-pull cache planes outside the "
        "export seam, and hot-path uploads must carry an explicit "
        "sharding — unannotated transfers silently all-gather or "
        "replicate on a tp mesh"
    )

    #: numpy calls that materialize on host (module-qualified only —
    #: ``jnp.asarray`` stays on device, bare ``asarray`` is ambiguous).
    _PULLS = ("asarray", "array")
    _HOST_MODS = ("np", "numpy")

    def __init__(
        self,
        scoped_files: Sequence[str] = (
            "serving/scheduler.py",
            "serving/engine.py",
            "serving/programs.py",
            "serving/batcher.py",
        ),
    ) -> None:
        self._files = tuple(scoped_files)

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return any(norm.endswith(f) for f in self._files)

    @staticmethod
    def _mentions_cache(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and "cache" in sub.attr.lower():
                return True
            if isinstance(sub, ast.Name) and "cache" in sub.id.lower():
                return True
        return False

    @classmethod
    def _is_host_pull(cls, call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        parts = name.split(".")
        short = parts[-1]
        if short == "device_get":
            # jax.device_get / self._jax.device_get / bare device_get.
            return True
        if short in cls._PULLS and len(parts) >= 2:
            return parts[-2] in cls._HOST_MODS
        return False

    @staticmethod
    def _is_bare_device_put(call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        if name.rsplit(".", 1)[-1] != "device_put":
            return False
        operands = len(call.args) + len(call.keywords)
        return operands <= 1

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        # Function names walked INTO at each node, so seam functions
        # (export_*) exempt their whole lexical body.
        def visit(node: ast.AST, in_export: bool) -> Iterator[Finding]:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                in_export = in_export or "export" in node.name.lower()
            if isinstance(node, ast.Call):
                if (
                    not in_export
                    and self._is_host_pull(node)
                    and any(self._mentions_cache(a) for a in node.args)
                ):
                    yield self.finding(
                        ctx, node,
                        "host pull of KV-cache planes outside the export "
                        "seam — on a tp mesh this all-gathers sharded "
                        "pool HBM per call (and deadlocks multi-host); "
                        "ship blocks via ops/kv_cache.export_blocks",
                    )
                elif self._is_bare_device_put(node):
                    yield self.finding(
                        ctx, node,
                        "device_put without an explicit sharding/device "
                        "in a mesh-aware hot module — the operand "
                        "commits to the default device and sharded "
                        "dispatches drag it cross-mesh; place it with a "
                        "NamedSharding (the engine's _up helper)",
                    )
            for child in ast.iter_child_nodes(node):
                yield from visit(child, in_export)

        yield from visit(tree, False)


# ----------------------------------------------------------------------
# GL015 — jax.jit created inside a per-request function body
# ----------------------------------------------------------------------


class JitInRequestPathRule(Rule):
    """``jax.jit``/``pjit`` CALLED inside a per-request function body
    builds a fresh jitted callable per call — its XLA cache is garbage-
    collected with it, so every request pays a full trace+compile (and
    the compile lock serializes the scheduler behind it). The serving
    discipline is: programs are built ONCE, at module scope or in a
    builder, and request paths only *call* them. This rule is the
    static twin of the runtime
    ``app_tpu_steady_state_recompiles_total`` counter
    (``serving/device_telemetry.py``): the counter catches shape drift
    through a correctly-built program, this catches the program being
    rebuilt at all.

    Exempt (not request paths):

    * module scope — the normal home of shared jits;
    * builder functions: ``_build_*`` / ``*_program`` (the
      ``serving/programs.py`` idiom), with exemption inherited by
      their nested defs (a decorator inside ``_build_llm_steps`` runs
      at build time);
    * constructors and boot/state rebuilds: ``__init__`` / ``_init*``
      / ``init*`` — they run per boot, not per request;
    * loader modules (``hf_loader.py`` / ``checkpoint.py`` /
      ``lora.py``): checkpoint ingestion jits leaf-transforms by
      design.

    Deliberate boot-path jits elsewhere carry an inline
    ``# graftlint: disable=GL015`` with their justification.
    """

    rule_id = "GL015"
    name = "jit-in-request-path"
    rationale = (
        "jax.jit created inside a per-request function recompiles on "
        "every call and serializes the scheduler behind the compile "
        "lock; build programs once (module scope or a _build_*/"
        "*_program builder) and only CALL them on request paths"
    )

    _EXEMPT_FILES = (
        "serving/hf_loader.py",
        "serving/checkpoint.py",
        "serving/lora.py",
    )

    def __init__(self, scoped_dirs: Sequence[str] = ("serving",)) -> None:
        self._dirs = tuple(scoped_dirs)

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        if any(norm.endswith(f) for f in self._EXEMPT_FILES):
            return False
        return any(
            f"/{d}/" in norm or norm.startswith(f"{d}/")
            for d in self._dirs
        )

    @staticmethod
    def _exempt_name(name: str) -> bool:
        return (
            name.startswith("_build")
            or name.endswith("_program")
            or name == "__init__"
            or name.startswith("_init")
            or name.startswith("init")
        )

    @classmethod
    def _is_jit_maker(cls, call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        short = name.rsplit(".", 1)[-1]
        if short in ("jit", "pjit"):
            return True
        if short == "partial":
            # partial(jax.jit, ...) — the decorator-factory idiom.
            return any(
                (dotted_name(a) or "").rsplit(".", 1)[-1]
                in ("jit", "pjit")
                for a in call.args
            )
        return False

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        # Exemption inherits downward (the GL014 in_export idiom): a
        # jit created anywhere inside a builder's lexical body runs at
        # build time, however deeply nested.
        def visit(
            node: ast.AST, in_function: bool, exempt: bool
        ) -> Iterator[Finding]:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                exempt = exempt or self._exempt_name(node.name)
                in_function = True
            if (
                in_function
                and not exempt
                and isinstance(node, ast.Call)
                and self._is_jit_maker(node)
            ):
                yield self.finding(
                    ctx, node,
                    "jax.jit created inside a per-request function — "
                    "each call rebuilds and recompiles the program; "
                    "build it once at module scope or in a _build_*/"
                    "*_program builder and call the built program here",
                )
            for child in ast.iter_child_nodes(node):
                yield from visit(child, in_function, exempt)

        yield from visit(tree, False, False)


# ----------------------------------------------------------------------
# GL016 — request-controlled strings as metric label values
# ----------------------------------------------------------------------


class UnboundedMetricLabelRule(Rule):
    """A metric label whose value flows from a request-controlled
    string (tenant ids, header values) is an unbounded-cardinality
    time series: every distinct client-chosen value mints a new series,
    and an adversarial (or merely enthusiastic) client can blow up the
    exporter's memory and the scrape size. The serving discipline is
    the ``TPU_TENANT_LABEL_MAX`` clamp (``serving/tenant_ledger.py``):
    request-controlled values pass through a bounded label mapper
    (first-K distinct values, overflow folded into ``_other``) before
    they may reach a label. This rule is the static twin of that
    runtime clamp.

    Flagged (in ``serving/`` and ``service/`` only):

    * metrics-manager recording calls (``increment_counter`` /
      ``add_counter`` / ``record_histogram`` / ``set_gauge`` /
      ``delta_updown_counter``) whose *label value* positions (the odd
      elements of the trailing key/value pairs) contain a
      request-controlled expression — an identifier or attribute named
      ``tenant`` / ``tenant_id``, or a ``header``/``headers`` access;
    * prometheus-style ``.labels(...)`` calls with such a value.

    Clean: the value is wrapped in a clamp/allowlist helper — a call to
    a function whose name is ``label_for`` / ``clamp_label`` or ends
    with ``_label`` (the bounded-mapper naming convention).

    Conservative: only the marker names above taint; a label value
    computed from engine-owned state (model names, reason literals,
    outcome vocabularies) never matches.
    """

    rule_id = "GL016"
    name = "unbounded-metric-label"
    rationale = (
        "request-controlled strings (tenant ids, headers) as metric "
        "label values are unbounded cardinality; route them through a "
        "bounded clamp/allowlist helper (TPU_TENANT_LABEL_MAX idiom) "
        "before they reach a label"
    )

    #: Recorder method → index of the first label element in args
    #: (after name [+ value]); the trailing args alternate key, value.
    _RECORDERS = {
        "increment_counter": 1,
        "add_counter": 2,
        "record_histogram": 2,
        "set_gauge": 2,
        "delta_updown_counter": 2,
    }
    _TAINT = frozenset(("tenant", "tenant_id", "header", "headers"))
    _CLAMPS = frozenset(("label_for", "clamp_label"))

    def __init__(
        self, scoped_dirs: Sequence[str] = ("serving", "service")
    ) -> None:
        self._dirs = tuple(scoped_dirs)

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return any(
            f"/{d}/" in norm or norm.startswith(f"{d}/")
            for d in self._dirs
        )

    @classmethod
    def _is_clamped(cls, node: ast.AST) -> bool:
        """The value is a clamp-helper call — bounded by construction."""
        if not isinstance(node, ast.Call):
            return False
        name = (dotted_name(node.func) or "").rsplit(".", 1)[-1]
        return name in cls._CLAMPS or name.endswith("_label")

    @classmethod
    def _tainted(cls, node: ast.AST) -> bool:
        if cls._is_clamped(node):
            return False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id in cls._TAINT:
                return True
            if isinstance(sub, ast.Attribute) and sub.attr in cls._TAINT:
                return True
        return False

    def _check_value(
        self, ctx: FileContext, call: ast.Call, value: ast.AST
    ) -> Iterator[Finding]:
        if self._tainted(value):
            yield self.finding(
                ctx, call,
                "request-controlled string as a metric label value — "
                "unbounded series cardinality; clamp it through a "
                "bounded label mapper (label_for/*_label; "
                "TPU_TENANT_LABEL_MAX idiom) first",
            )

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not isinstance(
                node.func, ast.Attribute
            ):
                continue
            attr = node.func.attr
            if attr in self._RECORDERS:
                first = self._RECORDERS[attr]
                labels = node.args[first:]
                # Values sit at the odd offsets of the key/value tail.
                for i in range(1, len(labels), 2):
                    for f in self._check_value(ctx, node, labels[i]):
                        yield f
                        break
            elif attr == "labels":
                # prometheus_client idiom: .labels(v1, k2=v2).
                for value in (*node.args, *(
                    kw.value for kw in node.keywords
                )):
                    found = False
                    for f in self._check_value(ctx, node, value):
                        yield f
                        found = True
                        break
                    if found:
                        break


# ----------------------------------------------------------------------
# GL017 — control-loop threshold comparisons without hysteresis
# ----------------------------------------------------------------------


class ThresholdNoHysteresisRule(Rule):
    """A control loop that flips state the first time a noisy load
    signal crosses a threshold oscillates: one bad tick trips the
    actuator, the next good tick untrips it, and the system flaps at
    the noise frequency instead of responding to sustained pressure.
    Every controller in this repo that earned its keep — the watchdog,
    the pool scaler's sustain windows, the brownout ladder
    (``serving/brownout.py``), the hedge budget — pairs its thresholds
    with a sustain window, an enter/exit hysteresis band, or a budget
    guard. This rule is the static twin of that discipline.

    Flagged (in ``serving/`` and ``service/`` only): an ``if`` whose
    test compares a *signal* expression (a name mentioning ``burn``,
    ``headroom``, ``load_per_replica``, ``occupancy``, or
    ``saturation``) against a *threshold* expression (a name mentioning
    ``threshold``, ``floor``, ``enter``, ``exit``, ``watermark``, or
    ``limit`` — the env-derived-knob naming convention), where the
    branch body **assigns instance state** (``self.x = ...`` — a level,
    a mode, an open/tripped flag), and the enclosing function shows no
    guard evidence: no name mentioning ``since`` / ``sustain`` /
    ``streak`` / ``consecutive`` / ``hysteresis`` / ``budget`` /
    ``window``.

    Clean: shedding or raising inside the branch (a per-request
    decision, not controller state), sustain-anchor idioms
    (``self._pressure_since``), ``Sustain``/``HedgeBudget``-style
    guards, and comparisons whose sides don't carry both marker
    families. Conservative by construction — it looks for the *shape*
    of a flapping controller, not for every threshold.
    """

    rule_id = "GL017"
    name = "threshold-no-hysteresis"
    rationale = (
        "state flipped on a raw signal-vs-threshold comparison flaps "
        "at the noise frequency; pair the threshold with a sustain "
        "window or an enter/exit hysteresis band (the "
        "serving/brownout.py ladder idiom)"
    )

    _SIGNALS = ("burn", "headroom", "load_per_replica", "occupancy",
                "saturation")
    _THRESHOLDS = ("threshold", "floor", "enter", "exit", "watermark",
                   "limit")
    _GUARDS = ("since", "sustain", "streak", "consecutive",
               "hysteresis", "budget", "window")

    def __init__(
        self, scoped_dirs: Sequence[str] = ("serving", "service")
    ) -> None:
        self._dirs = tuple(scoped_dirs)

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return any(
            f"/{d}/" in norm or norm.startswith(f"{d}/")
            for d in self._dirs
        )

    @staticmethod
    def _idents(node: ast.AST) -> list[str]:
        """Every identifier string mentioned in the expression."""
        out: list[str] = []
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                out.append(sub.id.lower())
            elif isinstance(sub, ast.Attribute):
                out.append(sub.attr.lower())
            elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(sub.name.lower())
        return out

    @classmethod
    def _mentions(cls, node: ast.AST, markers: Sequence[str]) -> bool:
        return any(
            m in ident for ident in cls._idents(node) for m in markers
        )

    @classmethod
    def _threshold_compare(cls, test: ast.AST) -> bool:
        """One side mentions a signal, the other a threshold knob."""
        for node in ast.walk(test):
            if not isinstance(node, ast.Compare) or len(node.comparators) != 1:
                continue
            left, right = node.left, node.comparators[0]
            if (
                cls._mentions(left, cls._SIGNALS)
                and cls._mentions(right, cls._THRESHOLDS)
            ) or (
                cls._mentions(right, cls._SIGNALS)
                and cls._mentions(left, cls._THRESHOLDS)
            ):
                return True
        return False

    @staticmethod
    def _flips_self_state(body: Sequence[ast.stmt]) -> bool:
        """The branch assigns an attribute on ``self`` — controller
        state, as opposed to shedding/raising a request decision."""
        for stmt in body:
            for node in ast.walk(stmt):
                targets: list[ast.expr] = []
                if isinstance(node, ast.Assign):
                    targets = list(node.targets)
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for target in targets:
                    if (
                        isinstance(target, ast.Attribute)
                        and isinstance(target.value, ast.Name)
                        and target.value.id == "self"
                    ):
                        return True
        return False

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            # Guard evidence anywhere in the function exempts every
            # comparison in it: sustain anchors and hysteresis pairs
            # live next to the thresholds they guard.
            if self._mentions(fn, self._GUARDS):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.If):
                    continue
                if not self._threshold_compare(node.test):
                    continue
                if not self._flips_self_state(node.body):
                    continue
                yield self.finding(
                    ctx, node,
                    "state flipped on a raw threshold comparison of a "
                    "load signal — one noisy tick trips it and the "
                    "next untrips it; add a sustain window or an "
                    "enter/exit hysteresis pair (the brownout-ladder "
                    "idiom)",
                )


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------

ALL_RULES: "tuple[type[Rule], ...]" = (
    HostDeviceSyncRule,
    TracerBranchRule,
    RecompilationHazardRule,
    BlockingCallRule,
    LockDisciplineRule,
    ExceptionSwallowRule,
    DonatedBufferReuseRule,
    ScanBodyAsarrayRule,
    JitCacheGrowthRule,
    RepeatedHostPullRule,
    PerRowClockRule,
    BlockingIONoTimeoutRule,
    RetryNoBackoffRule,
    CrossMeshHostPullRule,
    JitInRequestPathRule,
    UnboundedMetricLabelRule,
    ThresholdNoHysteresisRule,
)
# (GL018/GL019 are appended to ALL_RULES below their definitions — the
# tuple predates them and later rules are defined after the registry.)


# ----------------------------------------------------------------------
# GL018 — host pull inside the device transfer leg
# ----------------------------------------------------------------------


class HostPullInDeviceLegRule(Rule):
    """The disaggregated-tier DEVICE leg exists to ship KV blocks
    pool→pool without touching host memory: per-block jitted extraction
    on the exporter, an explicit sharding-aware ``device_put``, and a
    donated jitted write on the importer. Its whole value evaporates —
    silently — if any step materializes a cache plane on host:
    ``jax.device_get`` or ``np.asarray``/``np.array`` of a cache/plane
    expression inside device-leg code re-introduces the PCIe round trip
    the leg was built to remove, and on a GSPMD-sharded pool it
    all-gathers shard HBM per call. The naming convention IS the
    contract: functions named ``*_device_leg`` or ``paged_move*`` are
    the device leg, and a host pull of plane data inside one is always
    a bug (the deliberate host bounce lives in ``export*`` functions,
    GL014's documented seam).
    """

    rule_id = "GL018"
    name = "host-pull-in-device-leg"
    rationale = (
        "the device transfer leg must never bounce cache planes "
        "through host memory — a device_get/np.asarray inside "
        "*_device_leg/paged_move* code silently re-adds the PCIe "
        "round trip (and all-gathers sharded pool HBM) the leg "
        "exists to remove"
    )

    _PULLS = ("asarray", "array")
    _HOST_MODS = ("np", "numpy")
    #: expression names that identify KV-plane data in transfer code.
    _PLANE_HINTS = ("cache", "plane", "blk", "block", "payload", "k_s",
                    "v_s")

    @staticmethod
    def _is_device_leg_name(name: str) -> bool:
        low = name.lower()
        return low.endswith("_device_leg") or low.startswith("paged_move")

    @classmethod
    def _mentions_plane(cls, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            label = None
            if isinstance(sub, ast.Attribute):
                label = sub.attr.lower()
            elif isinstance(sub, ast.Name):
                label = sub.id.lower()
            if label and any(h in label for h in cls._PLANE_HINTS):
                return True
        return False

    @classmethod
    def _is_host_pull(cls, call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        parts = name.split(".")
        short = parts[-1]
        if short == "device_get":
            return True
        if short in cls._PULLS and len(parts) >= 2:
            return parts[-2] in cls._HOST_MODS
        return False

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        # The device-leg property inherits into nested defs (a helper
        # closure inside a device-leg function is still the device leg).
        def visit(node: ast.AST, in_leg: bool) -> Iterator[Finding]:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                in_leg = in_leg or self._is_device_leg_name(node.name)
            if (
                in_leg
                and isinstance(node, ast.Call)
                and self._is_host_pull(node)
                and any(self._mentions_plane(a) for a in node.args)
            ):
                yield self.finding(
                    ctx, node,
                    "host pull of cache-plane data inside the device "
                    "transfer leg — this re-adds the host bounce the "
                    "leg exists to remove; keep planes on device "
                    "(jitted extract/move + explicit device_put) or "
                    "route through the export* host-bounce seam",
                )
            for child in ast.iter_child_nodes(node):
                yield from visit(child, in_leg)

        yield from visit(tree, False)


ALL_RULES = ALL_RULES + (HostPullInDeviceLegRule,)


# ----------------------------------------------------------------------
# GL019 — device sync outside the designated device-window seam
# ----------------------------------------------------------------------


class SyncOutsideDeviceWaitRule(Rule):
    """The scheduler loop's phase attribution (``serving/
    loop_profiler.py``) rests on one structural contract: the loop
    blocks on the device ONLY inside the designated device-window seam
    (``_process_window``'s fetch, ``_dispatch_window``'s lockstep
    barrier). A ``block_until_ready`` / ``.item()`` / ``float()``-on-a-
    device-value call inside any *other* scheduler-loop-phase function
    silently converts a host phase into a hidden device wait: the
    ``host_overhead_ratio`` signal then blames Python for time the
    device actually took (or vice versa), and the sync serializes the
    pipelined windows exactly like a GL001 hot-path sync — except
    invisibly, because the phase gauges say "prefill" or "reap".

    Scope: scheduler files only (``serving/scheduler.py`` — every
    function there IS loop-phase code). The seam functions are exempt
    by name; device values are recognized by the codebase's ``*_dev``
    naming convention, with call results excluded (``float(pull(x_dev)
    [row])`` is a host read of an already-pulled array, not a sync).
    Deliberate waits elsewhere (the multi-process lockstep barriers)
    carry an inline disable — the justification doubles as
    documentation.
    """

    rule_id = "GL019"
    name = "sync-outside-device-wait"
    rationale = (
        "a device sync inside a host loop phase hides a device wait "
        "from the per-phase attribution and serializes the pipelined "
        "windows; block on the device only inside the designated "
        "device-window seam (_process_window/_dispatch_window) or "
        "justify the barrier with an inline disable"
    )

    #: The designated device-wait seam: the only scheduler functions
    #: that may legitimately block on the device.
    _SEAM = frozenset(("_process_window", "_dispatch_window"))

    def __init__(
        self, scheduler_files: Sequence[str] = ("serving/scheduler.py",)
    ) -> None:
        self._files = tuple(scheduler_files)

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return any(norm.endswith(f) for f in self._files)

    @staticmethod
    def _dev_root(node: ast.AST) -> bool:
        """True when the expression is a Name/Attribute/Subscript chain
        whose ROOT identifier follows the ``*_dev`` device-plane naming
        convention. Call results are excluded: a pulled host copy of a
        device array is not a sync."""
        while isinstance(node, (ast.Subscript, ast.Attribute)):
            if (
                isinstance(node, ast.Attribute)
                and node.attr.endswith("_dev")
            ):
                return True
            node = node.value
        return isinstance(node, ast.Name) and node.id.endswith("_dev")

    @classmethod
    def _is_sync(cls, call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        short = name.rsplit(".", 1)[-1]
        if short == "block_until_ready":
            return True
        if (
            short == "item"
            and isinstance(call.func, ast.Attribute)
            and cls._dev_root(call.func.value)
        ):
            return True
        if (
            isinstance(call.func, ast.Name)
            and call.func.id == "float"
            and len(call.args) == 1
            and cls._dev_root(call.args[0])
        ):
            return True
        return False

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        # Seam-ness inherits into nested defs (a helper closure inside
        # _process_window is still the seam); everything else in a
        # scheduler file is loop-phase code.
        def visit(node: ast.AST, in_seam: bool) -> Iterator[Finding]:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                in_seam = in_seam or node.name in self._SEAM
            if (
                not in_seam
                and isinstance(node, ast.Call)
                and self._is_sync(node)
            ):
                yield self.finding(
                    ctx, node,
                    "device sync inside a scheduler-loop phase function "
                    "outside the device-window seam — this hides a "
                    "device wait from the per-phase attribution "
                    "(host_overhead_ratio lies) and serializes the "
                    "pipelined windows; move the wait into "
                    "_process_window or justify the barrier inline",
                )
            for child in ast.iter_child_nodes(node):
                yield from visit(child, in_seam)

        yield from visit(tree, False)


ALL_RULES = ALL_RULES + (SyncOutsideDeviceWaitRule,)


class AckBeforeResultRule(Rule):
    """At-least-once delivery dies at exactly one line: the consumer
    that acks a message *before* its result is safely out. An ack is
    the broker's permission to forget — if the handler then crashes
    between the ack and the reply publish (or the terminal future
    resolution), the message is gone and the reply never happens: the
    silent-loss bug class the async serving plane (ISSUE 18) exists to
    prevent. The correct order is always publish-then-ack; a replayed
    duplicate is the dedup ledger's problem, a lost message is nobody's.

    Heuristic: inside one function body in ``pubsub/``/``serving/``
    scope, flag a ``.ack(`` call that lexically precedes a result seam
    — a ``publish``-named call (``publish``/``_publish_reply``/...), a
    dead-letter handoff, or a terminal ``set_result``/``set_exception``
    — later in the same body. A function that only acks (the dedup
    replay path, where the reply already went out) has no seam after
    the ack and does not fire; nested defs are separate bodies.
    Deliberate ack-first consumers (at-MOST-once by design) carry an
    inline disable — the justification doubles as documentation.
    """

    rule_id = "GL023"
    name = "ack-before-result"
    rationale = (
        "acking a message before its result publish / terminal seam "
        "converts at-least-once into at-most-once: a crash between the "
        "ack and the publish loses the message with no redelivery; "
        "publish the result first and let the dedup ledger absorb "
        "replayed duplicates, or justify at-most-once inline"
    )

    #: Call names that terminate a handler's result: the reply/DLQ
    #: publish and the future's terminal transitions.
    _SEAMS = ("publish", "dead_letter", "set_result", "set_exception")

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return any(
            f"/{d}/" in norm or norm.startswith(f"{d}/")
            for d in ("pubsub", "serving")
        )

    @classmethod
    def _is_seam(cls, call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        short = name.rsplit(".", 1)[-1].lstrip("_")
        return any(s in short for s in cls._SEAMS)

    @staticmethod
    def _is_ack(call: ast.Call) -> bool:
        return (
            isinstance(call.func, ast.Attribute)
            and call.func.attr == "ack"
        )

    @staticmethod
    def _body_calls(fn: ast.AST) -> "list[ast.Call]":
        """Every Call in ``fn``'s own body, nested defs excluded (a
        nested handler is its own consumer body)."""
        calls: list[ast.Call] = []
        stack: list[ast.AST] = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(node, ast.Call):
                calls.append(node)
            stack.extend(ast.iter_child_nodes(node))
        return calls

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            calls = self._body_calls(fn)
            seam_lines = [c.lineno for c in calls if self._is_seam(c)]
            if not seam_lines:
                continue
            last_seam = max(seam_lines)
            for call in calls:
                if self._is_ack(call) and call.lineno < last_seam:
                    yield self.finding(
                        ctx, call,
                        f"`{fn.name}` acks before its result publish / "
                        "terminal seam in the same body — a crash "
                        "between this ack and the publish loses the "
                        "message with no redelivery (at-least-once "
                        "becomes at-most-once); publish first, ack "
                        "last, and let the dedup ledger absorb "
                        "replays",
                    )


ALL_RULES = ALL_RULES + (AckBeforeResultRule,)


# ----------------------------------------------------------------------
# GL024 — transfer-handle acquisition without a budget
# ----------------------------------------------------------------------


class HandleNoDeadlineRule(Rule):
    """The multi-host disaggregation plane (ISSUE 19) moves KV blocks
    through *acquisition* calls — redeeming a dma claim ticket
    (``dma_fetch``), asking a remote prefill source for blocks
    (``fetch_prefilled``), waiting on the exporting scheduler
    (``export_cached``) — and every one of them blocks on another
    PROCESS. A stalled exporter, a partitioned source, or a
    half-killed pod parks the caller forever unless the call carries
    its budget; unlike an in-proc lock there is no supervisor on the
    other side to break the wait. The failure matrix's slow-loris and
    partition rows only degrade one rung because every acquisition
    site states a ``deadline=``/``timeout_s=`` bound.

    Heuristic: in ``serving/``/``service/`` scope, flag a call whose
    name ends in one of the acquisition verbs unless it carries a
    budget keyword (``deadline`` / ``timeout`` / ``timeout_s`` /
    ``wait_s`` / ``read_timeout_s`` / ``connect_timeout_s``) or a
    ``**kwargs`` splat that may. Raw socket/HTTP calls stay GL012's
    business — this rule is about the transfer-handle layer above
    them, where the budget is a ``Deadline`` threaded from the
    request.
    """

    rule_id = "GL024"
    name = "handle-no-deadline"
    rationale = (
        "cross-process transfer-handle acquisitions (dma_fetch / "
        "fetch_prefilled / export_cached) block on another process; "
        "without a deadline= / timeout_s= budget a stalled or "
        "partitioned peer parks the caller forever and the failure "
        "matrix's one-rung degradation contract breaks"
    )

    #: Call-name suffixes that acquire a cross-process transfer
    #: handle or wait on one being produced.
    _ACQUIRERS = frozenset(
        ("dma_fetch", "fetch_prefilled", "export_cached")
    )
    #: Keywords that state the budget.
    _BUDGET_KWARGS = frozenset((
        "deadline", "timeout", "timeout_s", "wait_s",
        "read_timeout_s", "connect_timeout_s",
    ))

    def applies_to(self, path: str) -> bool:
        norm = path.replace("\\", "/")
        return any(
            f"/{d}/" in norm or norm.startswith(f"{d}/")
            for d in ("serving", "service")
        )

    def check(self, tree: ast.Module, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            short = name.rsplit(".", 1)[-1]
            if short not in self._ACQUIRERS:
                continue
            if any(
                kw.arg is None or kw.arg in self._BUDGET_KWARGS
                for kw in node.keywords
            ):
                continue
            yield self.finding(
                ctx, node,
                f"`{name}(...)` acquires a cross-process transfer "
                "handle without a budget — thread the request's "
                "`deadline=` (or a `timeout_s=` bound) into the call "
                "so a stalled/partitioned peer degrades one rung "
                "instead of parking this thread forever",
            )


ALL_RULES = ALL_RULES + (HandleNoDeadlineRule,)


# ----------------------------------------------------------------------
# GL020–GL022 — project-wide concurrency rules (two-phase engine)
# ----------------------------------------------------------------------


class _ConcurrencyRule(ProjectRule):
    """Shared scoping for the project-wide concurrency rules: findings
    are only *reported* under the concurrency dirs (serving/, service/
    by default) — the index itself still spans every scanned file so
    cross-package call edges resolve."""

    def __init__(
        self, concurrency_dirs: Sequence[str] = ("serving", "service")
    ) -> None:
        self._dirs = tuple(concurrency_dirs)

    def applies_to(self, path: str) -> bool:
        parts = path.replace("\\", "/").split("/")
        return any(d in parts[:-1] for d in self._dirs)


class UnguardedSharedStateRule(_ConcurrencyRule):
    """An attribute consistently written under a lock in one method but
    accessed lock-free in another method reachable from a *different*
    thread root has no happens-before edge: the scheduler loop, prober,
    scaler, watchdog, and request threads all run concurrently, and a
    torn read across that mesh is exactly the bug class PR 14's
    lazy-init race belonged to.

    Two binding modes, strongest first:

    * **declared** — ``self._epoch = 0  # graftlint: guarded-by=_lock``
      binds the attribute to the named lock; every lock-free read *or*
      write outside ``__init__`` is flagged.
    * **inferred** — majority-access fallback: if a lock is held for
      at least two accesses (one of them a write) and for strictly more
      accesses than run lock-free, the attribute is treated as guarded
      by it and lock-free *writes* are flagged (reads are too noisy to
      infer without a declaration).

    Either way a finding additionally requires the attribute to be
    reachable from at least two distinct thread roots — single-thread
    state cannot race, however inconsistent its locking looks.
    """

    rule_id = "GL020"
    name = "unguarded-shared-state"
    rationale = (
        "an attribute written under a lock in one thread but accessed "
        "lock-free from another has no happens-before edge; hold the "
        "lock everywhere or declare the actual discipline with "
        "# graftlint: guarded-by=<lock>"
    )

    def check_project(self, index: ProjectIndex) -> Iterator[Finding]:
        by_attr: dict[tuple[str, str], list[AttrAccess]] = {}
        for fn in index.functions.values():
            # Locks guaranteed held on entry count as held at every
            # access: `# Callers hold self._lock` helpers are guarded
            # by their call sites, not their own body.
            entry = index.entry_locks(fn.key)
            for acc in fn.accesses:
                if acc.in_init:
                    continue
                if entry:
                    acc = replace(acc, locks_held=acc.locks_held | entry)
                by_attr.setdefault((acc.group, acc.attr), []).append(acc)
        for (group, attr), accesses in sorted(by_attr.items()):
            declared = index.guarded_by.get((group, attr))
            lock = declared or self._infer_lock(accesses)
            if lock is None:
                continue
            roots: set[str] = set()
            for acc in accesses:
                roots |= index.roots_of(acc.func)
            if len(roots) < 2:
                continue
            lock_name = lock.rsplit(".", 1)[-1]
            how = "declared" if declared else "inferred"
            for acc in sorted(accesses, key=lambda a: (a.path, a.line)):
                if lock in acc.locks_held:
                    continue
                if not declared and not acc.write:
                    continue
                if not index.roots_of(acc.func):
                    continue
                kind = "write to" if acc.write else "read of"
                yield self.project_finding(
                    index, acc.path, acc.line,
                    f"lock-free {kind} `self.{attr}` ({how} guarded by "
                    f"`{lock_name}`, and reachable from threads: "
                    f"{', '.join(sorted(roots))}); hold `{lock_name}` "
                    "here or justify the lock-free access inline",
                    col=acc.col,
                )

    @staticmethod
    def _infer_lock(accesses: list[AttrAccess]) -> Optional[str]:
        locked: dict[str, int] = {}
        locked_writes: dict[str, int] = {}
        unlocked = 0
        for acc in accesses:
            if not acc.locks_held:
                unlocked += 1
            for lock in acc.locks_held:
                locked[lock] = locked.get(lock, 0) + 1
                if acc.write:
                    locked_writes[lock] = locked_writes.get(lock, 0) + 1
        best: Optional[str] = None
        for lock, n in sorted(locked.items()):
            if locked_writes.get(lock, 0) < 1:
                continue
            if n < 2 or n <= unlocked:
                continue
            if best is None or n > locked[best]:
                best = lock
        return best


def may_acquire_while_holding(
    index: ProjectIndex,
) -> dict[tuple[str, str], tuple[str, int, tuple[str, ...]]]:
    """The static may-acquire-while-holding edge set GL021 runs cycle
    detection over: ``(held, acquired) -> (path, line, chain)`` — one
    example site per ordered pair where ``acquired`` is taken (directly
    or transitively through the call graph) inside a ``with held:``
    region. Shared with ``/debug/lockgraph``, which diffs this model
    against the runtime graph ``lockcheck.order_graph()`` learned."""
    witness: dict[tuple[str, str], tuple[str, int, tuple[str, ...]]] = {}
    for fn in index.functions.values():
        for held_key, region in fn.regions:
            if held_key.startswith("?."):
                continue
            # nested acquisitions in the same function body
            for acq in fn.acquisitions:
                if acq.lock == held_key or acq.lock.startswith("?."):
                    continue
                if region.holds_at(acq.line) or (
                    region.lineno < acq.line <= region.end_lineno
                ):
                    witness.setdefault(
                        (held_key, acq.lock),
                        (acq.path, acq.line, (fn.name,)),
                    )
            # transitive acquisitions through calls made under the
            # *lexical* region — deliberately ignoring manual
            # release windows: a release-around seam still relies
            # on timing, and the finding's inline disable is where
            # that reliance gets documented.
            for call in fn.calls:
                if call.callee is None:
                    continue
                if not (
                    region.lineno < call.line <= region.end_lineno
                ):
                    continue
                for lock, chain in index.may_acquire(
                    call.callee
                ).items():
                    # lock == held_key stays IN: re-acquiring a
                    # plain Lock through a call chain is a self-
                    # deadlock (_cycle_findings exempts RLocks).
                    if lock.startswith("?."):
                        continue
                    witness.setdefault(
                        (held_key, lock),
                        (call.path, call.line, (fn.name,) + chain),
                    )
    return witness


class LockOrderInversionRule(_ConcurrencyRule):
    """Two locks acquired in opposite orders on two code paths deadlock
    the moment both paths run concurrently — the exact hazard PR 4
    dodged *manually* by releasing the engine submit lock around pool
    adoption. This rule builds the may-acquire-while-holding graph
    (nested ``with`` blocks plus transitive acquisitions through the
    call graph) and flags every edge that participates in a cycle,
    including a plain-Lock self-cycle (re-acquiring a non-reentrant
    lock through a call chain is a self-deadlock, not an inversion,
    but the fix is the same).

    Only locks the index resolved to a concrete owner participate —
    an unresolved ``obj._lock`` would conflate every class's ``_lock``
    into one node and invent cycles that cannot happen.
    """

    rule_id = "GL021"
    name = "lock-order-inversion"
    rationale = (
        "two locks taken in opposite orders on concurrent paths "
        "deadlock under the wrong interleaving; pick one global order "
        "or release the outer lock around the foreign acquisition"
    )

    def check_project(self, index: ProjectIndex) -> Iterator[Finding]:
        yield from self._cycle_findings(
            index, may_acquire_while_holding(index)
        )

    def _cycle_findings(
        self,
        index: ProjectIndex,
        witness: dict[tuple[str, str], tuple[str, int, tuple[str, ...]]],
    ) -> Iterator[Finding]:
        adj: dict[str, set[str]] = {}
        for left, right in witness:
            adj.setdefault(left, set()).add(right)
            adj.setdefault(right, set())
        sccs = _tarjan(adj)
        in_cycle: dict[str, int] = {}
        for i, scc in enumerate(sccs):
            if len(scc) > 1:
                for node in scc:
                    in_cycle[node] = i
        findings: list[Finding] = []
        for (left, right), (path, line, chain) in sorted(witness.items()):
            self_cycle = left == right or (
                (right, left) in witness and left != right
            )
            same_scc = (
                in_cycle.get(left) is not None
                and in_cycle.get(left) == in_cycle.get(right)
            )
            if not (same_scc or self_cycle):
                continue
            if left == right:
                kind = index.locks.get(left)
                if kind is not None and kind.kind == "RLock":
                    continue  # re-entrant by design
                msg = (
                    f"`{_lock_label(left)}` may be re-acquired while "
                    f"already held (via {' -> '.join(chain)}); a plain "
                    "Lock self-deadlocks here"
                )
            else:
                back = witness.get((right, left))
                where = (
                    f"; the reverse order is taken at {back[0]}:{back[1]}"
                    if back is not None else
                    " (reverse path closes the cycle elsewhere)"
                )
                msg = (
                    "lock-order inversion: acquires "
                    f"`{_lock_label(right)}` while holding "
                    f"`{_lock_label(left)}` (via {' -> '.join(chain)})"
                    f"{where}; pick one global order or release "
                    f"`{_lock_label(left)}` around the acquisition"
                )
            findings.append(
                self.project_finding(index, path, line, msg)
            )
        yield from findings


class BlockingUnderLockRule(_ConcurrencyRule):
    """A ``with <lock>:`` region that reaches a blocking primitive —
    ``block_until_ready``/``device_get`` (device sync), HTTP, ``sleep``,
    a blocking ``queue.get`` — stalls every thread contending for that
    lock for the primitive's full duration: a device sync under the
    submit lock turns one slow window into a serving-wide convoy. The
    per-file GL004-era checks only see the direct body; this rule
    follows the call graph, so a helper three frames down still trips
    it.

    Condition regions are exempt (waiting is their job), as is code
    inside a manual release window (the lock is not actually held
    there).
    """

    rule_id = "GL022"
    name = "blocking-under-lock"
    rationale = (
        "a blocking call while holding a lock convoys every thread "
        "contending for it; move the wait outside the critical section "
        "or justify the hold inline"
    )

    def check_project(self, index: ProjectIndex) -> Iterator[Finding]:
        for key in sorted(index.functions):
            fn = index.functions[key]
            for held_key, region in fn.regions:
                lock_def = index.locks.get(held_key)
                if lock_def is not None and lock_def.kind == "Condition":
                    continue
                label = _lock_label(held_key)
                for name, line, col in fn.blocking:
                    if region.holds_at(line):
                        yield self.project_finding(
                            index, fn.path, line,
                            f"blocking call `{name}` while holding "
                            f"`{label}`; every thread contending for "
                            "the lock stalls behind it",
                            col=col,
                        )
                for call in fn.calls:
                    if call.callee is None:
                        continue
                    if not region.holds_at(call.line):
                        continue
                    for name, chain in sorted(
                        index.may_block(call.callee).items()
                    ):
                        yield self.project_finding(
                            index, fn.path, call.line,
                            f"call chain {' -> '.join((fn.name,) + chain)} "
                            f"reaches blocking `{name}` while holding "
                            f"`{label}`; move the wait outside the "
                            "critical section",
                            col=call.col,
                        )


def _lock_label(key: str) -> str:
    """Human name for a lock key: ``Engine._submit_lock`` for instance
    locks, the bare name for module-level ones."""
    if ":" in key:
        return key.rsplit(":", 1)[-1]
    return key


def _tarjan(adj: dict[str, set[str]]) -> list[list[str]]:
    """Tarjan's strongly-connected components, iterative (lint inputs
    are untrusted; no recursion-limit surprises)."""
    index_of: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    sccs: list[list[str]] = []
    counter = 0
    for start in sorted(adj):
        if start in index_of:
            continue
        work: list[tuple[str, Optional[str], Iterator[str]]] = [
            (start, None, iter(sorted(adj[start])))
        ]
        while work:
            node, parent, children = work[-1]
            if node not in index_of:
                index_of[node] = low[node] = counter
                counter += 1
                stack.append(node)
                on_stack.add(node)
            advanced = False
            for child in children:
                if child not in index_of:
                    work.append((child, node, iter(sorted(adj[child]))))
                    advanced = True
                    break
                if child in on_stack:
                    low[node] = min(low[node], index_of[child])
            if advanced:
                continue
            if low[node] == index_of[node]:
                scc: list[str] = []
                while True:
                    top = stack.pop()
                    on_stack.discard(top)
                    scc.append(top)
                    if top == node:
                        break
                sccs.append(scc)
            work.pop()
            if parent is not None:
                low[parent] = min(low[parent], low[node])
    return sccs


ALL_RULES = ALL_RULES + (
    UnguardedSharedStateRule,
    LockOrderInversionRule,
    BlockingUnderLockRule,
)


def default_rules(config: Optional[LintConfig] = None) -> list[Rule]:
    config = config or LintConfig()
    return [
        HostDeviceSyncRule(config.hot_path_dirs),
        TracerBranchRule(),
        RecompilationHazardRule(),
        BlockingCallRule(config.hot_path_files),
        LockDisciplineRule(config.hot_path_files),
        ExceptionSwallowRule(config.request_path_dirs),
        DonatedBufferReuseRule(),
        ScanBodyAsarrayRule(),
        JitCacheGrowthRule(),
        RepeatedHostPullRule(),
        PerRowClockRule(config.hot_path_files),
        BlockingIONoTimeoutRule(),
        RetryNoBackoffRule(),
        CrossMeshHostPullRule(),
        JitInRequestPathRule(),
        UnboundedMetricLabelRule(),
        ThresholdNoHysteresisRule(),
        HostPullInDeviceLegRule(),
        SyncOutsideDeviceWaitRule(),
        AckBeforeResultRule(),
        HandleNoDeadlineRule(),
        UnguardedSharedStateRule(config.concurrency_dirs),
        LockOrderInversionRule(config.concurrency_dirs),
        BlockingUnderLockRule(config.concurrency_dirs),
    ]
