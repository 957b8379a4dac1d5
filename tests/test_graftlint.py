"""graftlint rule-by-rule suite: one positive and one negative fixture
per rule (GL001–GL015), suppression syntax, baseline round-trip/drift,
CLI exit codes, and the gate that keeps the committed baseline in sync
with the tree."""

import os
import subprocess
import sys
import textwrap

from gofr_tpu.analysis.cli import main
from gofr_tpu.analysis.core import Baseline, LintConfig, run_paths


def _lint(tmp_path, rel, source, select=None):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    config = LintConfig()
    if select:
        config.select = set(select)
    findings = run_paths([str(tmp_path)], config=config)
    return [f.rule_id for f in findings], findings


# ----------------------------------------------------------------------
# GL001 — host-device sync
# ----------------------------------------------------------------------


def test_gl001_flags_item_and_conversions_on_hot_path(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/hot.py",
        """
        import numpy as np

        def emit(tokens_dev, logps_dev):
            a = tokens_dev.item()
            b = float(logps_dev)
            c = np.asarray(tokens_dev)
            return a, b, c
        """,
        select=["GL001"],
    )
    assert ids == ["GL001", "GL001", "GL001"]
    assert "device" in findings[0].message


def test_gl001_ignores_cold_paths_and_host_values(tmp_path):
    ids, _ = _lint(
        tmp_path, "datasource/cold.py",
        """
        def emit(tokens_dev):
            return float(tokens_dev)
        """,
        select=["GL001"],
    )
    assert ids == []  # datasource/ is not a hot-path dir
    ids, _ = _lint(
        tmp_path, "serving/host.py",
        """
        def emit(count):
            return float(count)  # plain host value, no device naming
        """,
        select=["GL001"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL002 — tracer branch in jit
# ----------------------------------------------------------------------


def test_gl002_flags_python_branch_on_tracer(tmp_path):
    ids, findings = _lint(
        tmp_path, "mod.py",
        """
        import jax

        @jax.jit
        def relu_bad(x):
            if x > 0:
                return x
            return 0.0
        """,
        select=["GL002"],
    )
    assert ids == ["GL002"]
    assert "relu_bad" in findings[0].message


def test_gl002_allows_shape_static_and_identity_branches(tmp_path):
    ids, _ = _lint(
        tmp_path, "mod.py",
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("k",))
        def ok(x, k, mask=None):
            if x.shape[0] > 2:      # shapes are static under trace
                x = x + 1
            if mask is not None:    # identity checks are host-level
                x = x * mask
            if k > 1:               # declared static
                x = x * k
            return x
        """,
        select=["GL002"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL003 — recompilation hazards
# ----------------------------------------------------------------------


def test_gl003_flags_mutable_static_arg_and_shape_keys(tmp_path):
    ids, _ = _lint(
        tmp_path, "mod.py",
        """
        import jax

        def run(x, opts):
            return x

        jitted = jax.jit(run, static_argnums=(1,))
        compiled = {}

        def call(x):
            compiled[f"{x.shape}"] = 1
            return jitted(x, [1, 2])
        """,
        select=["GL003"],
    )
    assert ids == ["GL003", "GL003"]


def test_gl003_allows_hashable_static_args(tmp_path):
    ids, _ = _lint(
        tmp_path, "mod.py",
        """
        import jax

        def run(x, opts):
            return x

        jitted = jax.jit(run, static_argnums=(1,))

        def call(x):
            return jitted(x, (1, 2))
        """,
        select=["GL003"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL004 — blocking calls
# ----------------------------------------------------------------------


def test_gl004_flags_sleep_in_async_and_hot_path(tmp_path):
    ids, _ = _lint(
        tmp_path, "handlers.py",
        """
        import time

        async def handler(ctx):
            time.sleep(0.1)
        """,
        select=["GL004"],
    )
    assert ids == ["GL004"]
    _, findings = _lint(
        tmp_path, "serving/engine.py",
        """
        import time

        def drain(self):
            time.sleep(0.05)
        """,
        select=["GL004"],
    )
    hot = [f for f in findings if f.path.endswith("serving/engine.py")]
    assert [f.rule_id for f in hot] == ["GL004"]
    assert "hot path" in hot[0].message


def test_gl004_allows_async_sleep_and_cold_path_sleep(tmp_path):
    ids, _ = _lint(
        tmp_path, "handlers.py",
        """
        import asyncio
        import time

        async def handler(ctx):
            await asyncio.sleep(0.1)

        def retry_backoff():
            time.sleep(1.0)  # not async, not a hot-path file
        """,
        select=["GL004"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL005 — lock discipline
# ----------------------------------------------------------------------


def test_gl005_flags_unlocked_write_to_guarded_attr(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/engine.py",
        """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._draining = False

            def stop(self):
                with self._lock:
                    self._draining = True

            def restart(self):
                self._draining = False  # raced against stop()
        """,
        select=["GL005"],
    )
    assert ids == ["GL005"]
    assert "_draining" in findings[0].message


def test_gl005_sees_across_mixin_classes_and_sibling_files(tmp_path):
    # The serving core is ONE runtime object composed from mixins across
    # files: a lock taken in engine.py must guard the same attribute
    # written from scheduler.py (and from another class in the same file).
    (tmp_path / "serving").mkdir(parents=True)
    (tmp_path / "serving" / "engine.py").write_text(textwrap.dedent(
        """
        import threading

        class Engine:
            def stop(self):
                with self._submit_lock:
                    self._running = False

        class OtherMixin:
            def boot(self):
                self._running = True  # same object, no lock
        """
    ))
    (tmp_path / "serving" / "scheduler.py").write_text(textwrap.dedent(
        """
        class SchedulerMixin:
            def loop(self):
                self._running = False  # lock lives in engine.py
        """
    ))
    config = LintConfig()
    config.select = {"GL005"}
    findings = run_paths([str(tmp_path)], config=config)
    flagged = sorted(f.path.rsplit("/", 1)[-1] for f in findings)
    assert flagged == ["engine.py", "scheduler.py"]


def test_gl005_allows_consistent_locking(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/engine.py",
        """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._draining = False

            def stop(self):
                with self._lock:
                    self._draining = True

            def restart(self):
                with self._lock:
                    self._draining = False
        """,
        select=["GL005"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL006 — swallowed exceptions
# ----------------------------------------------------------------------


def test_gl006_flags_broad_silent_except(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/routes.py",
        """
        def handle(req):
            try:
                return req.run()
            except Exception:
                pass
        """,
        select=["GL006"],
    )
    assert ids == ["GL006"]


def test_gl006_allows_narrow_or_handled_excepts(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/routes.py",
        """
        def handle(req, log):
            try:
                return req.run()
            except ValueError:
                pass                      # narrow: fine
            except Exception as exc:
                log.errorf("failed: %s", exc)   # handled: fine
                return None

        def fallback(req):
            try:
                return req.fast_path()
            except Exception:
                return req.slow_path()    # fallback work: fine
        """,
        select=["GL006"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL007 — donated-buffer reuse after donate_argnums
# ----------------------------------------------------------------------


def test_gl007_flags_read_after_donation(tmp_path):
    ids, findings = _lint(
        tmp_path, "mod.py",
        """
        import jax

        step = jax.jit(run, donate_argnums=(0,))

        def bad(cache, tokens):
            out = step(cache, tokens)
            return out, cache.lengths  # donated buffer read back
        """,
        select=["GL007"],
    )
    assert ids == ["GL007"]
    assert "donate" in findings[0].message


def test_gl007_flags_immediately_invoked_jit_donation(tmp_path):
    ids, _ = _lint(
        tmp_path, "mod.py",
        """
        import jax

        def bad(params, quantize):
            quantized = jax.jit(quantize, donate_argnums=(0,))(params)
            total = sum(params.values())  # params' buffers are gone
            return quantized, total
        """,
        select=["GL007"],
    )
    assert ids == ["GL007"]


def test_gl007_allows_rebinding_and_reassignment(tmp_path):
    ids, _ = _lint(
        tmp_path, "mod.py",
        """
        import jax

        step = jax.jit(run, donate_argnums=(0,))

        def good_rebind(cache, tokens):
            cache = step(cache, tokens)   # idiomatic: result rebinds
            return cache.lengths

        def good_attr(self, tokens):
            self.cache = step(self.cache, tokens)
            return self.cache

        def good_reassign(cache, tokens, fresh):
            out = step(cache, tokens)
            cache = fresh()               # new binding clears the taint
            return out, cache

        def good_no_donation(cache, tokens, plain):
            out = plain(cache, tokens)    # not a donating wrapper
            return out, cache
        """,
        select=["GL007"],
    )
    assert ids == []


def test_gl007_scopes_do_not_leak(tmp_path):
    # A donation inside one function must not taint another function's
    # use of the same variable name; args evaluated as part of the
    # donating call itself are pre-donation reads.
    ids, _ = _lint(
        tmp_path, "mod.py",
        """
        import jax

        step = jax.jit(run, donate_argnums=(0,))

        def donates(cache):
            return step(cache, cache.lengths)  # arg reads: pre-donation

        def unrelated(cache):
            return cache.lengths
        """,
        select=["GL007"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL008 — jnp.asarray / jnp.array inside lax.scan bodies
# ----------------------------------------------------------------------


def test_gl008_flags_asarray_in_scan_bodies(tmp_path):
    ids, findings = _lint(
        tmp_path, "models/layers.py",
        """
        import jax
        import jax.numpy as jnp

        def forward(x, params, table):
            def body(carry, layer):
                bias = jnp.asarray(table)       # baked per body trace
                return carry + layer + bias, None

            x, _ = jax.lax.scan(body, x, params)
            y, _ = jax.lax.scan(
                lambda c, l: (c + jnp.array([1.0]), None), x, params
            )
            return x + y
        """,
        select=["GL008"],
    )
    assert ids == ["GL008", "GL008"]
    assert "lax.scan" in findings[0].message
    assert "hoist" in findings[0].message


def test_gl008_ignores_conversions_outside_bodies(tmp_path):
    ids, _ = _lint(
        tmp_path, "models/layers.py",
        """
        import jax
        import jax.numpy as jnp

        def forward(x, params, table):
            bias = jnp.asarray(table)           # hoisted: fine
            def body(carry, layer):
                return carry + layer + bias, None

            x, _ = jax.lax.scan(body, x, params)
            return x

        def unrelated(table):
            # Not a scan body at all.
            return jnp.array(table)

        def factory_scan(x, params, make_body):
            # Factory-built bodies are statically out of reach — the
            # rule must stay quiet rather than guess.
            x, _ = jax.lax.scan(make_body(1), x, params)
            return x
        """,
        select=["GL008"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL009 — per-request jit-cache growth
# ----------------------------------------------------------------------


def test_gl009_flags_shape_keyed_lru_cache_and_dict_cached_jit(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/progs.py",
        """
        import functools
        from functools import lru_cache

        import jax

        class Engine:
            @lru_cache(maxsize=128)
            def _program(self, seq_len):
                # Method + per-request key: one executable per observed
                # prompt length, and the cache pins self forever.
                return jax.jit(lambda x: x * seq_len)

            def warm(self, prompt_len):
                self._cache[prompt_len] = jax.jit(lambda x: x)
                self._cache.setdefault(prompt_len, jax.jit(lambda x: x))

        @functools.cache
        def build_step(n_tokens):
            # Unbounded decorator around a jit builder.
            return jax.jit(lambda x: x[:n_tokens])
        """,
        select=["GL009"],
    )
    assert ids == ["GL009", "GL009", "GL009", "GL009"]
    assert "padding bucket" in findings[0].message


def test_gl009_ignores_bounded_bucketed_caches(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/progs.py",
        """
        from functools import lru_cache

        import jax

        @lru_cache(maxsize=8)
        def program_for_bucket(bucket):
            # Module-level, bounded, keyed on a CLOSED bucket set — the
            # fix the rule recommends.
            return jax.jit(lambda x: x + bucket)

        @lru_cache
        def expensive_lookup(seq_len):
            # Shape-ish key but no jit built: not a compile cache.
            return seq_len * 2

        PROGS = {}

        def warm(bucket):
            PROGS[bucket] = jax.jit(lambda x: x)  # bucket id key: fine
        """,
        select=["GL009"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL010 — repeated host pull of the same device value in a loop
# ----------------------------------------------------------------------


def test_gl010_flags_repeated_pull_of_same_value_in_loop(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/emit.py",
        """
        import jax
        import numpy as np

        def emit(rows, first_dev, lp_dev):
            out = []
            for row in rows:
                tok = int(np.asarray(first_dev)[row])
                lp = float(np.asarray(first_dev)[row])
                out.append((tok, lp))
            return out

        def fetch(rows, planes_dev):
            while rows:
                row = rows.pop()
                a = jax.device_get(planes_dev)[row]
                b = jax.device_get(planes_dev)[row + 1]
        """,
        select=["GL010"],
    )
    assert ids == ["GL010", "GL010"]
    assert "hoist one host copy" in findings[0].message


def test_gl010_ignores_hoisted_rebound_and_closure_pulls(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/emit.py",
        """
        import numpy as np

        def emit(rows, first_dev):
            first = np.asarray(first_dev)  # hoisted: the fix
            return [int(first[row]) for row in rows]

        def drain(inflight):
            while inflight:
                emitted = inflight.popleft()[0]
                a = np.asarray(emitted)  # rebound per iteration
                b = np.asarray(emitted)  # same iteration's value: fine
                del a, b

        def lazy(rows, x_dev):
            for row in rows:
                # Closure bodies are not per-iteration work of THIS loop.
                pull = lambda: np.asarray(x_dev) + np.asarray(x_dev)
            return pull

        def upload(rows, table):
            import jax.numpy as jnp
            for row in rows:
                a = jnp.asarray(table)  # host->device: GL008's business
                b = jnp.asarray(table)

        class Drainer:
            def drain(self):
                while self.queue:
                    a = np.asarray(self.emitted)
                    self.emitted = self.fetch()  # attribute rebound:
                    b = np.asarray(self.emitted)  # a different array
        """,
        select=["GL010"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL011 — per-row clock reads in scheduler emit/decode loops
# ----------------------------------------------------------------------


def test_gl011_flags_clock_in_per_row_loop_on_hot_path(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/scheduler.py",
        """
        import time

        def process_window(snapshot):
            for seq in snapshot:
                now = time.time()  # per-row stamp: k*S syscalls/window
                seq.ttft = now - seq.enqueued_at

        def flush(entries):
            for entry in entries:
                entry.first_at = time.monotonic()
        """,
        select=["GL011"],
    )
    assert ids == ["GL011", "GL011"]
    assert "once per window" in findings[0].message


def test_gl011_ignores_hoisted_while_polls_cold_paths_and_closures(tmp_path):
    # Hoisted stamps, while-loop deadline polls, and nested closures are
    # all fine on the hot path.
    ids, _ = _lint(
        tmp_path, "serving/scheduler.py",
        """
        import time

        def process_window(snapshot):
            now = time.time()  # hoisted: the fix
            for seq in snapshot:
                seq.ttft = now - seq.enqueued_at

        def drain(deadline_s):
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:  # poll: condition IS time
                pass

        def fetch(emitted, entries):
            for entry in entries:
                while not emitted.is_ready():  # readiness poll in a for
                    t = time.monotonic()
                entry.mark = 1

        def lazy(rows):
            for row in rows:
                stamp = lambda: time.time()  # not run by this loop
            return stamp
        """,
        select=["GL011"],
    )
    assert ids == []
    # Same per-row stamping OFF the hot path: not this rule's business.
    ids, _ = _lint(
        tmp_path, "datasource/poll.py",
        """
        import time

        def poll(rows):
            for row in rows:
                row.at = time.time()
        """,
        select=["GL011"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL012 — blocking network I/O without an explicit timeout
# ----------------------------------------------------------------------


def test_gl012_flags_timeoutless_clients_in_serving_and_service(tmp_path):
    ids, findings = _lint(
        tmp_path, "service/wire.py",
        """
        import httpx
        import requests
        import socket
        import urllib.request

        def build():
            return httpx.Client()  # inherits someone else's default

        def fetch(url):
            return requests.get(url)  # requests default: NO timeout

        def open_raw(url):
            return urllib.request.urlopen(url)

        def connect(addr):
            return socket.create_connection(addr)
        """,
        select=["GL012"],
    )
    assert ids == ["GL012", "GL012", "GL012", "GL012"]
    assert "timeout" in findings[0].message


def test_gl012_accepts_budgeted_calls_and_other_tiers(tmp_path):
    # Explicit budgets (kwarg or positional) are the fix; client METHOD
    # calls inherit their constructor's budget; other tiers are out of
    # scope for this rule.
    ids, _ = _lint(
        tmp_path, "serving/wire.py",
        """
        import httpx
        import requests
        import socket
        import urllib.request

        def build(read_s, connect_s):
            return httpx.Client(
                timeout=httpx.Timeout(read_s, connect=connect_s)
            )

        def fetch(client, url):
            return client.get(url)  # budget set at construction

        def fetch2(url):
            return requests.get(url, timeout=10)

        def open_raw(url):
            return urllib.request.urlopen(url, None, 10)

        def connect(addr):
            return socket.create_connection(addr, 5)
        """,
        select=["GL012"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "datasource/wire.py",
        """
        import requests

        def fetch(url):
            return requests.get(url)
        """,
        select=["GL012"],
    )
    assert ids == []  # datasource clients carry their own conventions


# ----------------------------------------------------------------------
# GL013 — retry loops without backoff
# ----------------------------------------------------------------------


def test_gl013_flags_backoffless_retry_loops(tmp_path):
    ids, findings = _lint(
        tmp_path, "service/retry.py",
        """
        def fetch(svc, url, max_retries):
            for attempt in range(max_retries + 1):
                try:
                    return svc.get(url)
                except ConnectionError:
                    continue  # immediate re-attempt: herd amplifier

        def push(svc, body, budget):
            retries_left = budget
            while retries_left > 0:
                try:
                    return svc.post("v1/x", json=body)
                except ConnectionError:
                    retries_left -= 1
        """,
        select=["GL013"],
    )
    assert ids == ["GL013", "GL013"]
    assert "backoff" in findings[0].message


def test_gl013_accepts_backoff_and_plain_loops(tmp_path):
    # Jittered sleeps, RetryConfig, re-raising handlers, and loops that
    # are not retry loops at all are the negative space.
    ids, _ = _lint(
        tmp_path, "serving/retry_ok.py",
        """
        import time

        def fetch(svc, url, cfg):
            for attempt in range(cfg.max_retries + 1):
                try:
                    return svc.get(url)
                except ConnectionError:
                    time.sleep(cfg.delay_s(attempt))

        def ship(self, req, payload):
            for attempt in range(self.transfer_retries + 1):
                try:
                    return self._import(req, payload)
                except ConnectionError:
                    pass
                self._sleep(self._transfer_delay(attempt))

        def strict(svc, url, max_retries):
            for attempt in range(max_retries):
                try:
                    return svc.get(url)
                except ConnectionError:
                    raise  # not a retry: failures propagate

        def walk(replicas):
            for replica in replicas:  # adoption walk, not a retry loop
                try:
                    if replica.adopt():
                        return True
                except ValueError:
                    continue
            return False
        """,
        select=["GL013"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "datasource/retry.py",
        """
        def fetch(svc, url, max_retries):
            for attempt in range(max_retries):
                try:
                    return svc.get(url)
                except ConnectionError:
                    continue
        """,
        select=["GL013"],
    )
    assert ids == []  # out of the serving/service scope


# ----------------------------------------------------------------------
# GL014 — cross-mesh host pulls / sharding-annotation drift
# ----------------------------------------------------------------------


def test_gl014_flags_cache_pulls_and_bare_device_put(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/scheduler.py",
        """
        import jax
        import numpy as np

        def _flush(self):
            planes = jax.device_get(self.cache.k)  # all-gathers the pool
            rows = np.asarray(self.cache.lengths)
            return planes, rows

        def _upload(self, table):
            return jax.device_put(table)  # no placement: drift
        """,
        select=["GL014"],
    )
    assert ids == ["GL014", "GL014", "GL014"]
    assert "export seam" in findings[0].message
    assert "NamedSharding" in findings[2].message


def test_gl014_accepts_export_seam_placed_puts_and_cold_files(tmp_path):
    # The export seam, device-side jnp.asarray, placed device_puts, and
    # non-cache pulls are the negative space.
    ids, _ = _lint(
        tmp_path, "serving/engine.py",
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def export_blocks_for(self, ids):
            # the deliberate host bounce: export-named seam
            return np.asarray(jax.device_get(self.cache.k[:, ids]))

        def _up(self, x, rep):
            return jax.device_put(x, rep)  # placed: fine

        def _emit(self, tokens_dev):
            return np.asarray(tokens_dev)  # not a cache plane (GL001's job)

        def _trace(self, cache):
            return jnp.asarray(cache.lengths)  # stays on device
        """,
        select=["GL014"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "serving/hf_loader.py",
        """
        import jax

        def to_device(x):
            return jax.device_put(x)  # boot path, out of scope
        """,
        select=["GL014"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL015 — jax.jit created inside a per-request function body
# ----------------------------------------------------------------------


def test_gl015_flags_jit_built_in_request_path(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/pipeline.py",
        """
        import jax
        from functools import partial

        def handle_generate(self, tokens):
            step = jax.jit(lambda t: t + 1)  # fresh program per request
            return step(tokens)

        def _decode_once(self, params, x):
            fn = partial(jax.jit, donate_argnums=(0,))(self._fwd)
            return fn(params, x)
        """,
        select=["GL015"],
    )
    assert ids == ["GL015", "GL015"]
    assert "per-request" in findings[0].message


def test_gl015_exempts_module_scope_builders_and_boot(tmp_path):
    # Module scope, _build_*/*_program builders (exemption inherited by
    # their nested defs), __init__/_init* boot paths, and the loader
    # files are the negative space; calling an already-built program in
    # a request path is of course fine.
    ids, _ = _lint(
        tmp_path, "serving/steps.py",
        """
        import jax
        from functools import partial

        shared_step = jax.jit(lambda t: t + 1)  # module scope

        class EngineBits:
            def __init__(self):
                self._cache_init = jax.jit(self._make_cache)

            def _init_serving_state(self):
                self._pool = jax.jit(self._make_pool)()

            def _build_steps(self):
                @partial(jax.jit, donate_argnums=(1,))
                def decode(params, cache):
                    return params, cache

                self._decode = decode

            def sampling_program(self):
                return jax.jit(self._sample)

            def handle(self, tokens):
                return self._decode(tokens)  # CALLING a program: fine
        """,
        select=["GL015"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "serving/hf_loader.py",
        """
        import jax

        def load_leaf(x):
            return jax.jit(lambda v: v)(x)  # loader module, out of scope
        """,
        select=["GL015"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "ops/kernels.py",
        """
        import jax

        def helper(x):
            return jax.jit(lambda v: v)(x)  # outside serving/
        """,
        select=["GL015"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL016 — request-controlled strings as metric label values
# ----------------------------------------------------------------------


def test_gl016_flags_request_controlled_label_values(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/handlers.py",
        """
        def account(self, req, ctx):
            self._metrics.increment_counter(
                "app_requests_total", "tenant", req.tenant
            )
            self._metrics.add_counter(
                "app_tokens_total", 5, "model", self.model_name,
                "tenant", tenant_id,
            )
            self._metrics.set_gauge(
                "app_queue", 1.0, "who", ctx.headers["x-tenant-id"]
            )
            REQUESTS.labels(tenant=req.tenant).inc()
        """,
        select=["GL016"],
    )
    assert ids == ["GL016", "GL016", "GL016", "GL016"]
    assert "cardinality" in findings[0].message


def test_gl016_accepts_clamped_and_engine_owned_labels(tmp_path):
    # A clamp-helper call (label_for/*_label) bounds the value by
    # construction; engine-owned values (model names, reason literals)
    # never taint; key POSITIONS named "tenant" are fine — only the
    # VALUE matters; and metric calls outside serving//service/ are out
    # of scope.
    ids, _ = _lint(
        tmp_path, "serving/handlers.py",
        """
        def account(self, req, ledger):
            self._metrics.increment_counter(
                "app_requests_total",
                "tenant", ledger.label_for(req.tenant),
            )
            self._metrics.add_counter(
                "app_tokens_total", 5,
                "tenant", clamp_label(req.tenant),
            )
            self._metrics.increment_counter(
                "app_requests_shed_total",
                "model", self.model_name, "reason", "tenant_quota",
            )
        """,
        select=["GL016"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "metrics/export.py",
        """
        def account(m, req):
            m.increment_counter("app_requests_total", "tenant", req.tenant)
        """,
        select=["GL016"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL017 — control-loop threshold comparisons without hysteresis
# ----------------------------------------------------------------------


def test_gl017_flags_threshold_state_flip_without_hysteresis(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/controller.py",
        """
        class Controller:
            def tick(self):
                if self.burn_rate > self.enter_threshold:
                    self.level = 1  # flips on one noisy tick
                if self.pool_headroom() < self.headroom_floor:
                    self.degraded = True
        """,
        select=["GL017"],
    )
    assert ids == ["GL017", "GL017"]
    assert "sustain" in findings[0].message


def test_gl017_accepts_sustain_windows_and_shed_decisions(tmp_path):
    # A sustain anchor (the *_since idiom) or any hysteresis/budget
    # guard evidence in the function exempts it; shedding/raising in
    # the branch is a per-request decision, not controller state; and
    # files outside serving//service/ are out of scope.
    ids, _ = _lint(
        tmp_path, "serving/controller.py",
        """
        class Controller:
            def tick(self, now):
                if self.burn_rate > self.enter_threshold:
                    if self._over_since is None:
                        self._over_since = now
                    elif now - self._over_since >= self.sustain_s:
                        self.level += 1
        """,
        select=["GL017"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "serving/admission.py",
        """
        class Admission:
            def check(self, req):
                if self.pool_headroom() < self.admit_floor:
                    self._shed("hbm_headroom")
                    raise TooManyRequests("retry elsewhere")
        """,
        select=["GL017"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "ops/controller.py",
        """
        class Controller:
            def tick(self):
                if self.burn_rate > self.enter_threshold:
                    self.level = 1  # outside serving//service/
        """,
        select=["GL017"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL018 — host pull inside the device transfer leg
# ----------------------------------------------------------------------


def test_gl018_flags_host_pulls_in_device_leg_functions(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/scheduler.py",
        """
        import jax
        import numpy as np

        def _export_payload_device_leg(self, block_ids):
            planes = [
                np.asarray(self.cache.k[:, b]) for b in block_ids
            ]  # the bounce the leg exists to remove
            return planes

        def paged_move_block(cache, dst, k_blk):
            host = jax.device_get(k_blk)  # never on the device leg
            return cache
        """,
        select=["GL018"],
    )
    assert ids == ["GL018", "GL018"]
    assert "device" in findings[0].message


def test_gl018_accepts_device_resident_legs_and_export_seam(tmp_path):
    # Jitted extraction, explicit sharding-aware device_put, non-plane
    # host reads, and the documented export* host bounce are the
    # negative space; device-leg-ness inherits into nested helpers.
    ids, _ = _lint(
        tmp_path, "serving/scheduler.py",
        """
        import jax
        import numpy as np

        def _write_block_device_leg(self, bid, payload, j):
            k_blk = jax.device_put(
                payload.k_blocks[j], self._block_sharding
            )  # shard-to-shard, stays on device
            return self._paged_move_block(
                self.cache, self._up(np.int32(bid)), k_blk
            )

        def export_blocks(cache, ids):
            # the deliberate host bounce: export-named seam (GL014)
            return np.asarray(jax.device_get(cache.k[:, ids]))

        def _transfer_stats_device_leg(self):
            return np.asarray(self._timings)  # host data, not a plane
        """,
        select=["GL018"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "serving/scheduler.py",
        """
        import numpy as np

        def _import_device_leg(self, payload):
            def helper(j):
                return np.asarray(payload.k_blocks[j])  # inherited leg
            return [helper(j) for j in range(payload.n_blocks)]
        """,
        select=["GL018"],
    )
    assert ids == ["GL018"]


# ----------------------------------------------------------------------
# GL019 — device sync outside the designated device-window seam
# ----------------------------------------------------------------------


def test_gl019_flags_syncs_in_loop_phase_functions(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/scheduler.py",
        """
        import jax

        def _reap_lifecycle(self):
            jax.block_until_ready(self.cache.lengths)  # hidden wait

        def _ledger_tick(self):
            n = self._nsteps_dev.item()  # device pull in a host phase
            return n

        def _dispatch_prefill_chunk(self):
            lp = float(self._logps_dev)  # sync outside the seam
            return lp
        """,
        select=["GL019"],
    )
    assert ids == ["GL019", "GL019", "GL019"]
    assert "device-window seam" in findings[0].message


def test_gl019_accepts_seam_waits_and_host_reads(tmp_path):
    # The designated seam (incl. nested helpers), float()/.item() of
    # already-pulled host arrays (call results), and non-device values
    # are the negative space; inline disables document deliberate
    # barriers (the lockstep idiom).
    ids, _ = _lint(
        tmp_path, "serving/scheduler.py",
        """
        import jax
        import numpy as np

        def _process_window(self, emitted):
            jax.block_until_ready(emitted)  # THE device-wait seam

            def helper(arr):
                return float(arr_dev)  # seam-ness inherits
            return helper(emitted)

        def _dispatch_window(self):
            self._jax.block_until_ready(self._tokens_dev)  # lockstep seam

        def _flush_prefill_emits(self, pull, lp_dev, row):
            lp = float(pull(lp_dev)[row])  # pulled host copy, not a sync
            return lp

        def _retire(self, req):
            return float(req.ttft_s)  # host value, not a device plane

        def _dispatch_prefill_chunk(self):
            if self._lockstep:
                self._jax.block_until_ready(self.cache.lengths)  # graftlint: disable=GL019 — deliberate lockstep barrier
        """,
        select=["GL019"],
    )
    assert ids == []
    # Out-of-scope file: the rule is scheduler-loop specific.
    ids, _ = _lint(
        tmp_path, "serving/engine.py",
        """
        import jax

        def warm_up(self):
            jax.block_until_ready(self._tokens_dev)
        """,
        select=["GL019"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL023 — ack before the result publish / terminal seam
# ----------------------------------------------------------------------


def test_gl023_flags_ack_before_result_seam(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/consumer.py",
        """
        def handle(self, msg):
            self._sub.ack(msg.id)  # broker forgets the message here
            reply = self._run(msg)
            self.broker.publish("tpu.replies", reply)

        def park(self, msg, exc):
            self._sub.ack(msg.id)  # crash here and the DLQ entry is lost
            self._dead_letter(msg, exc)

        def resolve(self, msg, result):
            self.sub.ack(msg.id)
            msg.future.set_result(result)
        """,
        select=["GL023"],
    )
    assert ids == ["GL023", "GL023", "GL023"]
    assert "at-least-once" in findings[0].message


def test_gl023_accepts_publish_then_ack_and_ack_only(tmp_path):
    # Publish-first-ack-last is the contract; an ack with no later seam
    # (the dedup replay path, where the reply already went out) is the
    # negative space; nested defs are separate bodies; out-of-scope
    # files are untouched; deliberate at-most-once carries a disable.
    ids, _ = _lint(
        tmp_path, "pubsub/consumer.py",
        """
        def handle(self, msg):
            reply = self._run(msg)
            self.broker.publish("tpu.replies", reply)
            self._sub.ack(msg.id)  # reply is durable; safe to forget

        def replay(self, msg):
            if msg.id in self._ledger:
                self._sub.ack(msg.id)  # reply already published

        def outer(self, msg):
            self._sub.ack(msg.id)
            def emit(r):
                self.broker.publish("tpu.replies", r)
            return emit

        def at_most_once(self, msg):
            self._sub.ack(msg.id)  # graftlint: disable=GL023 — metrics tick, loss-tolerant by contract
            self.broker.publish("tpu.metrics", msg.value)
        """,
        select=["GL023"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "datasource/consumer.py",
        """
        def handle(self, msg):
            self._sub.ack(msg.id)
            self.broker.publish("tpu.replies", msg.value)
        """,
        select=["GL023"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL024 — transfer-handle acquisition without a budget
# ----------------------------------------------------------------------


def test_gl024_flags_budgetless_handle_acquisition(tmp_path):
    ids, findings = _lint(
        tmp_path, "service/puller.py",
        """
        def pull(self, handle):
            return dma_fetch(handle)  # blocks on the exporter forever

        def ask(self, source, ids):
            return source.fetch_prefilled(ids)

        def export(self, engine, ids):
            return engine.export_cached(ids)
        """,
        select=["GL024"],
    )
    assert ids == ["GL024", "GL024", "GL024"]
    assert "deadline" in findings[0].message


def test_gl024_accepts_budgeted_and_out_of_scope(tmp_path):
    # A deadline=/timeout_s= kwarg (or a **kwargs splat that may carry
    # one) states the budget; files outside serving//service/ are not
    # transfer-plane code; deliberate unbounded waits carry a disable.
    ids, _ = _lint(
        tmp_path, "service/puller.py",
        """
        def pull(self, handle, deadline):
            return dma_fetch(handle, deadline=deadline)

        def ask(self, source, ids, budget):
            return source.fetch_prefilled(
                ids, deadline=budget, timeout_s=2.0
            )

        def export(self, engine, ids, **kw):
            return engine.export_cached(ids, **kw)

        def forever(self, handle):
            return dma_fetch(handle)  # graftlint: disable=GL024 — test harness, budget owned by the pytest timeout
        """,
        select=["GL024"],
    )
    assert ids == []
    ids, _ = _lint(
        tmp_path, "datasource/puller.py",
        """
        def pull(self, handle):
            return dma_fetch(handle)
        """,
        select=["GL024"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# suppressions
# ----------------------------------------------------------------------


def test_inline_suppression_silences_one_rule(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/routes.py",
        """
        def handle(req):
            try:
                return req.run()
            except Exception:  # graftlint: disable=GL006 — probe endpoint
                pass
        """,
        select=["GL006"],
    )
    assert ids == []


def test_disable_next_line_and_unrelated_rule_still_fires(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/hot.py",
        """
        def emit(tokens_dev):
            # graftlint: disable-next-line=GL001
            a = float(tokens_dev)
            b = float(tokens_dev)  # graftlint: disable=GL004 (wrong rule)
            return a, b
        """,
        select=["GL001"],
    )
    assert ids == ["GL001"]  # only the wrongly-suppressed line fires


# ----------------------------------------------------------------------
# baseline
# ----------------------------------------------------------------------

_BASELINE_SRC = """
def handle(req):
    try:
        return req.run()
    except Exception:
        pass
"""


def test_baseline_roundtrip_and_line_shift_stability(tmp_path):
    _, findings = _lint(tmp_path, "serving/routes.py", _BASELINE_SRC)
    baseline = Baseline.from_findings(findings)
    new, stale = baseline.apply(findings)
    assert new == [] and stale == []
    # Insert lines above: fingerprints key on content, not line numbers.
    shifted = "# a comment\n# another\n" + textwrap.dedent(_BASELINE_SRC)
    (tmp_path / "serving/routes.py").write_text(shifted)
    _, findings2 = _lint(tmp_path, "serving/routes.py", shifted)
    new, stale = baseline.apply(findings2)
    assert new == [] and stale == []


def test_baseline_drift_detection(tmp_path):
    _, findings = _lint(tmp_path, "serving/routes.py", _BASELINE_SRC)
    baseline = Baseline.from_findings(findings)
    # The debt is paid off: the baseline entry must be reported stale.
    new, stale = baseline.apply([])
    assert new == [] and len(stale) == 1


def test_cli_exit_codes_and_check_baseline(tmp_path, capsys, monkeypatch):
    monkeypatch.chdir(tmp_path)
    target = tmp_path / "serving" / "routes.py"
    target.parent.mkdir(parents=True)
    target.write_text(textwrap.dedent(_BASELINE_SRC))
    # New findings, no baseline yet -> 1.
    assert main([str(tmp_path)]) == 1
    assert "GL006" in capsys.readouterr().out
    # Accept as baseline -> 0, then a clean re-run -> 0.
    assert main([str(tmp_path), "--write-baseline"]) == 0
    assert main([str(tmp_path)]) == 0
    assert main([str(tmp_path), "--check-baseline"]) == 0
    # Pay off the debt: plain run stays 0, --check-baseline demands a
    # baseline refresh (exit 1) so stale entries can't mask regressions.
    target.write_text("def handle(req):\n    return req.run()\n")
    assert main([str(tmp_path)]) == 0
    assert main([str(tmp_path), "--check-baseline"]) == 1
    assert "no longer occur" in capsys.readouterr().err
    assert main([str(tmp_path), "--write-baseline"]) == 0
    assert main([str(tmp_path), "--check-baseline"]) == 0


def test_pyproject_fallback_parses_multiline_lists(tmp_path):
    # The 3.10 fallback parser must handle values spanning lines — the
    # repo's own hot-path-files list does.
    from gofr_tpu.analysis.core import load_pyproject_config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_pyproject_config(os.path.join(repo, "pyproject.toml"))
    assert cfg.get("hot-path-files") == [
        "serving/batcher.py", "serving/scheduler.py", "serving/engine.py",
    ]
    assert cfg.get("request-path-dirs") == ["serving", "ops", "grpc"]


def test_pyproject_fallback_recovers_from_non_literal_values(tmp_path):
    # TOML booleans parse, and a value the fallback cannot parse must not
    # wedge the scan and swallow every following key.
    from gofr_tpu.analysis.core import load_pyproject_config

    pp = tmp_path / "pyproject.toml"
    pp.write_text(textwrap.dedent(
        """
        [tool.graftlint]
        flag = true
        weird = 1979-05-27T07:32:00Z
        exclude = [
            "a.py",
            "b.py",
        ]
        """
    ))
    cfg = load_pyproject_config(str(pp))
    assert cfg.get("exclude") == ["a.py", "b.py"]
    # tomllib parses `flag` natively; the 3.10 fallback maps true->True.
    assert cfg.get("flag") is True


def test_baseline_is_cwd_independent(tmp_path, monkeypatch):
    proj = tmp_path / "proj"
    (proj / "serving").mkdir(parents=True)
    (proj / "pyproject.toml").write_text("")  # marks the repo root
    (proj / "serving" / "routes.py").write_text(textwrap.dedent(_BASELINE_SRC))
    monkeypatch.chdir(proj)
    assert main([str(proj), "--write-baseline"]) == 0
    # Same tree, analyzed from a different CWD: fingerprints must match.
    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    monkeypatch.chdir(elsewhere)
    assert main([str(proj), "--check-baseline"]) == 0


def test_scoped_select_does_not_rot_the_baseline(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    target = tmp_path / "serving" / "routes.py"
    target.parent.mkdir(parents=True)
    target.write_text(textwrap.dedent(_BASELINE_SRC))  # one GL006 finding
    assert main([str(tmp_path), "--write-baseline"]) == 0
    # A GL001-only run produces no GL006 findings; that absence is NOT
    # paid-off debt, and a scoped rewrite must keep the GL006 entry.
    assert main([str(tmp_path), "--select", "GL001", "--check-baseline"]) == 0
    assert main([str(tmp_path), "--select", "GL001", "--write-baseline"]) == 0
    assert main([str(tmp_path), "--check-baseline"]) == 0


def test_cli_list_rules_and_missing_path(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in (
        "GL001", "GL002", "GL003", "GL004", "GL005", "GL006", "GL007",
        "GL008", "GL009", "GL010", "GL011", "GL012", "GL013", "GL014",
    ):
        assert rule_id in out
    assert main(["/nonexistent/path"]) == 2


def test_module_entrypoint_runs():
    proc = subprocess.run(
        [sys.executable, "-m", "gofr_tpu.analysis", "--list-rules"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    assert "GL001" in proc.stdout


# ----------------------------------------------------------------------
# the repo gate: committed baseline stays in sync with the tree
# ----------------------------------------------------------------------


def test_repo_clean_against_committed_baseline(monkeypatch, capsys):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.chdir(repo)
    rc = main(["gofr_tpu", "--check-baseline"])
    captured = capsys.readouterr()
    assert rc == 0, (
        "graftlint gate failed — new findings or baseline drift:\n"
        + captured.out + captured.err
    )


# ----------------------------------------------------------------------
# the project index (GL020–GL022's shared substrate)
# ----------------------------------------------------------------------


def _index(tmp_path, files):
    """Build a ProjectIndex from {relpath: source} the way run_paths
    does — via core._load_file, so suppressions/paths match production."""
    from gofr_tpu.analysis.core import _load_file
    from gofr_tpu.analysis.project import ProjectIndex

    loaded = []
    for rel, source in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(source))
        got = _load_file(str(p), root=str(tmp_path))
        assert isinstance(got, tuple), f"parse failed for {rel}: {got}"
        loaded.append(got)
    return ProjectIndex.build(loaded)


def test_project_index_groups_mixins_into_one_runtime_object(tmp_path):
    index = _index(tmp_path, {
        "serving/engine.py": """
            class SchedulerMixin:
                def loop(self):
                    pass

            class Engine(SchedulerMixin):
                def submit(self):
                    self.loop()
        """,
    })
    # One composition group; self.loop() resolves into it.
    (leader,) = [g for g, members in index.groups.items()
                 if {"Engine", "SchedulerMixin"} <= members]
    submit = index.functions["serving/engine.py::Engine.submit"]
    assert submit.group == leader
    callees = [c.callee for c in submit.calls]
    assert "serving/engine.py::SchedulerMixin.loop" in callees


def test_project_index_call_edges_and_import_shadowing(tmp_path):
    index = _index(tmp_path, {
        "serving/a.py": """
            import os

            def helper():
                pass

            class Widget:
                def exists(self):
                    pass

                def run(self):
                    helper()            # module-level function
                    os.path.exists("x")  # library call — NOT Widget.exists
        """,
    })
    run = index.functions["serving/a.py::Widget.run"]
    resolved = {c.name: c.callee for c in run.calls}
    assert resolved["helper"] == "serving/a.py::helper"
    # `os` is an imported name: the unique-method fallback must not
    # resolve os.path.exists to Widget.exists.
    assert resolved.get("os.path.exists") is None


def test_project_index_lock_regions_subtract_release_windows(tmp_path):
    from gofr_tpu.analysis.project import lock_regions

    index = _index(tmp_path, {
        "serving/b.py": """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()

                def flip(self):
                    with self._lock:
                        a = 1
                        self._lock.release()
                        b = 2   # NOT held here
                        self._lock.acquire()
                        c = 3
        """,
    })
    ctx = index.files["serving/b.py"]
    tree = __import__("ast").parse(ctx.source)
    fn = tree.body[1].body[1]  # Box.flip
    (region,) = lock_regions(fn)
    held = {line: region.holds_at(line) for line in range(10, 15)}
    assert held[10] and held[14]         # a = 1, c = 3
    assert not held[12]                  # b = 2 — inside the window


def test_project_index_thread_roots_and_reachability(tmp_path):
    index = _index(tmp_path, {
        "serving/c.py": """
            import threading

            class Prober:
                def start(self):
                    threading.Thread(target=self._probe).start()
                    t = threading.Thread(None, self._watch)
                    t.start()

                def _probe(self):
                    self._tick()

                def _watch(self):
                    pass

                def _tick(self):
                    pass
        """,
    })
    assert "serving/c.py::Prober._probe" in index.thread_roots
    assert "serving/c.py::Prober._watch" in index.thread_roots
    # _tick runs on the probe thread (and on no caller thread: only
    # start() is public, and it never calls _tick directly).
    roots = index.roots_of("serving/c.py::Prober._tick")
    assert roots == frozenset({"_probe"})  # probe thread only, no caller


def test_project_index_entry_locks_meet_over_call_sites(tmp_path):
    index = _index(tmp_path, {
        "serving/d.py": """
            import threading

            class Ledger:
                def __init__(self):
                    self._lock = threading.Lock()

                def tick(self):
                    with self._lock:
                        self._step()

                def flush(self):
                    with self._lock:
                        self._step()

                def _step(self):
                    pass

                def _orphan(self):
                    pass
        """,
    })
    # Every call site holds _lock -> the helper inherits it on entry.
    entry = index.entry_locks("serving/d.py::Ledger._step")
    assert any(k.endswith("._lock") for k in entry)
    # A never-called private helper gets no guarantee.
    assert index.entry_locks("serving/d.py::Ledger._orphan") == frozenset()


# ----------------------------------------------------------------------
# GL020 — unguarded shared state
# ----------------------------------------------------------------------


def test_gl020_flags_lock_free_write_with_inferred_guard(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/pool.py",
        """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0

            def add(self):
                with self._lock:
                    self._count += 1

            def remove(self):
                with self._lock:
                    self._count -= 1

            def reset(self):
                self._count = 0  # lock-free, raced by the drain thread

            def start(self):
                threading.Thread(target=self._drain).start()

            def _drain(self):
                self.remove()
        """,
        select=["GL020"],
    )
    assert ids == ["GL020"]
    assert "_count" in findings[0].message
    assert "inferred" in findings[0].message


def test_gl020_declared_guard_flags_reads_too(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/gauge.py",
        """
        import threading

        class Gauge:
            def __init__(self):
                self._lock = threading.Lock()
                self._value = 0  # graftlint: guarded-by=_lock

            def bump(self):
                with self._lock:
                    self._value += 1

            def peek(self):
                return self._value  # declared guard: reads count

            def start(self):
                threading.Thread(target=self.bump).start()
        """,
        select=["GL020"],
    )
    assert ids == ["GL020"]
    assert "read" in findings[0].message
    assert "declared" in findings[0].message


def test_gl020_quiet_on_consistent_locking_and_single_thread(tmp_path):
    # Consistent locking: clean.
    ids, _ = _lint(
        tmp_path, "serving/ok.py",
        """
        import threading

        class Ok:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def a(self):
                with self._lock:
                    self._n += 1

            def b(self):
                with self._lock:
                    self._n -= 1

            def start(self):
                threading.Thread(target=self.a).start()
        """,
        select=["GL020"],
    )
    assert ids == []
    # No second thread root: a lock-free write is single-threaded
    # discipline, not a race — stay quiet.
    ids, _ = _lint(
        tmp_path, "serving/solo.py",
        """
        import threading

        class Solo:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def a(self):
                with self._lock:
                    self._n += 1

            def b(self):
                with self._lock:
                    self._n -= 1

            def reset(self):
                self._n = 0
        """,
        select=["GL020"],
    )
    assert ids == []


def test_gl020_helper_called_under_lock_is_not_flagged(tmp_path):
    # The `# Callers hold self._lock` idiom: every call site of _step
    # holds the lock, so its write is covered by entry_locks.
    ids, _ = _lint(
        tmp_path, "serving/brown.py",
        """
        import threading

        class Brownout:
            def __init__(self):
                self._lock = threading.Lock()
                self._factor = 1.0

            def tighten(self):
                with self._lock:
                    self._step(-0.1)

            def relax(self):
                with self._lock:
                    self._step(0.1)

            def _step(self, delta):
                self._factor += delta

            def start(self):
                threading.Thread(target=self.tighten).start()
        """,
        select=["GL020"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL021 — lock-order inversion
# ----------------------------------------------------------------------


def test_gl021_flags_pool_engine_inversion(tmp_path):
    # The pre-PR-4 shape: the submit path holds the engine's submit
    # lock while reserving in the pool (engine -> pool), while the
    # scaler's drain path holds the pool lock while cancelling in the
    # engine (pool -> engine). Two threads, opposite order: deadlock
    # under the wrong interleaving.
    ids, findings = _lint(
        tmp_path, "serving/pair.py",
        """
        import threading

        class Engine:
            def __init__(self, pool):
                self._submit_lock = threading.Lock()
                self._pool = pool

            def submit(self):
                with self._submit_lock:
                    self._pool.reserve()

            def cancel_all(self):
                with self._submit_lock:
                    pass

        class Pool:
            def __init__(self, engine):
                self._lock = threading.Lock()
                self._engine = engine

            def reserve(self):
                with self._lock:
                    pass

            def scale_down(self):
                with self._lock:
                    self._engine.cancel_all()
        """,
        select=["GL021"],
    )
    assert ids and set(ids) == {"GL021"}
    joined = " ".join(f.message for f in findings)
    assert "_submit_lock" in joined and "_lock" in joined


def test_gl021_quiet_on_consistent_order_and_rlock_reentry(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/ordered.py",
        """
        import threading

        class Ordered:
            def __init__(self):
                self._outer = threading.Lock()
                self._inner = threading.Lock()
                self._re = threading.RLock()

            def a(self):
                with self._outer:
                    with self._inner:
                        pass

            def b(self):
                with self._outer:
                    self._help()

            def _help(self):
                with self._inner:
                    pass

            def reenter(self):
                with self._re:
                    self._again()

            def _again(self):
                with self._re:
                    pass
        """,
        select=["GL021"],
    )
    assert ids == []


def test_gl021_flags_blocking_self_reacquisition_of_plain_lock(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/selfhang.py",
        """
        import threading

        class SelfHang:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self._inner()

            def _inner(self):
                with self._lock:
                    pass
        """,
        select=["GL021"],
    )
    assert ids == ["GL021"]
    assert "deadlock" in findings[0].message.lower()


# ----------------------------------------------------------------------
# GL022 — blocking call under a lock
# ----------------------------------------------------------------------


def test_gl022_flags_direct_and_transitive_blocking_under_lock(tmp_path):
    ids, findings = _lint(
        tmp_path, "serving/blocky.py",
        """
        import threading
        import time
        import urllib.request

        class Blocky:
            def __init__(self):
                self._lock = threading.Lock()

            def direct(self):
                with self._lock:
                    time.sleep(0.5)

            def transitive(self):
                with self._lock:
                    self._fetch()

            def _fetch(self):
                urllib.request.urlopen("http://upstream")
        """,
        select=["GL022"],
    )
    assert ids == ["GL022", "GL022"]
    assert "time.sleep" in findings[0].message
    assert "_fetch" in findings[1].message or "urlopen" in findings[1].message


def test_gl022_quiet_on_conditions_nonblocking_and_release_windows(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/fine.py",
        """
        import queue
        import threading
        import time

        class Fine:
            def __init__(self):
                self._cond = threading.Condition()
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def waiter(self):
                # Conditions exist to sleep while held: exempt.
                with self._cond:
                    self._cond.wait(timeout=1.0)

            def poll(self):
                with self._lock:
                    item = self._q.get(block=False)
                return item

            def around(self):
                with self._lock:
                    self._lock.release()
                    time.sleep(0.1)  # lock NOT held here
                    self._lock.acquire()
        """,
        select=["GL022"],
    )
    assert ids == []


def test_gl022_counters_named_queued_are_not_queues(tmp_path):
    ids, _ = _lint(
        tmp_path, "serving/counter.py",
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self._tenant_queued = {}

            def depth(self, tenant):
                with self._lock:
                    return self._tenant_queued.get(tenant, 0)
        """,
        select=["GL022"],
    )
    assert ids == []


# ----------------------------------------------------------------------
# GL005 regression — writes in release-around windows
# ----------------------------------------------------------------------


def test_gl005_flags_write_inside_release_window(tmp_path):
    # PR 4's release-around shape: the lexical with-block no longer
    # means "held" once the body releases — a write between release()
    # and re-acquire() is a lock-free write (the old span-based check
    # missed these).
    ids, findings = _lint(
        tmp_path, "serving/engine.py",  # GL005 scopes to hot-path files
        """
        import threading

        class Window:
            def __init__(self):
                self._lock = threading.Lock()
                self._state = "idle"

            def run(self):
                with self._lock:
                    self._state = "running"

            def handoff(self):
                with self._lock:
                    self._lock.release()
                    self._state = "detached"  # lock NOT held
                    self._lock.acquire()
        """,
        select=["GL005"],
    )
    assert ids == ["GL005"]
    assert findings[0].line == 16
    assert "_state" in findings[0].message


# ----------------------------------------------------------------------
# SARIF output
# ----------------------------------------------------------------------


def test_cli_sarif_format_and_exit_semantics(tmp_path, capsys, monkeypatch):
    import json as jsonlib

    bad = tmp_path / "serving" / "hot.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent(
        """
        def emit(tokens_dev):
            return tokens_dev.item()
        """
    ))
    (tmp_path / "pyproject.toml").write_text("")
    monkeypatch.chdir(tmp_path)
    rc = main(["serving", "--format=sarif", "--no-baseline", "--select=GL001"])
    out = capsys.readouterr().out
    assert rc == 1  # findings still fail the run — format is reporting only
    log = jsonlib.loads(out)
    assert log["version"] == "2.1.0"
    (run,) = log["runs"]
    assert run["tool"]["driver"]["name"] == "graftlint"
    (result,) = run["results"]
    assert result["ruleId"] == "GL001"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "serving/hot.py"
    assert loc["region"]["startLine"] == 3
    # Clean tree -> SARIF with zero results, exit 0.
    good = tmp_path / "serving" / "cold.py"
    good.write_text("x = 1\n")
    rc = main(
        ["serving/cold.py", "--format=sarif", "--no-baseline", "--select=GL001"]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert jsonlib.loads(out)["runs"][0]["results"] == []
