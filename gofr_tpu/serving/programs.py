"""Device-program builders + profiling for the LLM serving engine.

``_build_llm_steps`` builds the two jitted serving programs, the
prefill chunk step and the decode window (the entire device-side
serving dataplane). Mixin methods on InferenceEngine — split from
``engine.py`` along its build seam (r4 VERDICT weak #10)."""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import numpy as np


def prefill_rungs(prefill_batch: int) -> tuple[int, ...]:
    """The row counts the prefill step is compiled at: 1 and
    ``prefill_batch``. Every compiled row is computed in full, so a lone
    row in an 8-row step is seven eighths padding, and a lone row is
    what waits at most steps of an open loop below its knee and of a
    closed loop whose clients finish one at a time. Not the powers of
    two between: every rung is traced and lowered at every boot,
    compile cache or not (1.3-1.5 s of host Python a rung for mistral-7b
    on the v5e's host, PERF.md PR 29), and 2 to 4 rows waited at one
    step in seven where this was measured."""
    return (1, prefill_batch) if prefill_batch > 1 else (1,)


class LLMProgramsMixin:
    """Jitted-program construction."""

    # -- the mixin contract (mypy strict scope) ------------------------
    # Provided by InferenceEngine.__init__ / _init_llm_serving_state;
    # declared so the strict type gate checks this module's own logic
    # against a written-down contract (the SchedulerMixin idiom).
    _jax: Any
    _jnp: Any
    cfg: Any
    mesh: Any
    tokenizer: Any
    cache: Any
    params: Any
    quant: str
    family: str
    _running: bool
    _seed: int
    _top_k: int
    enable_top_p: bool
    enable_penalties: bool
    top_logprobs: int
    n_slots: int
    window_k: int
    prefill_batch: int
    prefill_chunk: int
    prefill_rungs: tuple[int, ...]
    moe_products: dict[tuple[str, int], str]
    decode_read_rungs: tuple[int, ...]
    max_len: int
    kv_block: int
    _slot_state_dirty: bool
    _up: Any  # host→device placement callable
    _compiles: Any  # serving.device_telemetry.CompileTracker
    # Device-resident slot planes (jax arrays).
    _tokens_dev: Any
    _logps_dev: Any
    _nsteps_dev: Any
    _seeds_dev: Any
    _noff_dev: Any
    _aids_dev: Any
    _pcounts_dev: Any
    _fpen_dev: Any
    _ppen_dev: Any
    _bidx_dev: Any
    _bval_dev: Any
    _topi_dev: Any
    _topl_dev: Any
    # Compiled-program callables (built below, compile-tracked).
    _prefill_chunk_step: Any
    _decode_window: Any

    def _build_llm_steps(self) -> None:
        jax, jnp = self._jax, self._jnp
        from gofr_tpu.models.transformer import (
            transformer_decode_step,
            transformer_prefill_chunk,
        )
        from gofr_tpu.ops.attention import decode_read_plan
        cfg, top_k = self.cfg, self._top_k
        # pallas kernels don't auto-partition under GSPMD: mesh-sharded
        # serving takes the dense attention formulations, which XLA
        # partitions (per-head locality under tp; sharded-softmax
        # collectives under cp).
        dense_attn = self.mesh is not None
        # The decode step's dense attention reads the rung of the cache
        # that holds the longest live slot. Where the position axis is
        # sharded (context parallel) a prefix lives on the first chips
        # only, so that cache keeps the whole read.
        bound_read = self.mesh is None or "cp" not in self.mesh.axis_names
        self.decode_read_rungs = decode_read_plan(
            self.max_len, paged=bool(self.kv_block),
            window=cfg.sliding_window, kernel=False if dense_attn else None,
            latent=cfg.is_latent or cfg.is_hybrid,
        ) if bound_read else (self.max_len,)
        # An expert layer that may hold a share of the experts counts its
        # routes; the steps return the counts beside their tokens (no
        # program of another model changes). A stacked expert layer picks
        # its product at each traced step from the step's rows
        # (``cfg.expert_product``); a prefill step that ran grouped returns
        # its expert load the same way.
        count_routes = cfg.counts_routes
        sharded = self.mesh is not None
        # A hybrid stack's steps return what its sparse layers did the same
        # way: a prefill step each row's queries past the dense length, a
        # decode step two planes a slot (positions attended through the
        # choice, context).
        hybrid = cfg.is_hybrid

        def moe_product(rows: int) -> Optional[str]:
            """app_tpu_moe_product_steps_total's ``product`` of a step of
            ``rows`` token rows: "einsum", or "grouped" (tiles of a stacked
            layer, ``ragged_dot`` over a set of leaves); None: no experts."""
            if not cfg.is_moe:
                return None
            product = cfg.expert_product(rows, sharded)
            return "einsum" if product == "einsum" else "grouped"

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            _rep_sh = NamedSharding(self.mesh, PartitionSpec())

            def rep(x: Any) -> Any:
                # Host-fetched outputs must be REPLICATED: on a multi-host
                # (DCN) mesh every process np.asarray()s its local shard,
                # which is only the full value if the sharding says so.
                return jax.lax.with_sharding_constraint(x, _rep_sh)
        else:
            def rep(x: Any) -> Any:
                return x

        enable_top_p = self.enable_top_p
        enable_penalties = self.enable_penalties
        top_lp_k = self.top_logprobs

        @jax.named_scope("sample")
        def sample(
            logits: Any, keys: Any, temps: Any, greedy: Any,
            topps: Any, pen: Optional[tuple] = None,
            bias: Optional[tuple] = None,
        ) -> tuple:
            """Returns (token, logprob) — the logprob is the log-softmax at
            the chosen token of the distribution the choice was made from
            (the model's own when no penalties apply), the number the
            OpenAI logprobs field reports.

            pen: optional (counts [rows, V] int32, fpen [rows], ppen
            [rows]) — OpenAI-style frequency/presence penalties over the
            GENERATED tokens (prompt tokens don't count, the vLLM
            convention), applied before greedy argmax AND sampling so
            temperature-0 requests honor them too."""
            logits = logits.astype(jnp.float32)
            if bias is not None:
                # OpenAI logit_bias: sparse per-request (token, bias)
                # pairs, padded with idx -1. Applied to the raw logits —
                # before penalties, greedy argmax, and sampling.
                bidx, bval = bias
                rows = jnp.arange(logits.shape[0])[:, None]
                logits = logits.at[rows, jnp.clip(bidx, 0)].add(
                    jnp.where(bidx >= 0, bval, 0.0)
                )
            if pen is not None:
                counts, fpen, ppen = pen
                cf = counts.astype(jnp.float32)
                logits = (
                    logits
                    - fpen[:, None] * cf
                    - ppen[:, None] * (cf > 0).astype(jnp.float32)
                )
            greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            scaled = logits / jnp.maximum(temps, 1e-4)[:, None]
            sorted_l = None
            if top_k > 0:
                sorted_l = jnp.sort(scaled, axis=-1)[:, ::-1]
                kth = sorted_l[:, top_k - 1][:, None]
                scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
            if enable_top_p:
                # Per-slot nucleus: keep the smallest prefix of the
                # sorted distribution with cumulative prob >= top_p
                # (slots at top_p=1.0 are untouched).
                if sorted_l is not None:
                    # Post-top_k sorted logits are the already-sorted
                    # list with positions >= top_k masked — no second
                    # vocab-wide sort on the decode hot path.
                    V = sorted_l.shape[-1]
                    sorted_p = jnp.where(
                        jnp.arange(V)[None, :] < top_k, sorted_l, -jnp.inf
                    )
                else:
                    sorted_p = jnp.sort(scaled, axis=-1)[:, ::-1]
                cum = jnp.cumsum(jax.nn.softmax(sorted_p, axis=-1), axis=-1)
                # Guarantee the predicate holds somewhere: fp32 cumsum
                # over a big vocab can top out just below a top_p≈1,
                # and argmax over all-False would return 0 — silently
                # collapsing the request to greedy.
                cum = cum.at[:, -1].set(2.0)
                cut_idx = jnp.argmax(cum >= topps[:, None], axis=-1)
                cutoff = jnp.take_along_axis(
                    sorted_p, cut_idx[:, None], axis=-1
                )
                scaled = jnp.where(
                    (topps < 1.0)[:, None] & (scaled < cutoff),
                    -jnp.inf, scaled,
                )
            sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(
                jnp.int32
            )
            chosen = jnp.where(greedy, greedy_tok, sampled)
            logp_all = jax.nn.log_softmax(logits, axis=-1)
            logp = jnp.take_along_axis(logp_all, chosen[:, None], axis=-1)[:, 0]
            if top_lp_k:
                # OpenAI top_logprobs alternatives, from the same
                # (biased/penalized) distribution the choice used.
                tl, ti = jax.lax.top_k(logp_all, top_lp_k)
                return chosen, logp, ti.astype(jnp.int32), tl
            return chosen, logp, None, None

        # Per-request reproducible sampling: each sampled token's key is
        # fold_in(fold_in(engine_base, request_seed), n_sampled_so_far) —
        # counter-based, so a seeded stream is identical regardless of
        # batch composition, window size, or pipeline depth.
        base_key = jax.random.PRNGKey(self._seed + 2)

        def row_keys(seeds: Any, nsteps: Any) -> Any:
            def one(sd: Any, n: Any) -> Any:
                return jax.random.fold_in(
                    jax.random.fold_in(base_key, sd), n
                )

            return jax.vmap(one)(seeds, nsteps)

        @partial(
            jax.jit, donate_argnums=(1, 12, 13, 14, 15, 18, 19),
            static_argnames=("use_bias",),
        )
        def prefill_chunk_step(
            params: Any, cache: Any, tokens: Any, slots: Any, starts: Any,
            lens: Any, finalize: Any, row_valid: Any, temps: Any,
            greedy: Any, topps: Any, seeds: Any, all_tokens: Any,
            all_logps: Any, pcounts: Any, nsteps: Any, bidx: Any,
            bval: Any, topi: Any, topl: Any, aids: Any, noff: Any,
            use_bias: bool = False,
        ) -> tuple:
            """One [rows, c] chunk (``jit_prefill_chunk_step`` in the
            profiler's trace): write K/V + attend; on rows whose prompt
            finishes (finalize) sample the first token and merge it into
            the decode token vector ON DEVICE. Padding rows duplicate row 0:
            where every row is multiplied their K/V writes are row 0's own
            (idempotent); in a step whose stacked expert layers ran in tiles
            a padding row's tokens are not multiplied, so it writes nothing
            (its writes go past ``max_len`` and are dropped,
            ``transformer_prefill_chunk``). Either way only the
            ``row_valid``-masked merge below keeps it out of the slots (a
            per-slot select, not a scatter, so duplicates can't race).
            pcounts: per-slot generated-token counts (penalties feature) —
            finalize RESETS the slot's row (new request) and counts the
            first sampled token; the first token itself is never penalized
            (its counts are the zeros just written)."""
            # A step whose expert layers ran grouped also returns its route
            # counts; they ride back as [rows + 1] float32 beside the first
            # tokens: each row's routes that landed on held experts (over
            # its valid tokens and the layers), then the step's expert load
            # ratio.
            grouped = moe_product(tokens.shape[0] * tokens.shape[1]) == "grouped"
            logits, cache, *counts = transformer_prefill_chunk(
                params, tokens, cache, slots, starts, lens, cfg,
                dense_attn=dense_attn, aids=aids[slots],
                row_valid=row_valid if grouped or hybrid else None,
                stats=grouped or hybrid, sharded=sharded,
            )
            moe = None
            if grouped:
                ((held, load_ratio),) = counts
                moe = rep(jnp.concatenate([held, load_ratio[None]]))
            elif hybrid:  # [rows]: each row's queries past the dense length
                moe = rep(counts[0].astype(jnp.float32))
            # Sample at the slot's counter OFFSET (noff): 0 for fresh
            # admissions, the delivered-token count for replayed requests
            # — so a non-greedy stream carried across a restart continues
            # on the same counter-based sample path (seeded-sampling
            # replay continuity).
            sub = row_keys(seeds[slots], noff[slots])
            first, first_lp, ftopi, ftopl = sample(
                logits, sub, temps, greedy, topps,
                bias=(bidx[slots], bval[slots]) if use_bias else None,
            )
            S = all_tokens.shape[0]
            match = (
                (jnp.arange(S)[:, None] == slots[None, :])
                & finalize[None, :] & row_valid[None, :]
            )  # [S, P]
            has = jnp.any(match, axis=1)
            idx = jnp.argmax(match, axis=1)
            all_tokens = jnp.where(has, first[idx], all_tokens)
            all_logps = jnp.where(has, first_lp[idx], all_logps)
            cache = cache._replace(
                lengths=jnp.where(has, (starts + lens)[idx], cache.lengths)
            )
            if enable_penalties:
                pcounts = jnp.where(has[:, None], 0, pcounts)
                pcounts = pcounts.at[
                    jnp.arange(S), all_tokens
                ].add(has.astype(jnp.int32))
            # The finalize token was sampled with n=noff; the slot's next
            # sample uses n=noff+1 (fresh requests: 0 then 1).
            nsteps = jnp.where(has, noff + 1, nsteps)
            if top_lp_k:
                topi = jnp.where(has[:, None], ftopi[idx], topi)
                topl = jnp.where(has[:, None], ftopl[idx], topl)
                return (cache, all_tokens, all_logps, rep(first),
                        rep(first_lp), pcounts, nsteps, topi, topl,
                        rep(ftopi), rep(ftopl), moe)
            return (cache, all_tokens, all_logps, rep(first), rep(first_lp),
                    pcounts, nsteps, topi, topl, None, None, moe)

        @partial(
            jax.jit, static_argnames=("k", "use_bias"),
            donate_argnums=(3, 5, 11, 15, 16),
        )
        def decode_window(
            params: Any, tokens: Any, logps: Any, cache: Any, active: Any,
            nsteps: Any, temps: Any, greedy: Any, topps: Any, fpen: Any,
            ppen: Any, pcounts: Any, seeds: Any, bidx: Any, bval: Any,
            topi: Any, topl: Any, aids: Any, k: int, use_bias: bool,
        ) -> tuple:
            """Run k decode steps entirely on device; emit the k
            (token, logprob) pairs that ENTER each step (so a freshly
            prefilled slot's first token is emitted by its first window)
            and carry the (k+1)-th as next input. One host fetch per k
            tokens — emitted tokens and logprobs pack into ONE [2, k, S]
            f32 block (token ids are exact in f32 below 2^24) so the
            host↔device roundtrip count stays one per window. Sampling
            keys are counter-based — nsteps threads through ON DEVICE and
            the seeds plane uploads only on admission — so steady-state
            dispatch uploads nothing host→device at all."""

            def body(carry: tuple, _: Any) -> tuple:
                """One decode step: forward + sample + penalty count
                scatter."""
                tokens, logps, cache, nsteps, pcounts, topi, topl = carry
                logits, cache, *held = transformer_decode_step(
                    params, tokens, cache, active, cfg,
                    dense_attn=dense_attn, aids=aids, bound_read=bound_read,
                    stats=count_routes or hybrid, sharded=sharded,
                )
                if hybrid:  # (attended, context): two planes of the block
                    held = list(held[0])
                pen = (pcounts, fpen, ppen) if enable_penalties else None
                sub = row_keys(seeds, nsteps)
                nxt, nlp, ntopi, ntopl = sample(
                    logits, sub, temps, greedy, topps, pen,
                    bias=(bidx, bval) if use_bias else None,
                )
                nsteps = nsteps + active.astype(jnp.int32)
                if enable_penalties:
                    pcounts = pcounts.at[
                        jnp.arange(nxt.shape[0]), nxt
                    ].add(active.astype(jnp.int32))
                # Alternatives travel WITH their token: the carried planes
                # belong to the token entering this step (ys), the fresh
                # ones to the token just chosen (next carry).
                ys = (tokens, logps, topi, topl) if top_lp_k else (
                    tokens, logps
                )
                if not top_lp_k:
                    ntopi, ntopl = topi, topl
                # ``held``: this step's routes on held experts, a slot
                # (a third plane of the emitted block, where counted).
                return (nxt, nlp, cache, nsteps, pcounts, ntopi, ntopl), (
                    ys, *held
                )

            (final, final_lp, cache, nsteps, pcounts, topi, topl), (
                ys, *held
            ) = jax.lax.scan(
                body,
                (tokens, logps, cache, nsteps, pcounts, topi, topl),
                length=k,
            )
            if top_lp_k:
                etoks, elps, etopi, etopl = ys
                etops = rep(jnp.stack([etopi.astype(jnp.float32), etopl]))
            else:
                etoks, elps = ys
                etops = None
            emitted = jnp.stack([etoks.astype(jnp.float32), elps, *held])
            return (rep(emitted), etops, final, final_lp, cache, nsteps,
                    pcounts, topi, topl)

        # Compile tracking (serving/device_telemetry.py): every serving
        # program is wrapped so each XLA cache growth counts under its
        # program name — and a compile after the warm-up fence bumps
        # the steady-state recompile counter, the dynamic twin of
        # graftlint GL015's static jit-in-request-path check.
        wrap = self._compiles.wrap
        self._prefill_chunk_step = wrap("prefill_chunk", prefill_chunk_step)
        self._decode_window = wrap("decode_window", decode_window)
        self.prefill_rungs = prefill_rungs(self.prefill_batch)
        # The expert product each program runs, by (program, its rows or
        # slots), for app_tpu_moe_product_steps_total: the rule the traced
        # steps applied.
        self.moe_products = {
            ("decode_window", self.n_slots): moe_product(self.n_slots),
            **{("prefill_chunk", rows): moe_product(rows * self.prefill_chunk)
               for rows in self.prefill_rungs},
        } if cfg.is_moe else {}
        self._compile_prefill_ladder()

    def _compile_prefill_ladder(self) -> None:
        """Compile the prefill step at every rung before the engine
        serves: a rung that compiled at its first use would stall every
        stream, and which rungs a warm-up's traffic draws is chance."""
        self._prefill_steps: dict[tuple[int, bool], Any] = {}
        for rows in self.prefill_rungs:
            self._prefill_step(rows, False)

    def _prefill_operands(self, *per_row: np.ndarray) -> tuple[Any, ...]:
        """The prefill step's operands in the program's order: weights
        and cache, the nine per-row host arrays (tokens, slots, starts,
        lens, finalize, row_valid, temps, greedy, topps) uploaded, the
        device planes."""
        return (
            self.params, self.cache, *map(self._up, per_row),
            self._seeds_dev, self._tokens_dev, self._logps_dev,
            self._pcounts_dev, self._nsteps_dev, self._bidx_dev,
            self._bval_dev, self._topi_dev, self._topl_dev,
            self._aids_dev, self._noff_dev,
        )

    def _prefill_step(self, rows: int, use_bias: bool) -> Any:
        """The compiled ``[rows, prefill_chunk]`` prefill step, called with
        :meth:`_prefill_operands`. Compiled from the operands' shapes
        and placements, nothing runs; the logit-bias variant of a rung
        compiles when a request first brings a bias, as it always has."""
        step = self._prefill_steps.get((rows, use_bias))
        if step is not None:
            return step

        def row(dtype: Any) -> np.ndarray:
            return np.zeros((rows,), dtype=dtype)

        operands = self._prefill_operands(
            np.zeros((rows, self.prefill_chunk), dtype=np.int32),
            row(np.int32), row(np.int32), row(np.int32), row(bool),
            row(bool), row(np.float32), row(bool), row(np.float32),
        )
        step = self._compiles.compile_ahead(
            "prefill_chunk",
            lambda: self._prefill_chunk_step.__wrapped__.lower(
                *operands, use_bias=use_bias
            ).compile(),
        )
        self._prefill_steps[(rows, use_bias)] = step
        return step

    def param_bytes(self) -> int:
        from gofr_tpu.ops.quant import quantized_bytes

        return quantized_bytes(self.params)

