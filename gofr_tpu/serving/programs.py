"""Device-program builders + profiling for the LLM serving engine.

``_build_llm_steps`` compiles the jitted prefill/decode/spec/mega
programs (the entire device-side serving dataplane); profile_decode
measures them. Mixin methods on InferenceEngine — split from
``engine.py`` along its build/profile seams (r4 VERDICT weak #10)."""

from __future__ import annotations

import time
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
    """Jitted-program construction + device profiling."""

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
    spec_tokens: int
    n_slots: int
    window_k: int
    prefill_batch: int
    prefill_chunk: int
    prefill_rungs: tuple[int, ...]
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
    _history_dev: Any
    # Compiled-program callables (built below, compile-tracked).
    _prefill_chunk_step: Any
    _prefill_chunk_step_hist: Any
    _prefill_multi_chunk: Any
    _prefill_multi_chunk_hist: Any
    _decode_window: Any
    _mega_window: Any
    _spec_window: Any
    _mega_spec_window: Any

    def _build_llm_steps(self) -> None:
        jax, jnp = self._jax, self._jnp
        from gofr_tpu.models.transformer import (
            transformer_decode_step,
            transformer_prefill_chunk,
        )
        cfg, top_k = self.cfg, self._top_k
        # pallas kernels don't auto-partition under GSPMD: mesh-sharded
        # serving takes the dense attention formulations, which XLA
        # partitions (per-head locality under tp; sharded-softmax
        # collectives under cp).
        dense_attn = self.mesh is not None

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
        # batch composition, window size, or mega/pipelined scheduling.
        base_key = jax.random.PRNGKey(self._seed + 2)

        def row_keys(seeds: Any, nsteps: Any) -> Any:
            def one(sd: Any, n: Any) -> Any:
                return jax.random.fold_in(
                    jax.random.fold_in(base_key, sd), n
                )

            return jax.vmap(one)(seeds, nsteps)

        def _prefill_core(
            params: Any, cache: Any, tokens: Any, slots: Any, starts: Any,
            lens: Any, finalize: Any, row_valid: Any, temps: Any,
            greedy: Any, topps: Any, seeds: Any, all_tokens: Any,
            all_logps: Any, pcounts: Any, nsteps: Any, bidx: Any,
            bval: Any, topi: Any, topl: Any, aids: Any, noff: Any,
            use_bias: bool,
        ) -> tuple:
            """One [P, c] chunk: write K/V + attend; on rows whose prompt
            finishes (finalize) sample the first token and merge it into
            the decode token vector ON DEVICE. Padding rows duplicate row 0
            (identical K/V writes are idempotent; the merge below is
            per-slot select, not scatter, so duplicates can't race).
            pcounts: per-slot generated-token counts (penalties feature) —
            finalize RESETS the slot's row (new request) and counts the
            first sampled token; the first token itself is never penalized
            (its counts are the zeros just written)."""
            logits, cache = transformer_prefill_chunk(
                params, tokens, cache, slots, starts, lens, cfg,
                dense_attn=dense_attn, aids=aids[slots],
            )
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
                        rep(ftopi), rep(ftopl))
            return (cache, all_tokens, all_logps, rep(first), rep(first_lp),
                    pcounts, nsteps, topi, topl, None, None)

        @partial(
            jax.jit, donate_argnums=(1, 12, 13, 14, 15, 18, 19),
            static_argnames=("use_bias",),
        )
        def prefill_chunk_step(*operands: Any, use_bias: bool = False) -> tuple:
            """The plain prefill step (speculation off), a function of
            its own so that the profiler's trace names its module
            ``jit_prefill_chunk_step`` as it names the ``_hist`` one."""
            return _prefill_core(*operands, use_bias)

        def _multi_chunk_core(
            params: Any, cache: Any, tokens3: Any, slots: Any,
            starts0: Any, n_chunks: Any, history: Any, aids: Any,
        ) -> tuple:
            """Up to D FULL (non-finalizing) [P, c] chunks in ONE dispatch:
            one host↔device round trip per D chunks instead of one per
            chunk (an 8k prompt at c=256 is 32 chunks). What a round trip
            costs on an attached chip is not measured (ROADMAP D4). No
            sampling and no lengths update happen here (both belong to
            the finalize chunk, which always runs via the single-chunk
            step); history recording (speculation) mirrors
            prefill_chunk_step_hist. tokens3: [D, P, c]; n_chunks ≤ D is
            a runtime operand, so one compile serves every prompt length."""
            D, Pb, c = tokens3.shape

            def cond(s: tuple) -> Any:
                return s[0] < n_chunks

            def body(s: tuple) -> tuple:
                i, cache, history = s
                toks = jax.lax.dynamic_index_in_dim(
                    tokens3, i, 0, keepdims=False
                )
                starts = starts0 + i * c
                lens = jnp.full((Pb,), c, jnp.int32)
                _, cache = transformer_prefill_chunk(
                    params, toks, cache, slots, starts, lens, cfg,
                    dense_attn=dense_attn, aids=aids[slots],
                )
                if history is not None:
                    hpos = jnp.clip(
                        starts[:, None] + jnp.arange(c)[None, :], 0,
                        history.shape[1] - 1,
                    )
                    history = history.at[slots[:, None], hpos].set(toks)
                return i + 1, cache, history

            _, cache, history = jax.lax.while_loop(
                cond, body, (jnp.asarray(0, jnp.int32), cache, history)
            )
            return cache, history

        @partial(jax.jit, donate_argnums=(1,))
        def prefill_multi_chunk(
            params: Any, cache: Any, tokens3: Any, slots: Any,
            starts0: Any, n_chunks: Any, aids: Any,
        ) -> Any:
            cache, _ = _multi_chunk_core(
                params, cache, tokens3, slots, starts0, n_chunks, None, aids
            )
            return cache

        @partial(jax.jit, donate_argnums=(1, 6))
        def prefill_multi_chunk_hist(
            params: Any, cache: Any, tokens3: Any, slots: Any,
            starts0: Any, n_chunks: Any, history: Any, aids: Any,
        ) -> tuple:
            return _multi_chunk_core(
                params, cache, tokens3, slots, starts0, n_chunks, history,
                aids,
            )

        @partial(
            jax.jit, donate_argnums=(1, 12, 13, 14, 15, 18, 19, 22),
            static_argnames=("use_bias",),
        )
        def prefill_chunk_step_hist(
            params: Any, cache: Any, tokens: Any, slots: Any, starts: Any,
            lens: Any, finalize: Any, row_valid: Any, temps: Any,
            greedy: Any, topps: Any, seeds: Any, all_tokens: Any,
            all_logps: Any, pcounts: Any, nsteps: Any, bidx: Any,
            bval: Any, topi: Any, topl: Any, aids: Any, noff: Any,
            history: Any, use_bias: bool = False,
        ) -> tuple:
            """Prefill + record the chunk's tokens into the draft history
            (speculation on). Padding rows duplicate row 0 — idempotent."""
            out = _prefill_core(
                params, cache, tokens, slots, starts, lens, finalize,
                row_valid, temps, greedy, topps, seeds, all_tokens,
                all_logps, pcounts, nsteps, bidx, bval, topi, topl, aids,
                noff, use_bias,
            )
            c = tokens.shape[1]
            hpos = jnp.clip(
                starts[:, None] + jnp.arange(c)[None, :], 0,
                history.shape[1] - 1,
            )
            history = history.at[slots[:, None], hpos].set(tokens)
            return out + (history,)

        def make_decode_body(
            params: Any, active: Any, temps: Any, greedy: Any, topps: Any,
            fpen: Any, ppen: Any, seeds: Any, bidx: Any, bval: Any,
            use_bias: bool, aids: Any,
        ) -> Any:
            """One decode step (scan body): forward + sample + penalty
            count scatter — shared by the plain window and the mega
            while_loop so the two dispatch modes cannot drift."""

            def body(carry: tuple, _: Any) -> tuple:
                tokens, logps, cache, nsteps, pcounts, topi, topl = carry
                logits, cache = transformer_decode_step(
                    params, tokens, cache, active, cfg,
                    dense_attn=dense_attn, aids=aids,
                )
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
                return (nxt, nlp, cache, nsteps, pcounts, ntopi, ntopl), ys

            return body

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
            body = make_decode_body(params, active, temps, greedy, topps,
                                    fpen, ppen, seeds, bidx, bval, use_bias,
                                    aids)
            (final, final_lp, cache, nsteps, pcounts, topi, topl), ys = (
                jax.lax.scan(
                    body,
                    (tokens, logps, cache, nsteps, pcounts, topi, topl),
                    length=k,
                )
            )
            if top_lp_k:
                etoks, elps, etopi, etopl = ys
                etops = rep(jnp.stack([etopi.astype(jnp.float32), etopl]))
            else:
                etoks, elps = ys
                etops = None
            emitted = jnp.stack([etoks.astype(jnp.float32), elps])
            return (rep(emitted), etops, final, final_lp, cache, nsteps,
                    pcounts, topi, topl)

        eos_id = self.tokenizer.eos_id if self.tokenizer is not None else -1

        @partial(
            jax.jit, static_argnames=("k", "m", "use_bias"),
            donate_argnums=(3, 5, 11, 15, 16),
        )
        def mega_window(
            params: Any, tokens: Any, logps: Any, cache: Any, active: Any,
            nsteps: Any, temps: Any, greedy: Any, topps: Any, fpen: Any,
            ppen: Any, pcounts: Any, seeds: Any, bidx: Any, bval: Any,
            topi: Any, topl: Any, remaining: Any, eos_stop: Any,
            aids: Any, k: int, m: int, use_bias: bool,
        ) -> tuple:
            """Up to m k-step windows in ONE dispatch. A device-side
            while_loop runs windows until every slot's `remaining` budget
            is covered (decremented k per window; zeroed when the slot
            emits EOS and `eos_stop` holds) or m windows have run. Emits
            into a fixed [2, m*k, S] buffer; entries past the returned
            windows_run*k are untouched zeros the host must not read.
            Slots whose budget ran out while others continue keep
            computing junk tokens — their cache writes land past their
            retired region (scatter drops OOB; paged lookups park at
            block 0) and the host drops the tokens post-retirement, so
            the junk is slot-local by construction."""
            body = make_decode_body(params, active, temps, greedy, topps,
                                    fpen, ppen, seeds, bidx, bval, use_bias,
                                    aids)
            S = tokens.shape[0]
            emitted0 = jnp.zeros((2, m * k, S), dtype=jnp.float32)
            etops0 = (
                jnp.zeros((2, m * k, S, top_lp_k), dtype=jnp.float32)
                if top_lp_k else jnp.zeros((0,), dtype=jnp.float32)
            )

            def win_body(state: tuple) -> tuple:
                (w, tokens, logps, cache, nsteps, pcounts, remaining,
                 emitted, etops, topi, topl) = state
                ((tokens, logps, cache, nsteps, pcounts, topi, topl),
                 ys) = jax.lax.scan(
                    body,
                    (tokens, logps, cache, nsteps, pcounts, topi, topl),
                    length=k,
                )
                if top_lp_k:
                    etoks, elps, etopi, etopl = ys
                    etops = jax.lax.dynamic_update_slice(
                        etops,
                        jnp.stack([etopi.astype(jnp.float32), etopl]),
                        (0, w * k, 0, 0),
                    )
                else:
                    etoks, elps = ys
                slab = jnp.stack([etoks.astype(jnp.float32), elps])
                emitted = jax.lax.dynamic_update_slice(
                    emitted, slab, (0, w * k, 0)
                )
                hit = jnp.any(etoks == eos_id, axis=0) & eos_stop
                remaining = jnp.where(hit, 0, jnp.maximum(remaining - k, 0))
                return (w + 1, tokens, logps, cache, nsteps, pcounts,
                        remaining, emitted, etops, topi, topl)

            def win_cond(state: tuple) -> Any:
                return (state[0] < m) & jnp.any(state[6] > 0)

            (w, final, final_lp, cache, nsteps, pcounts, _, emitted, etops,
             topi, topl) = jax.lax.while_loop(
                win_cond, win_body,
                (jnp.asarray(0, jnp.int32), tokens, logps, cache,
                 nsteps, pcounts, remaining, emitted0, etops0, topi, topl),
            )
            return (rep(emitted), rep(etops) if top_lp_k else None, rep(w),
                    final, final_lp, cache, nsteps, pcounts, topi, topl)

        G = self.spec_tokens

        def make_spec_body(
            params: Any, active: Any, temps: Any, greedy: Any, topps: Any,
            seeds: Any, bidx: Any, bval: Any, use_bias: bool, aids: Any,
        ) -> Any:
            """One speculative step (scan body), shared by the plain spec
            window and the mega-spec while_loop.

            Numerics-exact verify: the G+1 candidate positions run through
            ``transformer_decode_step`` — the SAME program the spec-off
            decode window scans — in an inner scan, so every position's
            logits have the decode step's accumulation shape and reduction
            order and are bit-identical to what a spec-off engine would
            compute at that stream position. (The previous design verified
            all positions in one batched ``[S, G+1]`` forward whose bf16
            reduction order differed, flipping near-tie argmaxes — the
            ROADMAP direction-1 blocker this replaces; graftlint GL025 now
            flags that bug class statically.) Each inner step commits its
            K/V and advances ``lengths`` exactly like plain decode; after
            the scan the step rewinds ``lengths`` to the accepted count, so
            writes past it are junk beyond the live region — never
            attended, overwritten by the next step (the commit_chunk_kv
            discipline, inherited for free).

            Because verification IS the decode-step + shared ``sample``
            closure (counter-based keys at the same stream offsets),
            acceptance extends beyond greedy: a seeded-SAMPLED slot accepts
            a draft token when the categorical draw at that position picks
            it, and per-request ``logit_bias`` rides through the same
            ``use_bias`` compile variant the decode window uses — both
            byte-identical to spec=0 by the same construction."""
            from gofr_tpu.models.transformer import (
                ngram_draft,
                transformer_decode_step,
            )

            def body(carry: tuple, _: Any) -> tuple:
                tokens, logps, cache, nsteps, history = carry
                draft = ngram_draft(history, cache.lengths, tokens, G)
                inputs = jnp.concatenate([tokens[:, None], draft], axis=1)
                lengths0 = cache.lengths

                def pos_body(pcarry: tuple, tok_j: Any) -> tuple:
                    cache_i, n_i = pcarry
                    logits, cache_i = transformer_decode_step(
                        params, tok_j, cache_i, active, cfg,
                        dense_attn=dense_attn, aids=aids,
                    )
                    sub = row_keys(seeds, n_i)
                    nxt, nlp, _, _ = sample(
                        logits, sub, temps, greedy, topps,
                        bias=(bidx, bval) if use_bias else None,
                    )
                    return (
                        (cache_i, n_i + active.astype(jnp.int32)),
                        (nxt, nlp),
                    )

                # "verify" in the trace: the G+1 decode-step forwards
                # that check the draft (their ops read verify/…/attn).
                with jax.named_scope("verify"):
                    (cache, _), (chosen_s, chosen_lp_s) = jax.lax.scan(
                        pos_body, (cache, nsteps), inputs.T
                    )
                chosen = chosen_s.T  # [S, G+1] — position j's TRUE token
                chosen_lp = chosen_lp_s.T
                # Accept the longest prefix of drafts that match the token
                # the decode-step program actually chose at each position
                # (greedy slots: the exact argmax; sampled slots: the exact
                # counter-keyed categorical draw — both identical to the
                # spec=0 stream by construction, so acceptance is lossless
                # for EVERY slot, not just greedy ones).
                match = draft == chosen[:, :G]
                acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
                counts = jnp.where(active, acc + 1, 0)
                bonus = jnp.take_along_axis(chosen, acc[:, None], axis=1)[:, 0]
                bonus_lp = jnp.take_along_axis(
                    chosen_lp, acc[:, None], axis=1
                )[:, 0]
                step_tokens = inputs  # [S, G+1]; first `counts` are emitted
                # Position j's emitted logprob is the one its token was
                # CHOSEN with at position j-1 (accepted ⇒ draft == chosen).
                step_logps = jnp.concatenate(
                    [logps[:, None], chosen_lp[:, :G]], axis=1
                )
                # History: current+accepted drafts at len..len+acc, bonus at
                # len+counts — the invariant "current token sits at
                # history[lengths]" holds into the next step. Rejected
                # drafts and inactive slots park at max_len-1 (XLA scatter
                # is nondeterministic on duplicate indices, so the rejected
                # entries must not share a position with the bonus write;
                # history[max_len-1] garbage only ever wastes a draft).
                S2, T = history.shape
                hvals = jnp.concatenate([inputs, bonus[:, None]], axis=1)
                hpos = lengths0[:, None] + jnp.arange(G + 2)[None, :]
                hpos = hpos.at[:, G + 1].set(lengths0 + counts)
                keep = jnp.concatenate(
                    [
                        jnp.arange(G + 1)[None, :] <= acc[:, None],
                        jnp.ones((S2, 1), dtype=bool),
                    ],
                    axis=1,
                )
                keep = keep & active[:, None]
                hpos = jnp.where(keep, jnp.minimum(hpos, T - 1), T - 1)
                history = history.at[
                    jnp.arange(S2)[:, None], hpos
                ].set(hvals)
                # The inner scan advanced lengths by G+1 per active slot;
                # the stream only accepted `counts`. Rewind — junk K/V
                # above lengths0+counts is never attended and the next
                # step's decode writes overwrite it in order.
                cache = cache._replace(lengths=lengths0 + counts)
                nsteps = nsteps + counts
                return (
                    (bonus, bonus_lp, cache, nsteps, history),
                    (step_tokens, step_logps, counts),
                )

            return body

        @partial(
            jax.jit, static_argnames=("k", "use_bias"),
            donate_argnums=(3, 5, 9),
        )
        def spec_window(
            params: Any, tokens: Any, logps: Any, cache: Any, active: Any,
            nsteps: Any, temps: Any, greedy: Any, topps: Any,
            history: Any, seeds: Any, bidx: Any, bval: Any, aids: Any,
            k: int, use_bias: bool,
        ) -> tuple:
            """k speculative steps on device. Each step drafts G tokens by
            n-gram lookup in the slot's own history, verifies draft+current
            by running the DECODE-STEP program over the G+1 positions
            (bit-exact vs spec=0 — see make_spec_body), accepts the longest
            prefix matching the program's own choices (greedy AND sampled
            slots), and carries the bonus token. Emits per step: tokens
            [S, G+1] (= the step's inputs), logps, and counts [S]
            (=accepted+1 valid entries)."""
            body = make_spec_body(params, active, temps, greedy, topps,
                                  seeds, bidx, bval, use_bias, aids)
            ((final, final_lp, cache, nsteps, history),
             (etoks, elps, ecnt)) = jax.lax.scan(
                body, (tokens, logps, cache, nsteps, history), length=k
            )
            emitted = jnp.stack(
                [etoks.astype(jnp.float32), elps]
            )  # [2, k, S, G+1]
            return (rep(emitted), rep(ecnt), final, final_lp, cache, nsteps,
                    history)

        @partial(
            jax.jit, static_argnames=("k", "m", "use_bias"),
            donate_argnums=(3, 5, 9),
        )
        def mega_spec_window(
            params: Any, tokens: Any, logps: Any, cache: Any, active: Any,
            nsteps: Any, temps: Any, greedy: Any, topps: Any,
            history: Any, seeds: Any, bidx: Any, bval: Any,
            remaining: Any, eos_stop: Any,
            aids: Any, k: int, m: int, use_bias: bool,
        ) -> tuple:
            """Mega × speculation: up to m k-step spec windows in ONE
            dispatch. `remaining` decrements by the ACTUAL emitted token
            counts (speculation emits ≥ k per window per live slot, so
            coverage ≥ the plain-decode guarantee); EOS detection scans
            only the VALID (first `counts`) entries of each step —
            rejected draft positions must not zero a budget."""
            body = make_spec_body(params, active, temps, greedy, topps,
                                  seeds, bidx, bval, use_bias, aids)
            S = tokens.shape[0]
            emitted0 = jnp.zeros((2, m * k, S, G + 1), dtype=jnp.float32)
            ecnt0 = jnp.zeros((m * k, S), dtype=jnp.int32)

            def win_body(state: tuple) -> tuple:
                (w, tokens, logps, cache, nsteps, history, remaining,
                 emitted, ecnt) = state
                ((tokens, logps, cache, nsteps, history),
                 (etoks, elps, cnts)) = jax.lax.scan(
                    body, (tokens, logps, cache, nsteps, history), length=k
                )
                slab = jnp.stack([etoks.astype(jnp.float32), elps])
                emitted = jax.lax.dynamic_update_slice(
                    emitted, slab, (0, w * k, 0, 0)
                )
                ecnt = jax.lax.dynamic_update_slice(
                    ecnt, cnts.astype(jnp.int32), (w * k, 0)
                )
                valid = (
                    jnp.arange(G + 1)[None, None, :] < cnts[:, :, None]
                )  # [k, S, G+1]
                hit = (
                    ((etoks == eos_id) & valid).any(axis=(0, 2)) & eos_stop
                )
                delivered = cnts.sum(axis=0).astype(jnp.int32)  # [S]
                remaining = jnp.where(
                    hit, 0, jnp.maximum(remaining - delivered, 0)
                )
                return (w + 1, tokens, logps, cache, nsteps, history,
                        remaining, emitted, ecnt)

            def win_cond(state: tuple) -> Any:
                return (state[0] < m) & jnp.any(state[6] > 0)

            ((w, final, final_lp, cache, nsteps, history, _, emitted,
              ecnt)) = jax.lax.while_loop(
                win_cond, win_body,
                (jnp.asarray(0, jnp.int32), tokens, logps, cache, nsteps,
                 history, remaining, emitted0, ecnt0),
            )
            return (rep(emitted), rep(ecnt), rep(w), final, final_lp, cache,
                    nsteps, history)

        # Compile tracking (serving/device_telemetry.py): every serving
        # program is wrapped so each XLA cache growth counts under its
        # program name — and a compile after the warm-up fence bumps
        # the steady-state recompile counter, the dynamic twin of
        # graftlint GL015's static jit-in-request-path check.
        wrap = self._compiles.wrap
        self._prefill_chunk_step = wrap("prefill_chunk", prefill_chunk_step)
        self._prefill_chunk_step_hist = wrap(
            "prefill_chunk_hist", prefill_chunk_step_hist
        )
        self._prefill_multi_chunk = wrap(
            "prefill_multi_chunk", prefill_multi_chunk
        )
        self._prefill_multi_chunk_hist = wrap(
            "prefill_multi_chunk_hist", prefill_multi_chunk_hist
        )
        self._decode_window = wrap("decode_window", decode_window)
        self._mega_window = wrap("mega_window", mega_window)
        self._spec_window = wrap("spec_window", spec_window)
        self._mega_spec_window = wrap("mega_spec_window", mega_spec_window)
        self.prefill_rungs = prefill_rungs(self.prefill_batch)
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
        device planes, and the history plane under speculation."""
        operands = (
            self.params, self.cache, *map(self._up, per_row),
            self._seeds_dev, self._tokens_dev, self._logps_dev,
            self._pcounts_dev, self._nsteps_dev, self._bidx_dev,
            self._bval_dev, self._topi_dev, self._topl_dev,
            self._aids_dev, self._noff_dev,
        )
        if self.spec_tokens:
            operands += (self._history_dev,)
        return operands

    def _prefill_step(self, rows: int, use_bias: bool) -> Any:
        """The compiled ``[rows, prefill_chunk]`` prefill step (the
        ``_hist`` program under speculation), called with
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
        name, program = "prefill_chunk", self._prefill_chunk_step
        if self.spec_tokens:
            name, program = "prefill_chunk_hist", self._prefill_chunk_step_hist
        step = self._compiles.compile_ahead(
            name,
            lambda: program.__wrapped__.lower(
                *operands, use_bias=use_bias
            ).compile(),
        )
        self._prefill_steps[(rows, use_bias)] = step
        return step

    # ------------------------------------------------------------------
    # profiling (bench harness; VERDICT r1 weak #4 — know where time goes)
    # ------------------------------------------------------------------

    def profile_decode(
        self, n_windows: int = 8, prompt_len: int = 16
    ) -> dict:
        """Measure device-only decode window time and the host↔device fetch
        RTT, with the engine stopped. Chains ``n_windows`` windows
        back-to-back with one final block, so the fetch RTT amortizes out:
        ``window_s ≈ (total - rtt) / n_windows``.

        Returns ``{"window_s", "step_s", "rtt_s", "prefill_s"}``.
        """
        if self.family != "llm":
            raise RuntimeError("profile_decode is for llm engines")
        if self._running:
            raise RuntimeError("stop the engine before profiling")
        jax, jnp = self._jax, self._jnp
        B, P = self.n_slots, self.prefill_batch
        prompt_len = min(prompt_len, self.prefill_chunk)

        # Prefill ALL slots via chunk steps so decode reads realistic KV
        # prefixes. Timed on the last call (first pays compile).
        prefill_s = 0.0
        for base in range(0, B, P):
            rows = list(range(base, min(base + P, B)))
            tokens = np.ones((P, self.prefill_chunk), dtype=np.int32)
            slots = np.full((P,), rows[0], dtype=np.int32)
            slots[: len(rows)] = rows
            starts = np.zeros((P,), dtype=np.int32)
            lens = np.full((P,), prompt_len, dtype=np.int32)
            finalize = np.ones((P,), dtype=bool)
            row_valid = np.zeros((P,), dtype=bool)
            row_valid[: len(rows)] = True
            temps = np.ones((P,), dtype=np.float32)
            topps = np.ones((P,), dtype=np.float32)
            greedy = np.ones((P,), dtype=bool)
            t0 = time.perf_counter()
            (self.cache, self._tokens_dev, self._logps_dev, first, _flp,
             self._pcounts_dev, self._nsteps_dev, self._topi_dev,
             self._topl_dev, _fti, _ftl) = (
                self._prefill_chunk_step(
                    self.params, self.cache, self._up(tokens),
                    self._up(slots), self._up(starts), self._up(lens),
                    self._up(finalize), self._up(row_valid),
                    self._up(temps), self._up(greedy),
                    self._up(topps),
                    self._seeds_dev, self._tokens_dev, self._logps_dev,
                    self._pcounts_dev, self._nsteps_dev, self._bidx_dev,
                    self._bval_dev, self._topi_dev, self._topl_dev,
                    self._aids_dev, self._noff_dev,
                    use_bias=False,
                )
            )
            jax.block_until_ready(first)
            prefill_s = time.perf_counter() - t0

        # Fresh [B]-shaped vectors — the prefill loop's temps/greedy above
        # are [P]-shaped and P != B crashes the decode window.
        active = jnp.ones((B,), dtype=bool)
        tdev = jnp.ones((B,), dtype=jnp.float32)
        pdev = jnp.ones((B,), dtype=jnp.float32)
        gdev = jnp.ones((B,), dtype=bool)

        def window() -> Any:
            out = self._decode_window(
                self.params, self._tokens_dev, self._logps_dev, self.cache,
                active, self._nsteps_dev, tdev, gdev, pdev,
                self._fpen_dev, self._ppen_dev, self._pcounts_dev,
                self._seeds_dev, self._bidx_dev, self._bval_dev,
                self._topi_dev, self._topl_dev, self._aids_dev,
                k=self.window_k, use_bias=False,
            )
            (emitted, _etops, self._tokens_dev, self._logps_dev, self.cache,
             self._nsteps_dev, self._pcounts_dev, self._topi_dev,
             self._topl_dev) = out
            return emitted

        # Warmup (compile) + RTT probe: a blocking fetch of a just-computed
        # tiny array is ~one host↔device round trip.
        jax.block_until_ready(window())
        rtts = []
        for _ in range(5):
            x = self._tokens_dev + 1
            t0 = time.perf_counter()
            np.asarray(x)
            rtts.append(time.perf_counter() - t0)
        rtt_s = sorted(rtts)[len(rtts) // 2]

        t0 = time.perf_counter()
        last = None
        for _ in range(n_windows):
            last = window()
        jax.block_until_ready(last)
        total = time.perf_counter() - t0
        window_s = max(total - rtt_s, 1e-9) / n_windows

        # Reset cache lengths so profiling state can't leak into serving.
        self.cache = self.cache._replace(
            lengths=jnp.zeros_like(self.cache.lengths)
        )
        self._slot_state_dirty = True
        return {
            "window_s": window_s,
            "step_s": window_s / self.window_k,
            "rtt_s": rtt_s,
            "prefill_s": prefill_s,
        }

    def param_bytes(self) -> int:
        from gofr_tpu.ops.quant import quantized_bytes

        return quantized_bytes(self.params)

