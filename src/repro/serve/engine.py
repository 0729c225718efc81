"""Serving engine — autoregressive decode as Loop-of-stencil-reduce-s.

The decode loop is the -s variant verbatim (DESIGN.md §4):
    stencil step : one `decode_step` (attention over the KV-cache
                   neighbourhood — the sliding-window layers are literal
                   sequence stencils)
    reduce /⊕    : `all` monoid over per-sequence done flags
    state s      : position counter + PRNG key
    condition c  : every sequence hit EOS ∨ token budget

The whole generation lowers to ONE on-device while_loop: the KV cache is
the paper's persistent device memory — it never leaves HBM, and the
done-reduce feeding the condition runs on device (beyond the paper, which
still bounced the reduce result to the host each iteration).

In stream-tier terms (:mod:`repro.core.streaming`) a generate batch IS a
lane farm: each sequence is a lane of the done-masked loop, running to
its own EOS trip count while the KV cache plays the persistent lane
frame.  The host side composes accordingly — :class:`repro.serve.
batcher.Batcher` drives batches through the FarmEngine's double-buffered
read ∥ decode ∥ write protocol.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.pattern import LoopOfStencilReduce
from repro.models import transformer as T


@dataclasses.dataclass
class GenerateConfig:
    max_new_tokens: int = 64
    eos_id: int = 1
    temperature: float = 0.0       # 0 → greedy
    seed: int = 0


def prefill(cfg: ArchConfig, params, tokens, *, max_seq: int,
            cache_dtype=jnp.bfloat16, patch_embeds=None, enc_out=None,
            cross_caches=None):
    """Run the prompt through the model, returning (last_logits, caches)."""
    B = tokens.shape[0]
    caches = T.init_cache(cfg, B, max_seq, cache_dtype)
    logits, caches = T.step_with_cache(
        cfg, params, caches, tokens, 0, patch_embeds=patch_embeds,
        enc_out=enc_out, cross_caches=cross_caches)
    return logits[:, -1], caches


def generate(cfg: ArchConfig, params, prompt, gcfg: GenerateConfig, *,
             max_seq: Optional[int] = None, cache_dtype=jnp.bfloat16,
             enc_out=None, cross_caches=None, patch_embeds=None,
             budgets=None):
    """Batched generation.  Returns (tokens (B, max_new), lengths, iters).

    ``budgets`` is an optional (B,) int vector of per-sequence
    ``max_new_tokens`` (each in [1, gcfg.max_new_tokens]): the
    done-mask retires a sequence at its OWN budget, mirroring the
    continuous engine's per-request budgets, so round-mode batches honor
    ``Request.max_new_tokens`` identically.  ``lengths`` is clipped to
    the budget (post-done positions are eos-padded in ``out``).
    """
    B, S0 = prompt.shape
    P = cfg.vision_patches or 0
    max_seq = max_seq or (S0 + P + gcfg.max_new_tokens)

    last_logits, caches = prefill(
        cfg, params, prompt, max_seq=max_seq, cache_dtype=cache_dtype,
        patch_embeds=patch_embeds, enc_out=enc_out,
        cross_caches=cross_caches)

    def sample(logits, key):
        if gcfg.temperature > 0:
            return jax.random.categorical(key, logits / gcfg.temperature,
                                          axis=-1)
        return jnp.argmax(logits, axis=-1)

    bud = (jnp.full((B,), gcfg.max_new_tokens, jnp.int32)
           if budgets is None else jnp.asarray(budgets, jnp.int32))
    key0 = jax.random.PRNGKey(gcfg.seed)
    first = sample(last_logits, key0)                     # (B,)
    out0 = jnp.zeros((B, gcfg.max_new_tokens), jnp.int32)
    out0 = out0.at[:, 0].set(first)
    done0 = jnp.logical_or(first == gcfg.eos_id, bud <= 1)

    def step_fn(carry):
        caches, out, done, t, key = carry
        tok = jax.lax.dynamic_slice_in_dim(out, t - 1, 1, axis=1)
        logits, caches = T.decode_step(
            cfg, params, caches, tok, S0 + P + t - 1,
            enc_out=enc_out, cross_caches=cross_caches)
        key, sub = jax.random.split(key)
        nxt = sample(logits[:, 0], sub)
        nxt = jnp.where(done, jnp.full_like(nxt, gcfg.eos_id), nxt)
        if gcfg.max_new_tokens > 1:
            # cap == 1: the repeat/until still runs its one mandatory
            # body step, whose write index (t=1) would CLIP onto column
            # 0 and eos-pad over the only real token — skip it (every
            # lane is already done0-retired at cap 1)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, nxt[:, None].astype(out.dtype), t, axis=1)
        done = done | (nxt == gcfg.eos_id) | (t + 1 >= bud)
        return (caches, out, done, t + 1, key)

    loop = LoopOfStencilReduce(
        f=step_fn, mode="step", combine="all", identity=True,
        measure=lambda c: c[2],                   # per-sequence done flags
        cond=lambda r, s: jnp.logical_or(r, s >= gcfg.max_new_tokens),
        state_init=lambda: jnp.asarray(1, jnp.int32),
        state_update=lambda s, a, it: s + 1,
        max_iters=gcfg.max_new_tokens)

    res = loop.run((caches, out0, done0, jnp.asarray(1, jnp.int32), key0))
    _, out, done, _, _ = res.a
    lengths = jnp.where(
        (out == gcfg.eos_id).any(axis=1),
        (out == gcfg.eos_id).argmax(axis=1) + 1, gcfg.max_new_tokens)
    # a budget-retired sequence has eos PADS from its budget on — clip
    # so the pad never counts as a sampled token
    lengths = jnp.minimum(lengths, bud)
    return out, lengths, res.iters


def generate_jit(cfg: ArchConfig, gcfg: GenerateConfig, **kw):
    """Jit-compiled generate closure (static cfg/gcfg)."""
    return jax.jit(functools.partial(generate, cfg, gcfg=gcfg, **kw))


# ---------------------------------------------------------------------------
# Continuous batching — per-sequence KV-slot refill.
# ---------------------------------------------------------------------------


def request_budget(req, cap: int) -> int:
    """Resolve a request's per-sequence token budget against the engine
    cap — the ONE validation rule shared by the round path
    (:meth:`repro.serve.batcher.Batcher.run_all`) and the continuous
    engine, so the two paths cannot drift (their budget parity is
    regression-tested)."""
    bud = getattr(req, "max_new_tokens", None)
    bud = cap if bud is None else bud
    if not 1 <= bud <= cap:
        raise ValueError(
            f"request budget {bud} outside [1, max_new_tokens={cap}] "
            "(the slot width)")
    return bud


@dataclasses.dataclass
class _RestoredRequest:
    """A request rebuilt from a :meth:`ContinuousEngine.snapshot` tree —
    duck-typed like :class:`repro.serve.batcher.Request` (kept here to
    avoid an engine→batcher import cycle).  ``deadline`` is re-anchored
    to the RESUMED process's clock from the snapshot's stored remaining
    time."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: Optional[int] = None
    deadline: Optional[float] = None


def _arch_has_ssm(cfg: ArchConfig) -> bool:
    """Whether the stack carries SSM layers — their sequential state
    updates have no pad-masking path, so ragged (padded) prefill is
    attention-only."""
    pattern = (cfg.decoder_pattern() if cfg.is_encoder_decoder
               else cfg.block_pattern())
    prefix, unit, _ = pattern
    return any(s.kind == "ssm" for s in (*prefix, *unit))


class ContinuousEngine:
    """Continuous-batching decode: persistent KV-cache slots with
    per-sequence refill — the serve-side twin of the farm tier's
    lane refill (:meth:`repro.core.streaming.FarmEngine.run`).

    ``slots`` KV-cache lanes persist on device.  Decode advances in
    bounded *segments* (the :func:`repro.core.pattern.segmented_while`
    tier: control returns to the dispatcher as soon as any sequence
    newly finishes, or after ``segment`` steps).  A finished sequence's
    tokens are emitted immediately — not at the batch barrier — and its
    KV slot is handed to the next queued request mid-batch: the
    newcomer's prompt is prefilled into the slot (one whole-slot cache
    write, which also evicts any stale keys of the previous occupant)
    while the other sequences keep decoding at their own depths
    (per-sequence cache positions, RoPE and masks — see
    :func:`repro.models.transformer.step_with_cache`).

    One compilation serves every segment and every slot prefill of a
    stream (``stats["segment_traces"]`` / ``stats["prefill_traces"]``
    count trace events; both stay 1 after the first request).

    Prompts may be RAGGED: the engine binds ONE slot pool at
    ``max_prompt_len`` (given, or the longest prompt of the first run)
    and admits each request through a right-padded per-slot prefill with
    a prompt-length mask (:func:`repro.models.transformer.
    step_with_cache` ``prompt_len=``) — pad keys never enter an
    attention window or a ring cache, the first token is sampled at the
    prompt's own last REAL row, and decode continues from each slot's
    own depth.  ``stats["idle_slot_steps"]`` (the farm tier's
    ``wasted_lane_steps`` analogue) counts slot-steps burned on retired
    or done-masked slots; draining a ragged queue through one pool keeps
    it strictly below exact-length grouping, which idles a whole cohort
    at every group tail.

    Constraints: per-request ``max_new_tokens`` is capped by the
    engine-level ``gcfg.max_new_tokens`` (the slot width); models with
    absolute position embeddings, encoders or vision prefixes are not
    supported (their position bookkeeping is not per-sequence); ragged
    admission needs an attention-only stack (SSM state updates are
    sequential and have no pad-masking path — group those by exact
    length upstream, as :meth:`repro.serve.batcher.Batcher.
    run_continuous` does automatically).
    """

    def __init__(self, cfg: ArchConfig, params, gcfg: GenerateConfig, *,
                 slots: int = 8, cache_dtype=jnp.bfloat16,
                 segment: int = 8, max_prompt_len: Optional[int] = None):
        if cfg.abs_pos_embed or cfg.is_encoder_decoder or \
                cfg.vision_patches:
            raise ValueError(
                "continuous batching needs per-sequence positions; "
                "absolute position embeddings, encoder-decoder and "
                "vision-prefix models are round-based only")
        if segment < 1:
            raise ValueError(f"segment must be >= 1; got {segment}")
        self.cfg, self.params, self.gcfg = cfg, params, gcfg
        self.slots, self.cache_dtype = slots, cache_dtype
        self.segment = segment
        self.max_prompt_len = max_prompt_len
        self._bound = False
        self._segment_fn = jax.jit(self._segment_impl,
                                   donate_argnums=(1, 2, 3, 4, 5, 6, 7))
        self._prefill_fn = jax.jit(self._prefill_impl,
                                   donate_argnums=(1, 2, 3, 4, 5, 6, 7))
        # the chained-dispatch twins (run(..., chained=True)): only the
        # KV pool is donated — the small carry rows (out/done/t/...)
        # must SURVIVE each call, because the async drain still holds
        # the previous segments' captures and reads them only after the
        # next segment is in flight
        self._chain_seg_fn = jax.jit(self._chain_seg_impl,
                                     donate_argnums=(1,))
        self._chain_prefill_fn = jax.jit(self._prefill_impl,
                                         donate_argnums=(1,))
        self._chain_restore_fn = jax.jit(self._restore_slot_impl,
                                         donate_argnums=(0,))
        self._chain_retire_fn = jax.jit(
            lambda done, idx: done.at[idx].set(True))
        # deadline eviction with an empty queue: retire the slot in
        # place (same compilation for every eviction, done donated)
        self._retire_fn = jax.jit(
            lambda done, idx: done.at[idx].set(True),
            donate_argnums=(0,))
        # snapshot/restore of ONE slot's whole state (per-slot cache
        # slices + carry row): the reader is un-donated (safe on the
        # live buffers mid-stream), the writer donates like a prefill
        self._snap_slot_fn = jax.jit(self._snap_slot_impl)
        self._restore_slot_fn = jax.jit(
            self._restore_slot_impl,
            donate_argnums=(0, 1, 2, 3, 4, 5, 6))
        self.stats = {"requests": 0, "segments": 0, "prefills": 0,
                      "emitted": 0, "segment_traces": 0,
                      "chain_traces": 0,
                      "prefill_traces": 0, "slot_steps": 0,
                      "idle_slot_steps": 0, "evicted": 0, "shed": 0,
                      "snapshots": 0, "replayed_items": 0,
                      "recovered_occupants": 0, "recovery_seconds": 0.0}
        self._resume_state = None       # staged by restore()
        self._rt_capture = None         # live snapshot closure

    # -- static geometry (first run binds the shapes) --------------------
    def _bind(self, prompt_len: int):
        B, cap = self.slots, self.gcfg.max_new_tokens
        self._S0 = prompt_len                   # slot (max) prompt width
        self._max_seq = prompt_len + cap
        self._caches = T.init_cache(self.cfg, B, self._max_seq,
                                    self.cache_dtype)
        self._out = jnp.zeros((B, cap), jnp.int32)
        self._done = jnp.ones((B,), bool)
        self._t = jnp.ones((B,), jnp.int32)     # tokens generated
        self._budget = jnp.ones((B,), jnp.int32)
        self._keys = jnp.zeros((B, 2), jnp.uint32)
        self._plen = jnp.full((B,), prompt_len, jnp.int32)
        self._bound = True

    def _sample(self, logits, key):
        if self.gcfg.temperature > 0:
            return jax.random.categorical(
                key, logits / self.gcfg.temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    # -- slot prefill: hand a finished slot to the next request ----------
    def _prefill_impl(self, params, caches, out, done, t, budget, keys,
                      plens, idx, prompt, plen, bud, key):
        """Admit one request into slot ``idx`` (dynamic): prefill its
        RIGHT-PADDED prompt into a fresh single-sequence cache under the
        ``plen`` prompt-length mask, write that cache over the slot (one
        whole-slot dynamic_update_slice per leaf — this is the slot
        hand-off, and it evicts the previous occupant's stale keys
        wholesale), sample the first token at the prompt's own last REAL
        row, and re-arm the slot's carry.  One compilation serves every
        admission — the padded prompt width is the bound
        ``max_prompt_len``, whatever the request's true length."""
        self.stats["prefill_traces"] += 1       # traced once per stream
        fresh = T.init_cache(self.cfg, 1, self._max_seq, self.cache_dtype)
        logits, fresh = T.step_with_cache(self.cfg, params, fresh,
                                          prompt[None], 0,
                                          prompt_len=plen[None])
        last = jax.lax.dynamic_index_in_dim(logits[0], plen - 1, axis=0,
                                            keepdims=True)     # (1, V)
        first = self._sample(last, key)[0]

        def slot_write(axis):
            return lambda b, f: jax.lax.dynamic_update_slice_in_dim(
                b, f.astype(b.dtype), idx, axis=axis)
        caches = {"prefix": jax.tree.map(slot_write(0), caches["prefix"],
                                         fresh["prefix"]),
                  "unit": jax.tree.map(slot_write(1), caches["unit"],
                                       fresh["unit"])}
        out = out.at[idx].set(0).at[idx, 0].set(first.astype(jnp.int32))
        done = done.at[idx].set(
            jnp.logical_or(first == self.gcfg.eos_id, bud <= 1))
        t = t.at[idx].set(1)
        budget = budget.at[idx].set(bud)
        keys = keys.at[idx].set(key)
        plens = plens.at[idx].set(plen)
        return caches, out, done, t, budget, keys, plens

    # -- slot snapshot / restore (preemption recovery) -------------------
    def _snap_slot_impl(self, caches, idx):
        """Slice ONE slot's cache state out (prefix leaves carry the
        sequence on axis 0, unit leaves on axis 1 — the same convention
        ``_prefill_impl``'s slot_write uses)."""
        pfx = jax.tree.map(
            lambda b: jax.lax.dynamic_slice_in_dim(b, idx, 1, axis=0),
            caches["prefix"])
        unit = jax.tree.map(
            lambda b: jax.lax.dynamic_slice_in_dim(b, idx, 1, axis=1),
            caches["unit"])
        return pfx, unit

    def _restore_slot_impl(self, caches, out, done, t, budget, keys,
                           plens, idx, pfx, unit, out_row, dn, tv, budv,
                           keyv, plenv):
        """Re-seat one snapshotted in-flight decode into slot ``idx``
        (possibly a different slot index than it occupied pre-crash —
        the resumed engine may have a different slot count): the saved
        cache slices write through the SAME whole-slot paths a prefill
        uses, and the carry row (out/done/t/budget/key/plen) re-arms
        with the saved values so decoding continues mid-generation,
        sampling included (the PRNG key is part of the carry)."""
        def slot_write(axis):
            return lambda b, f: jax.lax.dynamic_update_slice_in_dim(
                b, f.astype(b.dtype), idx, axis=axis)
        caches = {"prefix": jax.tree.map(slot_write(0), caches["prefix"],
                                         pfx),
                  "unit": jax.tree.map(slot_write(1), caches["unit"],
                                       unit)}
        out = out.at[idx].set(out_row.astype(out.dtype))
        done = done.at[idx].set(dn)
        t = t.at[idx].set(jnp.asarray(tv, t.dtype))
        budget = budget.at[idx].set(jnp.asarray(budv, budget.dtype))
        keys = keys.at[idx].set(keyv.astype(keys.dtype))
        plens = plens.at[idx].set(jnp.asarray(plenv, plens.dtype))
        return caches, out, done, t, budget, keys, plens

    def snapshot(self) -> dict:
        """The in-flight serve state as ONE logical tree: every occupied
        slot's KV-cache slices, output row, position/budget/PRNG-key
        carry, its request (deadline stored as REMAINING seconds — it
        re-anchors to the resumed process's clock), the not-yet-admitted
        queue, and the admission-key cursor (``stats["prefills"]`` — so
        post-resume admissions sample the same keys an uninterrupted run
        would).  Topology-free over ``slots``: restore onto a pool of
        any size.  Only meaningful at a segment boundary (use
        ``on_segment``, or pass ``recovery=`` to :meth:`run`)."""
        if self._rt_capture is None:
            raise ValueError(
                "snapshot() captures in-flight serve state; nothing has "
                "run yet — call run() (pass recovery= to persist "
                "snapshots automatically)")
        return self._rt_capture()

    def restore(self, state: dict) -> "ContinuousEngine":
        """Stage a :meth:`snapshot` tree; the next :meth:`run` resumes
        from it (in-flight decodes continue mid-generation, queued
        requests re-queue ahead of new ones).  The engine's ``slots``
        may differ from the snapshotting engine's; the generation cap
        and bound prompt width may not."""
        if not isinstance(state, dict) or state.get("kind") != "serve":
            raise ValueError("not a ContinuousEngine snapshot tree")
        if int(state.get("version", -1)) != 1:
            raise ValueError("unsupported ContinuousEngine snapshot "
                             f"version {state.get('version')!r}")
        if int(state["cap"]) != self.gcfg.max_new_tokens:
            raise ValueError(
                f"snapshot generation cap {state['cap']} != engine cap "
                f"{self.gcfg.max_new_tokens} (the out-buffer width is "
                "part of the slot geometry)")
        if self._bound and int(state["S0"]) != self._S0:
            raise ValueError(
                f"snapshot prompt width {state['S0']} != bound slot "
                f"width {self._S0}")
        self._resume_state = state
        return self

    # -- one bounded decode segment --------------------------------------
    def _segment_impl(self, params, caches, out, done, t, budget, keys,
                      plens):
        """Advance every live slot up to ``segment`` decode steps,
        returning as soon as any sequence newly finishes (EOS or its own
        token budget).  Per-sequence positions: slot b reads its last
        token at out[b, t_b-1] and writes the cache at plen_b + t_b - 1
        (each slot decodes from its OWN prompt depth — ragged prompts
        share the pool)."""
        self.stats["segment_traces"] += 1       # traced once per stream
        return self._segment_core(params, caches, out, done, t, budget,
                                  keys, plens)

    def _chain_seg_impl(self, params, caches, out, done, t, budget,
                        keys, plens):
        """The chained path's segment: the SAME decode core, jitted with
        only the KV pool donated — the returned carry rows double as the
        drain's captures (read asynchronously, one pipeline stage
        later), so their buffers must outlive the next dispatch."""
        self.stats["segment_traces"] += 1       # traced once per stream
        self.stats["chain_traces"] += 1
        return self._segment_core(params, caches, out, done, t, budget,
                                  keys, plens)

    def _segment_core(self, params, caches, out, done, t, budget, keys,
                      plens):
        from repro.core.pattern import segmented_while

        B, cap = self.slots, self.gcfg.max_new_tokens
        eos = self.gcfg.eos_id

        def body(carry):
            caches, out, done, t, keys = carry
            live = jnp.logical_not(done)
            tok = jnp.take_along_axis(out, (t - 1)[:, None], axis=1)
            pos = (plens + t - 1)[:, None]               # (B, 1)
            logits, caches = T.decode_step(self.cfg, params, caches,
                                           tok, pos)
            if self.gcfg.temperature > 0:
                nk = jax.vmap(jax.random.split)(keys)    # (B, 2, 2)
                keys = jnp.where(live[:, None], nk[:, 0], keys)
                nxt = jax.vmap(
                    lambda lg, kk: self._sample(lg, kk))(logits[:, 0],
                                                         nk[:, 1])
            else:
                nxt = jnp.argmax(logits[:, 0], axis=-1)
            nxt = jnp.where(live, nxt, jnp.full_like(nxt, eos))
            tw = jnp.minimum(t, cap - 1)
            row = jnp.arange(B)
            out = out.at[row, tw].set(
                jnp.where(live, nxt.astype(jnp.int32), out[row, tw]))
            t = jnp.where(live, t + 1, t)
            done = jnp.logical_or(
                done, jnp.logical_and(
                    live, jnp.logical_or(nxt == eos, t >= budget)))
            return caches, out, done, t, keys

        (caches, out, done, t, keys), steps = segmented_while(
            body, (caches, out, done, t, keys),
            finished=lambda c: c[2], segment=self.segment)
        return caches, out, done, t, budget, keys, plens, steps

    # -- the dispatcher ---------------------------------------------------
    def run(self, requests, emit, *, clock=None, recovery=None,
            resume: bool = False,
            on_segment: Optional[Callable] = None,
            chained: bool = False) -> int:
        """Serve ``requests`` (RAGGED prompt lengths and wildly
        different ``.max_new_tokens`` welcome) through the slots,
        calling ``emit(rid, tokens, status)`` the moment each finishes —
        completion order, mid-batch.  Returns the number of emissions.

        A request may carry an absolute ``.deadline`` (on ``clock``'s
        timeline; default ``time.monotonic`` — tests inject a fake
        clock for determinism).  A request whose deadline has already
        passed at admission is SHED: emitted immediately with
        ``status="timed_out"`` and no tokens, never touching a slot
        (``stats["shed"]``).  A slot whose occupant's deadline passes
        mid-decode is EVICTED after the current segment: its partial
        tokens emit with ``status="timed_out"`` and the KV slot is
        freed for the next queued request through the ordinary refill
        path — or retired in place when the queue is empty
        (``stats["evicted"]``).  No deadline → the request always runs
        to EOS or budget (``status="ok"``).

        Preemption recovery (DESIGN.md §Recovery): with ``recovery=``
        (a :class:`repro.resilience.recovery.RecoveryConfig`) every
        emission is write-ahead journaled (fsync'd, CRC-framed) BEFORE
        the ``emit`` callback runs, and the whole in-flight serve state
        — see :meth:`snapshot` — publishes atomically every
        ``snapshot_every`` segments.  ``resume=True`` restarts a killed
        run: the journal replays pre-crash emissions (each ``rid``
        suppressed from re-emission — rids must be unique per request,
        they are the exactly-once key), snapshotted in-flight decodes
        re-seat into slots and continue mid-generation (on a pool of
        ANY slot count — elastic resume; extras wait their turn ahead
        of the queue), queued requests re-queue ahead of new ones, and
        deadlines re-anchor to this process's clock from their stored
        remaining time.  ``on_segment`` is called with the cumulative
        segment count at every segment boundary — the seam
        ``FaultPlan.preempt_hook`` kills through.

        ``chained=True`` switches the dispatcher to the chained
        pipeline (the serve twin of the farm tier's device-resident
        dispatch): segment t+1 is dispatched BEFORE segment t's
        done/token metadata is read back, so the per-segment
        admission/eviction round trip comes off the device's critical
        path.  Admissions land on the latest carry and therefore LAG
        one in-flight segment — a freed slot idles one extra segment
        before its next occupant decodes (counted in
        ``idle_slot_steps``), the price of never blocking the chain.
        Snapshot boundaries drain the pipeline explicitly; emission
        order, exactly-once and token bit-identity match the
        synchronous path.
        """
        clock = time.monotonic if clock is None else clock
        t_resume0 = time.perf_counter()
        if self._resume_state is None and recovery is not None and resume:
            from repro.resilience.recovery import load_snapshot
            st = load_snapshot(recovery.snap_dir)
            if st is not None:
                self.restore(st)    # validates kind / version / cap / S0
        state = None
        if self._resume_state is not None:
            state, self._resume_state = self._resume_state, None

        cap = self.gcfg.max_new_tokens
        journal = None
        emitted_pre: set = set()
        n_emit = 0

        def deliver(rid, tokens, status, journal_rec=True):
            """WAL-ordered emission: journal (fsync'd) FIRST, then the
            ``emit`` callback — a crash between the two re-delivers
            from the journal on resume, never re-decodes."""
            nonlocal n_emit
            if journal is not None and journal_rec:
                journal.append({"rid": rid,
                                "tokens": [int(x) for x in tokens],
                                "status": status})
            emit(rid, tokens, status)
            n_emit += 1

        if recovery is not None and resume:
            from repro.resilience.recovery import Journal
            for rec in Journal.replay(recovery.journal_path):
                rid = rec["rid"]
                if rid in emitted_pre:
                    continue
                emitted_pre.add(rid)
                deliver(rid, np.asarray(rec["tokens"], np.int32),
                        rec.get("status", "ok"), journal_rec=False)
                self.stats["replayed_items"] += 1
        if recovery is not None:
            from repro.resilience.recovery import Journal
            journal = Journal(recovery.journal_path,
                              fsync=recovery.fsync)

        queue = list(requests)
        restore_q: list = []
        if state is not None:
            # segment counter restores so snapshot step numbering (and
            # preempt thresholds) stay monotonic across restarts; the
            # prefill counter is the admission-key cursor — restoring
            # it makes post-resume admissions sample the same keys an
            # uninterrupted run would
            self.stats["segments"] = int(state.get("segments", 0))
            self.stats["prefills"] = int(state.get("prefills", 0))
            restore_q = [dict(e) for e in state.get("occupants") or ()]
            now0 = clock()
            requeued = []
            for q in state.get("queue") or ():
                rem = q.get("deadline_remaining")
                requeued.append(_RestoredRequest(
                    rid=q["rid"],
                    prompt=np.asarray(q["prompt"], np.int32),
                    max_new_tokens=q.get("max_new_tokens"),
                    deadline=(now0 + float(rem)) if rem is not None
                    else None))
            queue = requeued + queue    # pre-crash admissions first
        if not queue and not restore_q:
            if journal is not None:
                journal.close()
            if state is not None or resume:
                self.stats["recovery_seconds"] += (
                    time.perf_counter() - t_resume0)
            return n_emit
        lens = [len(r.prompt) for r in queue]
        if state is not None:
            bound = int(state["S0"])
            if self.max_prompt_len and self.max_prompt_len != bound:
                raise ValueError(
                    f"engine max_prompt_len={self.max_prompt_len} != "
                    f"snapshot prompt width {bound} (the restored cache "
                    "slices carry the snapshotting pool's width)")
        else:
            bound = (self._S0 if self._bound
                     else (self.max_prompt_len or max(lens, default=1)))
        for r, L in zip(queue, lens):
            if not 1 <= L <= bound:
                raise ValueError(
                    f"prompt length {L} outside [1, max_prompt_len="
                    f"{bound}] (the slot pool's bound prompt width; "
                    "build the engine with a larger max_prompt_len)")
            request_budget(r, cap)
        if any(L != bound for L in lens) and _arch_has_ssm(self.cfg):
            raise ValueError(
                "ragged prompts need an attention-only stack (an SSM "
                "layer's state update is sequential — a pad token would "
                "corrupt it); group requests by exact prompt length "
                "upstream, as Batcher.run_continuous does for SSM archs")
        if not self._bound:
            self._bind(bound)
        queue = queue[::-1]                     # pop() = FIFO order
        caches, out, done = self._caches, self._out, self._done
        t, budget, keys = self._t, self._budget, self._keys
        plens = self._plen
        pfx_def = jax.tree.structure(caches["prefix"])
        unit_def = jax.tree.structure(caches["unit"])
        occupants = [None] * self.slots
        base_key = jax.random.PRNGKey(self.gcfg.seed)
        prev_t = np.asarray(t).astype(np.int64)

        def deadline_of(req):
            return getattr(req, "deadline", None)

        def pull():
            """Next admissible request — requests already past their
            deadline are shed here, without ever touching a slot, and
            requests whose emission was journaled pre-crash are dropped
            (the replay already re-delivered them)."""
            while queue:
                req = queue.pop()
                if req.rid in emitted_pre:
                    continue
                dl = deadline_of(req)
                if dl is not None and clock() >= dl:
                    deliver(req.rid, np.zeros((0,), np.int32),
                            "timed_out")
                    self.stats["shed"] += 1
                    self.stats["requests"] += 1
                    continue
                return req
            return None

        prefill_fn = (self._chain_prefill_fn if chained
                      else self._prefill_fn)
        restore_fn = (self._chain_restore_fn if chained
                      else self._restore_slot_fn)

        def admit(slot, req):
            nonlocal caches, out, done, t, budget, keys, plens
            bud = request_budget(req, cap)
            ptoks = np.asarray(req.prompt, np.int32)
            prompt = np.zeros((self._S0,), np.int32)    # right-padded
            prompt[:len(ptoks)] = ptoks
            key = jax.random.fold_in(base_key, self.stats["prefills"])
            (caches, out, done, t, budget, keys,
             plens) = prefill_fn(
                self.params, caches, out, done, t, budget, keys, plens,
                jnp.asarray(slot, jnp.int32), jnp.asarray(prompt),
                jnp.asarray(len(ptoks), jnp.int32),
                jnp.asarray(bud, jnp.int32), key)
            occupants[slot] = req
            prev_t[slot] = 1            # the prefilled first token is
                                        # not a segment step
            self.stats["prefills"] += 1
            self.stats["requests"] += 1

        def fill(slot):
            """Seat the next unit of work into a free slot: snapshotted
            in-flight decodes first (they re-enter mid-generation,
            whatever slot index they held pre-crash), then the queue.
            Returns False when there is nothing left to seat."""
            nonlocal caches, out, done, t, budget, keys, plens
            while restore_q:
                e = restore_q.pop(0)
                if e["rid"] in emitted_pre:
                    continue
                rem = e.get("deadline_remaining")
                req = _RestoredRequest(
                    rid=e["rid"],
                    prompt=np.asarray(e["prompt"], np.int32),
                    max_new_tokens=e.get("max_new_tokens"),
                    deadline=(clock() + float(rem)) if rem is not None
                    else None)
                pfx = jax.tree.unflatten(
                    pfx_def, [jnp.asarray(l) for l in e["prefix"]])
                unit = jax.tree.unflatten(
                    unit_def, [jnp.asarray(l) for l in e["unit"]])
                (caches, out, done, t, budget, keys,
                 plens) = restore_fn(
                    caches, out, done, t, budget, keys, plens,
                    jnp.asarray(slot, jnp.int32), pfx, unit,
                    jnp.asarray(e["out"], jnp.int32),
                    jnp.asarray(bool(e["done"])),
                    jnp.asarray(int(e["t"]), jnp.int32),
                    jnp.asarray(int(e["budget"]), jnp.int32),
                    jnp.asarray(e["key"], jnp.uint32),
                    jnp.asarray(int(e["plen"]), jnp.int32))
                occupants[slot] = req
                prev_t[slot] = int(e["t"])
                self.stats["recovered_occupants"] += 1
                return True
            req = pull()
            if req is None:
                return False
            admit(slot, req)
            return True

        def capture(complete=None):
            """Build the :meth:`snapshot` tree from the live run state
            (the slot reader is un-donated — the pool stays intact)."""
            out_h = np.asarray(out)
            done_h = np.asarray(done)
            t_h = np.asarray(t).astype(np.int64)
            bud_h = np.asarray(budget)
            keys_h = np.asarray(keys)
            plen_h = np.asarray(plens)
            now = clock()
            occ = []
            for s in range(self.slots):
                req = occupants[s]
                if req is None:
                    continue
                pfx, unit = self._snap_slot_fn(
                    caches, jnp.asarray(s, jnp.int32))
                dl = deadline_of(req)
                occ.append({
                    "rid": req.rid,
                    "prompt": np.asarray(req.prompt, np.int32),
                    "max_new_tokens": getattr(req, "max_new_tokens",
                                              None),
                    "deadline_remaining": (float(dl - now)
                                           if dl is not None else None),
                    "done": bool(done_h[s]), "out": out_h[s].copy(),
                    "t": int(t_h[s]), "budget": int(bud_h[s]),
                    "key": keys_h[s].copy(), "plen": int(plen_h[s]),
                    "prefix": [np.asarray(l)
                               for l in jax.tree.leaves(pfx)],
                    "unit": [np.asarray(l)
                             for l in jax.tree.leaves(unit)]})
            # in-flight decodes a SMALLER resumed pool has not re-seated
            # yet survive verbatim — their slices are still topology-free
            occ.extend(restore_q)
            qs = []
            for req in reversed(queue):         # stored in FIFO order
                dl = deadline_of(req)
                qs.append({
                    "rid": req.rid,
                    "prompt": np.asarray(req.prompt, np.int32),
                    "max_new_tokens": getattr(req, "max_new_tokens",
                                              None),
                    "deadline_remaining": (float(dl - now)
                                           if dl is not None else None)})
            if complete is None:
                complete = not occ and not qs
            return {"kind": "serve", "version": 1,
                    "S0": int(self._S0), "cap": int(cap),
                    "segments": int(self.stats["segments"]),
                    "prefills": int(self.stats["prefills"]),
                    "occupants": occ, "queue": qs,
                    "complete": bool(complete)}

        self._rt_capture = capture

        def persist(complete=None):
            if recovery is None:
                return
            from repro.resilience.recovery import save_snapshot
            save_snapshot(recovery.snap_dir, self.stats["segments"],
                          capture(complete), keep=recovery.keep)
            self.stats["snapshots"] += 1

        def run_chained():
            """The serve twin of the farm tier's chained dispatch:
            segment t+1 dispatches BEFORE segment t's metadata is read,
            so the admission/eviction round trip runs while the device
            decodes.  Seating lands on the LATEST carry — an occupant
            seated during the drain of segment t was not in segment
            t+1's flight, so every in-flight capture carries its
            dispatch ordinal and the drain skips slots whose occupant
            was seated at or after it (``seated_at`` epoch guard: the
            captured done/t/out rows there belong to the previous
            occupant)."""
            nonlocal caches, out, done, t, budget, keys, plens, prev_t
            from collections import deque
            inflight: deque = deque()   # (ordinal, done, t, out, steps)
            seated_at = np.zeros((self.slots,), np.int64)
            ndisp = 0

            def dispatch():
                nonlocal caches, out, done, t, budget, keys, plens
                nonlocal ndisp
                (caches, out, done, t, budget, keys, plens,
                 steps) = self._chain_seg_fn(self.params, caches, out,
                                             done, t, budget, keys,
                                             plens)
                ndisp += 1
                self.stats["segments"] += 1
                if on_segment is not None:
                    # the same preemption window as the classic loop:
                    # compute in flight, nothing delivered yet
                    on_segment(self.stats["segments"])
                inflight.append((ndisp, done, t, out, steps))

            def drain_one():
                nonlocal prev_t, done
                d, done_d, t_d, out_d, steps_d = inflight.popleft()
                done_h, t_h, out_h, steps_h = jax.device_get(
                    (done_d, t_d, out_d, steps_d))
                t_h = t_h.astype(np.int64)
                valid = seated_at < d
                steps_i = int(steps_h)
                self.stats["slot_steps"] += steps_i * self.slots
                useful = int((t_h - prev_t)[valid].sum())
                self.stats["idle_slot_steps"] += \
                    steps_i * self.slots - useful
                prev_t = np.where(valid, t_h, prev_t)
                now = clock()
                for slot in range(self.slots):
                    req = occupants[slot]
                    if req is None or not valid[slot]:
                        continue
                    if done_h[slot]:
                        deliver(req.rid,
                                out_h[slot, :int(t_h[slot])].copy(),
                                "ok")
                        self.stats["emitted"] += 1
                        occupants[slot] = None
                        if fill(slot):
                            seated_at[slot] = ndisp
                        continue
                    dl = deadline_of(req)
                    if dl is not None and now >= dl:
                        deliver(req.rid,
                                out_h[slot, :int(t_h[slot])].copy(),
                                "timed_out")
                        self.stats["evicted"] += 1
                        occupants[slot] = None
                        if fill(slot):
                            seated_at[slot] = ndisp
                        else:
                            done = self._chain_retire_fn(
                                done, jnp.asarray(slot, jnp.int32))

            while True:
                work = any(o is not None for o in occupants)
                if not work and not inflight:
                    break
                if work:
                    dispatch()
                # lag-1 drain: with a fresh dispatch in flight, consume
                # only the PREVIOUS segment — the metadata read overlaps
                # the device's current segment.  At the tail, flush.
                if len(inflight) > (1 if work else 0):
                    drain_one()
                if work and recovery is not None and \
                        self.stats["segments"] % \
                        recovery.snapshot_every == 0:
                    # snapshot boundary: ONE explicit pipeline drain —
                    # the capture below then reads a carry every
                    # seating has landed on
                    while inflight:
                        drain_one()
                    persist()

        try:
            for slot in range(self.slots):
                if not fill(slot):
                    break
            persist(complete=False)   # RPO anchor: recoverable before
                                      # the first segment even starts
            if state is not None or resume:
                self.stats["recovery_seconds"] += (
                    time.perf_counter() - t_resume0)

            if chained:
                run_chained()
            else:
                while any(o is not None for o in occupants):
                    (caches, out, done, t, budget, keys, plens,
                     steps) = self._segment_fn(self.params, caches, out,
                                               done, t, budget, keys,
                                               plens)
                    self.stats["segments"] += 1
                    if on_segment is not None:
                        # BEFORE emission — the harshest preemption
                        # window: compute done, nothing delivered (the
                        # journal replay + snapshot redo cover exactly
                        # this gap)
                        on_segment(self.stats["segments"])
                    done_h = np.asarray(done)
                    t_h = np.asarray(t).astype(np.int64)
                    out_h = np.asarray(out)
                    # idle-slot accounting (the wasted_lane_steps
                    # analogue): each body step advances every LIVE
                    # slot one token; retired/done-masked slots burn
                    # the step
                    steps_h = int(steps)
                    useful = int((t_h - prev_t).sum())
                    self.stats["slot_steps"] += steps_h * self.slots
                    self.stats["idle_slot_steps"] += \
                        steps_h * self.slots - useful
                    prev_t = t_h.copy()
                    now = clock()
                    for slot in range(self.slots):
                        req = occupants[slot]
                        if req is None:
                            continue
                        if done_h[slot]:
                            deliver(req.rid,
                                    out_h[slot, :int(t_h[slot])].copy(),
                                    "ok")
                            self.stats["emitted"] += 1
                            occupants[slot] = None
                            fill(slot)
                            continue
                        dl = deadline_of(req)
                        if dl is not None and now >= dl:
                            # deadline eviction: the partial output
                            # emits now and the KV slot is freed
                            # mid-batch — the next request prefills
                            # over it (the ordinary refill path evicts
                            # the stale keys wholesale), or the slot
                            # retires in place
                            deliver(req.rid,
                                    out_h[slot, :int(t_h[slot])].copy(),
                                    "timed_out")
                            self.stats["evicted"] += 1
                            occupants[slot] = None
                            if not fill(slot):
                                done = self._retire_fn(
                                    done, jnp.asarray(slot, jnp.int32))
                    if recovery is not None and \
                            self.stats["segments"] % \
                            recovery.snapshot_every == 0:
                        persist()
            persist(complete=True)
        finally:
            # locals always name the LIVE buffers (the donated inputs
            # were consumed by the calls that produced these), so a
            # raising emit callback cannot strand the engine on deleted
            # device buffers
            self._caches, self._out, self._done = caches, out, done
            self._t, self._budget, self._keys = t, budget, keys
            self._plen = plens
            if journal is not None:
                journal.close()
        return n_emit
