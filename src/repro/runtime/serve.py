"""Serving runtime: device-resident continuous batching over the EMPA pool.

The KV-cache slot pool *is* the paper's core pool: a request is a QT, a
cache slot is a core — rented on admission, returned at EOS (§4.3's
rent/terminate cycle), preallocation reserves slots for a stream of
requests (§5.1).  The refactor pushed the supervisor onto the device:

* per-slot decode state (last token, emitted count, budget, active mask)
  lives on device as a :class:`DecodeState`;
* one jitted **decode chunk** (`build_decode_chunk`) advances every active
  slot up to ``chunk`` tokens inside a single ``lax.while_loop`` — greedy
  argmax, EOS/max-new retirement and the active mask are all computed on
  device, so the host syncs once per chunk instead of once per slot per
  tick;
* admission packs every rentable pending prompt into one right-padded
  batched prefill (`build_admit_step`) that scatters prompt caches into
  the rented slots — one compiled call per admission round, not one per
  request.

**Paged mode** (``ServingEngine(paged=True)``) applies the same rent /
release discipline one level down: the rented resource is a fixed-size
KV *block* (runtime/paging.py), so a slot's cache cost is proportional
to its actual sequence, not to ``max_seq``:

* admission rents ``ceil(len/block)`` blocks and *reserves* (the paper's
  §5.1 preallocation, as host accounting) the worst-case remainder, so
  decode growth can never starve mid-flight;
* identical prompt-prefix blocks are shared through a host-side hash
  map with device refcounts — rented once, referenced by many chains;
* inside the jitted chunk, slots crossing a block boundary rent one
  block each through a single vectorized ``pool.rent_many`` — no host
  sync;
* retirement releases the whole chain; refcount-zero blocks return to
  the pool.

**Chunked prefill** (``ServingEngine(chunked_prefill=True)``) applies the
paper's *fragment outsourcing* to prompts: a core never receives its
whole job at once — the supervisor feeds it fragments as capacity
appears (the companion EMPA paper's quasi-thread discipline).  Instead
of one monolithic admission prefill (which stalls every active decode
slot behind the longest prompt and compiles one variant per pow2 length
bucket), an admitted slot enters ``PHASE_PREFILL`` and the **unified
mixed tick** (`build_mixed_tick`) advances all slots together:

* a PREFILLING slot consumes one prompt fragment (≤ ``prefill_chunk_
  tokens``), written into its cache at its position offset;
* a DECODING slot advances one token — the *same* ``model.prefill_
  chunk`` forward treats it as a length-1 fragment;
* paged chains rent blocks chunk-granularly as fragments land
  (`paging.extend_chains`), never faster — the §5.1 worst-case
  reservation is still taken at admission, so lazy growth cannot
  starve; a fully-written shared prefix is skipped, not recomputed;
* one compile total, one host sync per tick, per-tick latency bounded
  by one fragment — no head-of-line blocking, and the outputs stay
  token-exact vs monolithic admission.

**Speculative decoding** (``ServingEngine(speculative=True)``) applies
the paper's outsourcing pattern to the decode hot path itself: decode
is memory-bound at one token per forward, so a cheap *drafter core*
(`runtime/draft.py` — a device-resident n-gram matcher over each slot's
recent token stream) runs ahead and proposes up to ``spec_k`` candidate
tokens per DECODING slot, and the supervisor-coordinated **verify
forward** (`build_spec_tick`) scores all slots' draft fragments in one
``model.prefill_chunk`` call through the same position-offset causal
mask chunked prefill uses — on both cache layouts:

* acceptance takes the longest prefix where draft == argmax, plus the
  bonus token the forward produced anyway: 1..``spec_k + 1`` tokens per
  slot per forward, **bit-exact** vs non-speculative greedy decode (a
  wrong draft costs speculated work, never a wrong token);
* ``cache["pos"]`` rewinds past rejected drafts; the speculatively
  written KV rows/pages are left dead — overwritten by the next
  fragment's write-then-attend before the mask can read them, and paged
  chains stay inside the admission-time §5.1 worst-case reservation, so
  speculation adds no stall mode;
* PREFILLING slots keep consuming prompt fragments in the same tick —
  speculation composes with chunked prefill.

**Preemptive over-commit** (``ServingEngine(overcommit=True)``) is the
supervisor's rent/release discipline under pressure: instead of taking
the §5.1 worst-case block reservation at admission (which caps
occupancy at what the pool could serve if *every* slot grew to its full
budget), admission asks only for what the request needs *now* and the
supervisor claws blocks back mid-flight when growth runs the pool dry:

* when ``extend_chains`` / ``grow_to_cover`` would stall a tick, the
  host loop picks a **victim** — the slot with the fewest generated
  tokens, ties broken toward the latest admission — and evicts it:
  ``paging.evict_chain`` drops the chain (refcount-aware: shared prefix
  blocks another chain references survive), the drafter window resets,
  and the request parks in ``PHASE_PREEMPTED`` with its full token
  history (prompt + everything generated so far);
* a parked request **resumes** through the existing chunked-prefill
  path: its replay stream (prompt + generated-so-far) is outsourced
  fragment by fragment, and greedy determinism makes the recompute
  replay the stream token-exactly — the final fragment's argmax *is*
  the token the request was about to decode, so resumption re-emits
  nothing and continues bit-exact on both cache layouts, greedy and
  speculative alike;
* progress is guaranteed: the last non-preempted slot is never evicted
  and admission rejects requests whose worst-case chain exceeds the
  whole pool, so the maximal-progress request always runs to
  retirement and frees its chain.

Host Python keeps only what must be host-side: the rent/return ledger
(`core/supervisor.CorePool`, itself a thin wrapper over the same jittable
`runtime/pool` transitions), the prefix-hash map, the per-slot fragment
cursors, the re-admission queue, and the request queue.

**Spans.** Each ``step()`` records its host phases as
``jax.profiler.TraceAnnotation`` spans, which an active profiler puts
on the device trace's clock (and which cost about a microsecond each
when none is): ``serve.tick`` around the whole step (``i``: ticks
run), ``serve.admit`` (``queued``: frontier length), ``serve.schedule``,
``serve.dispatch`` (the uploads and the jitted call; ``family``,
``decode_rows``, ``frag_tokens``, ``kv_pages``), ``serve.sync`` (the one
``jax.device_get``; ``compiles``: programs compiled during the
dispatch), ``serve.emit``, ``serve.preempt`` and ``serve.epilogue``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis import manifest as audit_manifest
from repro.configs.base import ArchConfig
from repro.core.supervisor import CorePool
from repro.models import model as model_lib
from repro.models.model import PagedLayout
from repro.runtime import draft as draft_lib
from repro.runtime import faults as faults_lib
from repro.runtime import paging
from repro.runtime import pool as pool_lib
from repro.runtime.accounting import TierAccounting
from repro.runtime.sharding import ShardingRules, use_rules

NO_TOKEN = -1          # emitted-buffer sentinel: slot idle this iteration

# families whose prefill is exact under right-padding (causal attention);
# recurrent state (ssm/hybrid) would absorb pad tokens, so those admit
# one exact-length prompt per prefill call instead of a padded pack
PACKED_PREFILL_FAMILIES = ("dense", "moe", "vlm")

# Programs the backend compiled in this process, and the seconds that
# took, fed by one jax.monitoring listener.  A load from the persistent
# compilation cache is timed inside the same backend-compile event (its
# own retrieval event nests in it), so this one event counts both.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiled = [0, 0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compiled[0] += 1
        _compiled[1] += duration


jax.monitoring.register_event_duration_secs_listener(_count_compile)


def build_prefill_step(cfg: ArchConfig, max_seq: int,
                       rules: Optional[ShardingRules] = None):
    def prefill_step(params, batch):
        with use_rules(rules):
            return model_lib.prefill(params, batch, cfg, max_seq)
    return prefill_step


def build_decode_step(cfg: ArchConfig,
                      rules: Optional[ShardingRules] = None):
    def decode_step(params, token, cache):
        with use_rules(rules):
            return model_lib.decode_step(params, token, cache, cfg)
    return decode_step


def _register_jit_site(fn, *, family: str, jit: bool,
                       paged: Optional[PagedLayout],
                       donate_state: dict, static_keys=()):
    """Single finishing step for every tick builder: register the site
    with the static auditor's manifest, then jit with the donation list.

    The contiguous/paged wrapper pairs that used to close each builder
    (``if not jit: return fn`` / ``return jax.jit(fn, donate_argnums=
    ...)``) collapse here: the two variants differ only in which
    argnums carry donated persistent state, and that mapping
    (``donate_state``: argnum -> buffer name) is exactly the meta-info
    ``python -m repro.analysis.audit`` needs to prove donation coverage
    and enumerate the retrace-key space — so declaring it IS publishing
    it.  Registration happens even for ``jit=False`` builds (the
    cluster supervisor re-jits with explicit shardings but the donation
    contract is the same).
    """
    layout = "contiguous" if paged is None else "paged"
    donate = tuple(sorted(donate_state))
    audit_manifest.register_site(audit_manifest.JitSite(
        name=f"{family}/{layout}", family=family, layout=layout,
        donate_argnums=donate, state_args=dict(donate_state),
        static_keys=tuple(static_keys)))
    if not jit:
        return fn
    return jax.jit(fn, donate_argnums=donate)


# ---------------------------------------------------------------------------
# Device-resident decode state + jitted transitions
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """Per-slot decode supervisor state; every field is (n_slots,)."""

    tokens: jax.Array    # int32 — last emitted token (decode input)
    n_out: jax.Array     # int32 — tokens emitted so far (incl. prefill's)
    max_new: jax.Array   # int32 — per-request budget
    active: jax.Array    # bool — slot is decoding


def init_decode_state(n_slots: int) -> DecodeState:
    return DecodeState(tokens=jnp.zeros((n_slots,), jnp.int32),
                       n_out=jnp.zeros((n_slots,), jnp.int32),
                       max_new=jnp.zeros((n_slots,), jnp.int32),
                       active=jnp.zeros((n_slots,), bool))


def abstract_decode_state(n_slots: int) -> DecodeState:
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        init_decode_state(n_slots))


def _merge_rows(new, old, keep_new):
    """Per-slot select between two cache leaves (batch axis 0 for `pos`,
    axis 1 for layer-stacked leaves — same convention as init_cache)."""
    if new.ndim == 1:
        return jnp.where(keep_new, new, old)
    shape = [1] * new.ndim
    shape[1] = -1
    return jnp.where(keep_new.reshape(shape), new, old)


def build_decode_chunk(cfg: ArchConfig, *, chunk: int, eos_id: int,
                       rules: Optional[ShardingRules] = None,
                       decode_fn: Optional[Callable] = None,
                       jit: bool = True,
                       paged: Optional[PagedLayout] = None):
    """Jitted multi-token decode tick: one host round-trip per `chunk`.

    Contiguous: fn(params, state, cache) -> (state, cache, emitted,
    iters).  Paged: fn(params, state, cache, bstate) -> (state, cache,
    bstate, emitted, iters, stalls) — each loop iteration first grows
    block chains on device (`paging.grow_for_decode`), then decodes.
    `emitted` is (n_slots, chunk) int32 (NO_TOKEN for idle cells),
    `iters` counts executed loop iterations (early exit when every slot
    retires) and `stalls` counts slot-iterations that could not advance
    because the block pool ran dry — zero under the engine's
    admission-time reservation, and the pressure signal the over-commit
    supervisor evicts on (a stalled slot stays active and resumes once
    a chain is clawed back).
    The cache (and block state) is donated: the engine decodes in place.
    """
    decode = decode_fn or build_decode_step(cfg, rules)

    def advance(params, st: DecodeState, cache, active, i, emitted):
        """One decode step over every row + retirement bookkeeping."""
        pos0 = cache["pos"]
        logits, new_cache = decode(params, st.tokens, cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # a retired slot keeps its last token and frozen cache rows /
        # pages: it can never perturb an active one
        tok = jnp.where(active, nxt, st.tokens)
        n_out = st.n_out + active.astype(jnp.int32)
        if paged is None:
            cache = jax.tree_util.tree_map(
                lambda a, b: _merge_rows(a, b, active), new_cache, cache)
        else:
            # pages are disjoint per chain: an inactive row's write is
            # either dropped (released chain) or rewrites its own cell
            # with the identical value — only per-row leaves need merge
            cache = dict(new_cache,
                         pos=jnp.where(active, new_cache["pos"], pos0))
        emitted = emitted.at[:, i].set(jnp.where(active, tok, NO_TOKEN))
        retire = active & ((tok == eos_id) | (n_out >= st.max_new))
        # a slot excluded from `active` by a block-pool stall stays in
        # st.active: it simply didn't advance this iteration, and the
        # over-commit supervisor relieves the pressure at the next sync
        # (eviction) — deactivating it here would silently truncate it
        return DecodeState(tok, n_out, st.max_new, st.active & ~retire), \
            cache, emitted

    if paged is None:
        def chunk_fn(params, state: DecodeState, cache):
            n = state.tokens.shape[0]
            emitted0 = jnp.full((n, chunk), NO_TOKEN, jnp.int32)

            def cond(carry):
                i, st, _, _ = carry
                return (i < chunk) & jnp.any(st.active)

            def body(carry):
                i, st, cache, emitted = carry
                st, cache, emitted = advance(params, st, cache, st.active,
                                             i, emitted)
                return i + jnp.int32(1), st, cache, emitted

            iters, state, cache, emitted = jax.lax.while_loop(
                cond, body, (jnp.int32(0), state, cache, emitted0))
            return state, cache, emitted, iters

        return _register_jit_site(
            chunk_fn, family="decode_chunk", jit=jit, paged=paged,
            donate_state={2: "cache"}, static_keys=(("chunk", chunk),))

    def chunk_fn_paged(params, state: DecodeState, cache, bstate):
        n = state.tokens.shape[0]
        emitted0 = jnp.full((n, chunk), NO_TOKEN, jnp.int32)

        def cond(carry):
            i, st, _, _, _, _ = carry
            return (i < chunk) & jnp.any(st.active)

        def body(carry):
            i, st, cache, bstate, emitted, stalls = carry
            # rent one block per slot crossing a block boundary — the
            # supervisor action happens on device, no host round-trip
            bstate, tables, stalled = paging.grow_for_decode(
                bstate, cache["block_tables"], cache["pos"], st.active,
                block_size=paged.block_size)
            active = st.active & ~stalled
            stalls = stalls + jnp.sum(stalled).astype(jnp.int32)
            cache = dict(cache, block_tables=tables)
            st, cache, emitted = advance(params, st, cache, active, i,
                                         emitted)
            return i + jnp.int32(1), st, cache, bstate, emitted, stalls

        iters, state, cache, bstate, emitted, stalls = jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), state, cache, bstate, emitted0, jnp.int32(0)))
        return state, cache, bstate, emitted, iters, stalls

    return _register_jit_site(
        chunk_fn_paged, family="decode_chunk", jit=jit, paged=paged,
        donate_state={2: "cache", 3: "bstate"},
        static_keys=(("chunk", chunk),))


def build_mixed_tick(cfg: ArchConfig, *, chunk_tokens: int, eos_id: int,
                     rules: Optional[ShardingRules] = None,
                     jit: bool = True,
                     paged: Optional[PagedLayout] = None):
    """Jitted unified prefill/decode tick (the fragment-outsourcing step).

    One call advances *every* rented slot exactly one quantum: a slot in
    ``PHASE_PREFILL`` consumes its next prompt fragment (up to
    ``chunk_tokens`` tokens, written into the cache at its position
    offset), a slot in ``PHASE_DECODE`` advances one token — both through
    the same ``model.prefill_chunk`` forward, where a decode step is just
    a length-1 fragment.  One compile (no per-prompt-length buckets), one
    host sync per tick, per-tick latency bounded by one fragment's cost.

    Contiguous: ``fn(params, state, cache, frag_tokens (n, C), frag_len
    (n,), frag_last (n,), frag_max_new (n,)) -> (state, cache, emitted
    (n, 1))``.  ``emitted`` carries the decode token per active slot and
    the *first* token for rows whose final fragment just ran (the prefill
    argmax), ``NO_TOKEN`` elsewhere.

    Paged: ``fn(params, state, cache, bstate, frag_tokens, frag_len,
    frag_last, frag_max_new, frag_skip, frag_cols, frag_rent) -> (state,
    cache, bstate, emitted, stalls)``.  ``frag_rent``/``frag_cols``
    commit this tick's chunk-granular block rents
    (:func:`paging.extend_chains` — host-picked, reservation-backed),
    ``frag_skip`` fences writes below it (shared prefix blocks an
    earlier chain already stored), and decode rows still grow their
    chains on device via :func:`paging.grow_for_decode`.

    The cache (and block state) is donated: the engine ticks in place.
    """

    def run(params, state: DecodeState, cache, decode_rows, frag_tokens,
            frag_len, frag_last, frag_max_new, frag_skip):
        """Shared tail: one prefill_chunk forward + QT bookkeeping."""
        # trace-time check: the compiled width IS the fragment width
        assert frag_tokens.shape[1] == chunk_tokens, \
            (frag_tokens.shape, chunk_tokens)
        # a decoding slot is a length-1 fragment whose token lives in
        # device state; a prefilling slot's fragment comes from the host
        first_col = jnp.where(decode_rows, state.tokens, frag_tokens[:, 0])
        tokens = jnp.concatenate([first_col[:, None], frag_tokens[:, 1:]],
                                 axis=1)
        lengths = jnp.where(decode_rows, 1, frag_len)
        with use_rules(rules):
            logits, cache = model_lib.prefill_chunk(
                params, tokens, lengths, cache, cfg, skip_until=frag_skip)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        prefill_rows = frag_len > 0
        done_pref = prefill_rows & frag_last
        emit = decode_rows | done_pref
        tok = jnp.where(emit, nxt, state.tokens)
        n_out = jnp.where(done_pref, 1,
                          state.n_out + decode_rows.astype(jnp.int32))
        max_new = jnp.where(done_pref, frag_max_new, state.max_new)
        # same retirement rule as the decode chunk; like monolithic
        # admission, the first token is emitted without an EOS check and
        # a budget of 1 is already spent by it.  A stalled decode row
        # (in state.active but not decode_rows) stays active — it didn't
        # advance, and deactivating it would silently truncate it.
        retire = decode_rows & ((tok == eos_id) | (n_out >= max_new))
        active = (state.active & ~retire) | (done_pref & (max_new > 1))
        emitted = jnp.where(emit, tok, NO_TOKEN)[:, None]
        return DecodeState(tok, n_out, max_new, active), cache, emitted

    if paged is None:
        def tick(params, state: DecodeState, cache, frag_tokens, frag_len,
                 frag_last, frag_max_new):
            frag_skip = jnp.zeros_like(frag_len)
            return run(params, state, cache, state.active, frag_tokens,
                       frag_len, frag_last, frag_max_new, frag_skip)

        return _register_jit_site(
            tick, family="mixed_tick", jit=jit, paged=paged,
            donate_state={2: "cache"},
            static_keys=(("chunk_tokens", chunk_tokens),))

    def tick_paged(params, state: DecodeState, cache, bstate, frag_tokens,
                   frag_len, frag_last, frag_max_new, frag_skip, frag_cols,
                   frag_rent):
        # 1. commit this tick's fragment blocks (host-picked, cannot
        #    stall under the §5.1 reservation)
        bstate, tables = paging.extend_chains(
            bstate, cache["block_tables"], frag_cols, frag_rent)
        # 2. decode rows crossing a block boundary rent on device
        bstate, tables, stalled = paging.grow_for_decode(
            bstate, tables, cache["pos"], state.active,
            block_size=paged.block_size)
        decode_rows = state.active & ~stalled
        stalls = jnp.sum(stalled).astype(jnp.int32)
        cache = dict(cache, block_tables=tables)
        state, cache, emitted = run(params, state, cache, decode_rows,
                                    frag_tokens, frag_len, frag_last,
                                    frag_max_new, frag_skip)
        return state, cache, bstate, emitted, stalls

    return _register_jit_site(
        tick_paged, family="mixed_tick", jit=jit, paged=paged,
        donate_state={2: "cache", 3: "bstate"},
        static_keys=(("chunk_tokens", chunk_tokens),))


def build_spec_tick(cfg: ArchConfig, *, spec_k: int, chunk_tokens: int,
                    eos_id: int, hist_len: int = 64,
                    rules: Optional[ShardingRules] = None,
                    jit: bool = True,
                    paged: Optional[PagedLayout] = None):
    """Jitted speculative decode tick: drafter cores run ahead, one
    verify forward accepts k tokens per slot.

    The paper's outsourcing pattern on the decode hot path: a cheap
    device-resident n-gram drafter (`runtime/draft.py`) proposes up to
    ``spec_k`` continuation tokens per DECODING slot, and a single
    ``model.prefill_chunk`` forward over the ``(n_slots, W)`` draft
    fragments (``W = chunk_tokens >= spec_k + 1``) scores every slot's
    candidates at once through the same position-offset causal mask the
    chunked-prefill machinery already uses — on both cache layouts.
    Acceptance takes the longest prefix where draft == argmax plus the
    one *bonus* token the verify forward produced anyway, so each
    forward emits between 1 (drafter whiffed — the status quo) and
    ``spec_k + 1`` tokens, and greedy argmax verification makes the
    output **bit-exact** vs non-speculative decode.

    Rollback: the fragment wrote K/V at ``pos0 .. pos0 + dlen``;
    ``cache["pos"]`` rewinds to ``pos0 + n_emit`` and the rows past it
    are left dead — the next fragment's write-then-attend overwrites
    them before the mask can read them, and (paged) the chain stays
    within the admission-time §5.1 worst-case reservation, so no new
    stall mode appears.

    Speculation composes with chunked prefill: PREFILLING slots keep
    consuming host-scheduled prompt fragments in the same tick, exactly
    as in :func:`build_mixed_tick`.

    Contiguous: ``fn(params, state, dstate, cache, frag_tokens (n, W),
    frag_len, frag_last, frag_max_new) -> (state, dstate, cache,
    emitted (n, W), drafted, accepted)``.  Paged adds ``bstate`` after
    ``cache`` plus ``frag_skip/frag_cols/frag_rent`` and returns a
    ``stalls`` scalar.  ``drafted``/``accepted`` are per-tick totals of
    proposed and accepted draft tokens (the acceptance-rate numerator /
    denominator).  The cache (and block state) is donated.
    """
    assert chunk_tokens >= spec_k + 1, (chunk_tokens, spec_k)
    W = chunk_tokens
    propose, _clamp, run = _spec_core(cfg, spec_k=spec_k, width=W,
                                      eos_id=eos_id, rules=rules)

    if paged is None:
        def tick(params, state: DecodeState, dstate, cache, frag_tokens,
                 frag_len, frag_last, frag_max_new):
            decode_rows = state.active
            draft, dlen = propose(state, dstate, decode_rows)
            frag_skip = jnp.zeros_like(frag_len)
            return run(params, state, dstate, cache, decode_rows, draft,
                       dlen, frag_tokens, frag_len, frag_last, frag_max_new,
                       frag_skip)[:6]

        return _register_jit_site(
            tick, family="spec_tick", jit=jit, paged=paged,
            donate_state={2: "dstate", 3: "cache"},
            static_keys=(("spec_k", spec_k), ("chunk_tokens", W)))

    def tick_paged(params, state: DecodeState, dstate, cache, bstate,
                   frag_tokens, frag_len, frag_last, frag_max_new,
                   frag_skip, frag_cols, frag_rent):
        # 1. commit this tick's prompt-fragment blocks (host-picked)
        bstate, tables = paging.extend_chains(
            bstate, cache["block_tables"], frag_cols, frag_rent)
        # 2. drafter proposal, then cover the whole verify fragment's
        #    write span — it may cross several block boundaries
        draft, dlen = propose(state, dstate, state.active)
        bstate, tables, stalled = paging.grow_to_cover(
            bstate, tables, cache["pos"] + dlen, state.active,
            block_size=paged.block_size,
            max_rounds=spec_k // paged.block_size + 1)
        decode_rows = state.active & ~stalled
        dlen = jnp.where(decode_rows, dlen, 0)
        stalls = jnp.sum(stalled).astype(jnp.int32)
        cache = dict(cache, block_tables=tables)
        state, dstate, cache, emitted, drafted, accepted = run(
            params, state, dstate, cache, decode_rows, draft, dlen,
            frag_tokens, frag_len, frag_last, frag_max_new, frag_skip)[:6]
        return state, dstate, cache, bstate, emitted, drafted, accepted, \
            stalls

    return _register_jit_site(
        tick_paged, family="spec_tick", jit=jit, paged=paged,
        donate_state={2: "dstate", 3: "cache", 4: "bstate"},
        static_keys=(("spec_k", spec_k), ("chunk_tokens", W)))


def _spec_core(cfg: ArchConfig, *, spec_k: int, width: int, eos_id: int,
               rules: Optional[ShardingRules]):
    """The draft/verify/accept core shared by the single spec tick
    (:func:`build_spec_tick`, which composes with prompt fragments) and
    the multi-iteration spec chunk (:func:`build_spec_chunk`).  Returns
    ``(propose, run)`` closures; ``run`` also hands back the *next*
    iteration's proposal (fused ``draft_lib.push_and_propose`` — the
    accept/rewind/re-propose cycle never leaves the device), which the
    spec-chunk loop carries and the single tick drops (XLA dead-codes
    the unused branch)."""
    W = width

    def propose(state: DecodeState, dstate: draft_lib.DraftState,
                decode_rows):
        draft, dlen = draft_lib.propose(dstate, state.tokens, spec_k)
        return draft, clamp(state, dlen, decode_rows)

    def clamp(state: DecodeState, dlen, decode_rows):
        # budget clamp: emitting dlen + 1 tokens must stay within
        # max_new, so the fragment's writes stay inside the §5.1
        # reservation (and max_seq) the engine took at admission.
        # Applied at *consumption* time against the then-current state —
        # a fused proposal carried from the previous iteration sees the
        # same cap the unfused re-proposal would have computed.
        cap = jnp.maximum(state.max_new - state.n_out - 1, 0)
        return jnp.where(decode_rows, jnp.minimum(dlen, cap), 0)

    def run(params, state: DecodeState, dstate, cache, decode_rows, draft,
            dlen, frag_tokens, frag_len, frag_last, frag_max_new,
            frag_skip):
        assert frag_tokens.shape[1] == W, (frag_tokens.shape, W)
        pos0 = cache["pos"]
        # fragment assembly: a decoding slot runs [pending token,
        # draft_1 .. draft_dlen]; a prefilling slot runs its
        # host-scheduled prompt fragment
        first_col = jnp.where(decode_rows, state.tokens, frag_tokens[:, 0])
        dec_tail = jnp.pad(draft, ((0, 0), (0, W - 1 - spec_k)))
        tail = jnp.where(decode_rows[:, None], dec_tail, frag_tokens[:, 1:])
        tokens = jnp.concatenate([first_col[:, None], tail], axis=1)
        lengths = jnp.where(decode_rows, 1 + dlen, frag_len)
        with use_rules(rules):
            logits, cache = model_lib.prefill_chunk(
                params, tokens, lengths, cache, cfg, skip_until=frag_skip,
                all_logits=True)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (n, W)

        # -- verify: longest accepted prefix + bonus token ----------------
        jcol = jnp.arange(spec_k, dtype=jnp.int32)
        ok = (draft == greedy[:, :spec_k]) & (jcol[None, :] < dlen[:, None])
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        wcol = jnp.arange(W, dtype=jnp.int32)
        # sequential greedy stops at the first EOS: truncate there
        cand = wcol[None, :] <= acc[:, None]
        is_eos = (greedy == eos_id) & cand
        first_eos = jnp.min(jnp.where(is_eos, wcol[None, :], W), axis=1)
        m = jnp.minimum(acc, first_eos)          # accepted draft tokens
        n_emit = jnp.where(decode_rows, m + 1, 0)
        emit_mask = decode_rows[:, None] & (wcol[None, :] < n_emit[:, None])
        last_tok = jnp.take_along_axis(
            greedy, jnp.clip(n_emit - 1, 0, W - 1)[:, None], axis=1)[:, 0]

        # -- prefill rows: same bookkeeping as the mixed tick -------------
        prefill_rows = ~decode_rows & (frag_len > 0)
        done_pref = prefill_rows & frag_last
        pref_tok = jnp.take_along_axis(
            greedy, jnp.clip(frag_len - 1, 0, W - 1)[:, None], axis=1)[:, 0]
        tok = jnp.where(decode_rows, last_tok,
                        jnp.where(done_pref, pref_tok, state.tokens))
        n_out = jnp.where(done_pref, 1,
                          state.n_out + jnp.where(decode_rows, n_emit, 0))
        max_new = jnp.where(done_pref, frag_max_new, state.max_new)
        retire = decode_rows & ((tok == eos_id) | (n_out >= max_new))
        # stalled rows (state.active but not decode_rows) stay active
        active = (state.active & ~retire) | (done_pref & (max_new > 1))

        emitted = jnp.where(
            emit_mask, greedy,
            jnp.where(done_pref[:, None] & (wcol[None, :] == 0),
                      tok[:, None], NO_TOKEN))
        # rewind: prefill_chunk advanced decode rows by 1 + dlen; the
        # true position is pos0 + n_emit (rows past it are dead — the
        # next fragment overwrites before the mask can read them)
        cache = dict(cache, pos=jnp.where(decode_rows, pos0 + n_emit,
                                          cache["pos"]))
        # history: push the consumed inputs (pending token + accepted
        # drafts) — the new pending token `tok` stays out, per the
        # drafter's invariant.  Prompt history is seeded host-side at
        # the PREFILL -> DECODE transition, so prefill rows push 0.
        # Fused with the *next* proposal against the updated history
        # (the spec-chunk loop consumes it; budget-clamp there).
        dstate, nxt_draft, nxt_dlen = draft_lib.push_and_propose(
            dstate, tokens, jnp.where(decode_rows, n_emit, 0), tok,
            spec_k)
        drafted = jnp.sum(jnp.where(decode_rows, dlen, 0))
        accepted = jnp.sum(jnp.where(decode_rows, m, 0))
        return (DecodeState(tok, n_out, max_new, active), dstate, cache,
                emitted, drafted, accepted, nxt_draft, nxt_dlen)

    return propose, clamp, run


def build_spec_chunk(cfg: ArchConfig, *, spec_k: int, eos_id: int,
                     iters: int,
                     rules: Optional[ShardingRules] = None,
                     jit: bool = True,
                     paged: Optional[PagedLayout] = None):
    """Multi-iteration speculative decode chunk: up to ``iters`` verify
    forwards per host sync — PR 1's sync economy composed with the
    drafter, for the pure-decode phase (no prompt fragments pending).

    Every loop iteration is one draft → verify → accept/rewind cycle
    over all active slots (the :func:`_spec_core` the single tick also
    runs); the loop exits early when every slot retires.  Contiguous:
    ``fn(params, state, dstate, cache) -> (state, dstate, cache,
    emitted (n, iters*(spec_k+1)), fwd, slot_fwd, drafted, accepted)``
    where ``fwd`` counts executed verify forwards and ``slot_fwd`` the
    decoding-slot forwards (the tokens-per-forward denominator).  Paged
    adds the donated ``bstate`` and a ``stalls`` scalar.  The cache
    (and block state) is donated.

    The loop carries the drafter's *fused* proposal: iteration i's
    ``run`` pushes the consumed fragment and re-proposes against the
    updated history in the same graph (``draft_lib.push_and_propose``),
    so iteration i+1 only applies the budget clamp against its
    then-current state — the accept/rewind/re-propose cycle never
    leaves the device between verify forwards.
    """
    W = spec_k + 1
    propose, clamp, run = _spec_core(cfg, spec_k=spec_k, width=W,
                                     eos_id=eos_id, rules=rules)

    def zero_frags(n):
        return (jnp.zeros((n, W), jnp.int32), jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int32))

    def iteration(params, st, ds, cache, bstate, decode_rows, draft, dlen):
        ft, fl, flast, fmax = zero_frags(st.tokens.shape[0])
        st, ds, cache, em, d_i, a_i, nd, nl = run(
            params, st, ds, cache, decode_rows, draft, dlen, ft, fl,
            flast, fmax, fl)        # frag_skip == zeros == fl
        return st, ds, cache, em, d_i, a_i, nd, nl

    if paged is None:
        def chunk_fn(params, state: DecodeState, dstate, cache):
            n = state.tokens.shape[0]
            emitted0 = jnp.full((n, iters * W), NO_TOKEN, jnp.int32)
            zeros = jnp.int32(0)
            draft0, dlen0 = draft_lib.propose(dstate, state.tokens, spec_k)

            def cond(carry):
                i, st = carry[0], carry[1]
                return (i < iters) & jnp.any(st.active)

            def body(carry):
                i, st, ds, cache, draft, dlen, emitted, sf, dr, ac = carry
                decode_rows = st.active
                dlen = clamp(st, dlen, decode_rows)
                st, ds, cache, em, d_i, a_i, draft, dlen = iteration(
                    params, st, ds, cache, None, decode_rows, draft, dlen)
                emitted = jax.lax.dynamic_update_slice(emitted, em,
                                                       (0, i * W))
                sf = sf + jnp.sum(decode_rows).astype(jnp.int32)
                return (i + jnp.int32(1), st, ds, cache, draft, dlen,
                        emitted, sf, dr + d_i, ac + a_i)

            (fwd, state, dstate, cache, _, _, emitted, slot_fwd, drafted,
             accepted) = jax.lax.while_loop(
                cond, body, (zeros, state, dstate, cache, draft0, dlen0,
                             emitted0, zeros, zeros, zeros))
            return (state, dstate, cache, emitted, fwd, slot_fwd, drafted,
                    accepted)

        return _register_jit_site(
            chunk_fn, family="spec_chunk", jit=jit, paged=paged,
            donate_state={2: "dstate", 3: "cache"},
            static_keys=(("spec_k", spec_k), ("iters", iters)))

    def chunk_fn_paged(params, state: DecodeState, dstate, cache, bstate):
        n = state.tokens.shape[0]
        emitted0 = jnp.full((n, iters * W), NO_TOKEN, jnp.int32)
        zeros = jnp.int32(0)
        draft0, dlen0 = draft_lib.propose(dstate, state.tokens, spec_k)

        def cond(carry):
            i, st = carry[0], carry[1]
            return (i < iters) & jnp.any(st.active)

        def body(carry):
            (i, st, ds, cache, bstate, draft, dlen, emitted, sf, dr, ac,
             stalls) = carry
            dlen = clamp(st, dlen, st.active)
            bstate, tables, stalled = paging.grow_to_cover(
                bstate, cache["block_tables"], cache["pos"] + dlen,
                st.active, block_size=paged.block_size,
                max_rounds=spec_k // paged.block_size + 1)
            decode_rows = st.active & ~stalled
            dlen = jnp.where(decode_rows, dlen, 0)
            stalls = stalls + jnp.sum(stalled).astype(jnp.int32)
            cache = dict(cache, block_tables=tables)
            st, ds, cache, em, d_i, a_i, draft, dlen = iteration(
                params, st, ds, cache, bstate, decode_rows, draft, dlen)
            emitted = jax.lax.dynamic_update_slice(emitted, em, (0, i * W))
            sf = sf + jnp.sum(decode_rows).astype(jnp.int32)
            return (i + jnp.int32(1), st, ds, cache, bstate, draft, dlen,
                    emitted, sf, dr + d_i, ac + a_i, stalls)

        (fwd, state, dstate, cache, bstate, _, _, emitted, slot_fwd,
         drafted, accepted, stalls) = jax.lax.while_loop(
            cond, body, (zeros, state, dstate, cache, bstate, draft0,
                         dlen0, emitted0, zeros, zeros, zeros, zeros))
        return (state, dstate, cache, bstate, emitted, fwd, slot_fwd,
                drafted, accepted, stalls)

    return _register_jit_site(
        chunk_fn_paged, family="spec_chunk", jit=jit, paged=paged,
        donate_state={2: "dstate", 3: "cache", 4: "bstate"},
        static_keys=(("spec_k", spec_k), ("iters", iters)))


def build_solo_prefill_tick(cfg: ArchConfig, *, chunk_tokens: int,
                            rules: Optional[ShardingRules] = None,
                            jit: bool = True,
                            paged: Optional[PagedLayout] = None):
    """Cold-start fast path: with *no* slot decoding there is nobody to
    protect from head-of-line blocking, so instead of a full-batch
    fragment tick (which pays ``n_slots`` rows of compute for one
    prefilling job) the engine packs up to ``chunk_tokens`` prompt
    tokens for ONE job and runs them through a single-row
    ``prefill_chunk`` against that slot's cache view.

    Contiguous: ``fn(params, state, cache, slot, frag_tokens (1, Wp),
    frag_len (1,), frag_last (1,), frag_max_new (1,)) -> (state, cache,
    emitted (1,))`` — ``emitted`` carries the first token when the
    packed chunk finished the prompt, else ``NO_TOKEN``.  Paged adds
    ``bstate`` plus ``frag_skip/frag_cols/frag_rent`` (the cols/rent
    arrays are full ``(n_slots, K)`` with only ``slot``'s row set, so
    :func:`paging.extend_chains` is reused verbatim).  ``slot`` is a
    traced scalar: one compile covers every slot.
    """
    W = chunk_tokens

    def finish(state: DecodeState, slot, logits, frag_len, frag_last,
               frag_max_new):
        ftok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
        done = frag_last[0]
        mnew = frag_max_new[0]
        state = DecodeState(
            tokens=jnp.where(done, state.tokens.at[slot].set(ftok),
                             state.tokens),
            n_out=jnp.where(done, state.n_out.at[slot].set(1), state.n_out),
            max_new=jnp.where(done, state.max_new.at[slot].set(mnew),
                              state.max_new),
            active=jnp.where(done, state.active.at[slot].set(mnew > 1),
                             state.active))
        emitted = jnp.where(done, ftok, NO_TOKEN)[None]
        return state, emitted

    if paged is None:
        def tick(params, state: DecodeState, cache, slot, frag_tokens,
                 frag_len, frag_last, frag_max_new):
            assert frag_tokens.shape == (1, W), frag_tokens.shape
            sub = {
                "k": jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, 1),
                "v": jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, 1),
                "pos": jax.lax.dynamic_slice_in_dim(cache["pos"], slot, 1,
                                                    0),
            }
            with use_rules(rules):
                logits, sub = model_lib.prefill_chunk(
                    params, frag_tokens, frag_len, sub, cfg)
            cache = dict(
                cache,
                k=jax.lax.dynamic_update_slice_in_dim(cache["k"], sub["k"],
                                                      slot, 1),
                v=jax.lax.dynamic_update_slice_in_dim(cache["v"], sub["v"],
                                                      slot, 1),
                pos=jax.lax.dynamic_update_slice_in_dim(
                    cache["pos"], sub["pos"], slot, 0))
            state, emitted = finish(state, slot, logits, frag_len,
                                    frag_last, frag_max_new)
            return state, cache, emitted

        return _register_jit_site(
            tick, family="solo_prefill", jit=jit, paged=paged,
            donate_state={2: "cache"},
            static_keys=(("chunk_tokens", W),))

    def tick_paged(params, state: DecodeState, cache, bstate, slot,
                   frag_tokens, frag_len, frag_last, frag_max_new,
                   frag_skip, frag_cols, frag_rent):
        assert frag_tokens.shape == (1, W), frag_tokens.shape
        bstate, tables = paging.extend_chains(
            bstate, cache["block_tables"], frag_cols, frag_rent)
        # pages are global — only the bookkeeping rows need slicing
        sub = {
            "k": cache["k"], "v": cache["v"],
            "pos": jax.lax.dynamic_slice_in_dim(cache["pos"], slot, 1, 0),
            "block_tables": jax.lax.dynamic_slice_in_dim(tables, slot, 1,
                                                         0),
        }
        with use_rules(rules):
            logits, sub = model_lib.prefill_chunk(
                params, frag_tokens, frag_len, sub, cfg,
                skip_until=frag_skip)
        cache = dict(cache, k=sub["k"], v=sub["v"], block_tables=tables,
                     pos=jax.lax.dynamic_update_slice_in_dim(
                         cache["pos"], sub["pos"], slot, 0))
        state, emitted = finish(state, slot, logits, frag_len, frag_last,
                                frag_max_new)
        return state, cache, bstate, emitted

    return _register_jit_site(
        tick_paged, family="solo_prefill", jit=jit, paged=paged,
        donate_state={2: "cache", 3: "bstate"},
        static_keys=(("chunk_tokens", W),))


def build_admit_step(cfg: ArchConfig, max_seq: int,
                     rules: Optional[ShardingRules] = None):
    """Jitted packed admission: batched prefill + scatter into rented slots.

    fn(params, tokens (G,Sp), lengths (G,), max_new (G,), slots (G,),
       state, cache, first) -> (state, cache, first).

    Rows whose slot is out of range (the G-padding rows) are dropped by
    the scatter (`mode="drop"`), so the call compiles once per Sp bucket.
    A ``max_new`` of 1 admits inactive: the prefill argmax already is the
    whole budget, so the slot retires without a decode step.
    """

    def admit_fn(params, tokens, lengths, max_new, slots, state, cache,
                 first):
        logits, cache_g = _group_prefill(params, tokens, lengths, cfg,
                                         max_seq, rules)
        ftok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def put(big, small):
            if big.ndim == 1:                  # pos: (n_slots,)
                return big.at[slots].set(small, mode="drop")
            return big.at[:, slots].set(
                small.astype(big.dtype), mode="drop")
        cache = jax.tree_util.tree_map(put, cache, cache_g)
        state = _admit_state(state, slots, ftok, max_new)
        first = first.at[slots].set(ftok, mode="drop")
        return state, cache, first

    return _register_jit_site(
        admit_fn, family="admit_step", jit=True, paged=None,
        donate_state={6: "cache"}, static_keys=(("max_seq", max_seq),))


def _group_prefill(params, tokens, lengths, cfg, span, rules):
    """The shared packed-prefill call (span = group cache length)."""
    g = tokens.shape[0]
    batch = {"tokens": tokens}
    if cfg.frontend == "vision":
        batch["vision_embeds"] = jnp.zeros(
            (g, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32)
    if cfg.family == "encdec":
        batch["enc_embeds"] = jnp.zeros(
            (g, tokens.shape[1], cfg.frontend_dim), jnp.float32)
    with use_rules(rules):
        return model_lib.prefill(params, batch, cfg, span, lengths=lengths)


def _admit_state(state: DecodeState, slots, ftok, max_new) -> DecodeState:
    return DecodeState(
        tokens=state.tokens.at[slots].set(ftok, mode="drop"),
        n_out=state.n_out.at[slots].set(1, mode="drop"),
        max_new=state.max_new.at[slots].set(max_new, mode="drop"),
        # budget 1 is already spent by the prefill argmax
        active=state.active.at[slots].set(max_new > 1, mode="drop"))


def build_admit_step_paged(cfg: ArchConfig, max_seq: int,
                           layout: PagedLayout,
                           rules: Optional[ShardingRules] = None):
    """Paged packed admission: prefill the group over its (block-rounded)
    span, then scatter K/V *blocks* into host-rented pages.

    fn(params, tokens (G,Sp), lengths, max_new, slots (G,),
       gtables (G,NB), wtargets (G,nb_span), state, cache, bstate, first)
    -> (state, cache, bstate, first).

    ``gtables`` rows are the full chains committed to the slots' block
    tables; ``wtargets`` names the physical block each span-block of the
    group prefill is stored into — shared prefix blocks carry the
    out-of-range sentinel (already stored by an earlier chain; the
    scatter drops them).  ``paging.admit_chains`` rents the written
    blocks and takes one reference per chain entry.
    """
    bs = layout.block_size

    def admit_fn(params, tokens, lengths, max_new, slots, gtables,
                 wtargets, state, cache, bstate, first):
        g = tokens.shape[0]
        span_total = tokens.shape[1] + \
            (cfg.n_frontend_tokens if cfg.frontend == "vision" else 0)
        logits, cache_g = _group_prefill(params, tokens, lengths, cfg,
                                         span_total, rules)
        ftok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nb_span = span_total // bs
        wflat = wtargets.reshape(g * nb_span)
        for name in ("k", "v"):
            n_layers = cache_g[name].shape[0]
            blocks = cache_g[name].reshape(
                n_layers, g * nb_span, bs, *cache_g[name].shape[3:])
            cache[name] = cache[name].at[:, wflat].set(
                blocks.astype(cache[name].dtype), mode="drop")
        cache = dict(
            cache,
            pos=cache["pos"].at[slots].set(cache_g["pos"], mode="drop"),
            block_tables=cache["block_tables"].at[slots].set(
                gtables, mode="drop"))
        bstate = paging.admit_chains(bstate, gtables.reshape(-1), wflat)
        state = _admit_state(state, slots, ftok, max_new)
        first = first.at[slots].set(ftok, mode="drop")
        return state, cache, bstate, first

    return _register_jit_site(
        admit_fn, family="admit_step", jit=True, paged=layout,
        donate_state={8: "cache", 9: "bstate"},
        static_keys=(("max_seq", max_seq),
                     ("block_size", layout.block_size)))


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    # scheduling class — host-side metadata ONLY (the lint/tier-host-side
    # rule proves no traced tick ever reads it, which is what keeps the
    # tiered engine token-exact vs the untiered oracle by construction):
    # "latency" admits ahead of queue order and may displace
    # throughput-tier victims; "throughput" is the default class
    tier: str = "throughput"


def _pow2_bucket(n: int, cap: int) -> int:
    """Next power of two ≥ n, clamped to cap — bounds recompiles.

    Over-cap lengths clamp to `cap` (admission rejects them before any
    compile); the pre-fix behavior returned raw `n`, which compiled a
    fresh prefill for every distinct over-cap prompt length.
    """
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


def admit_span_buckets(max_seq: int, *, block_size: Optional[int] = None,
                       offset: int = 0, packed: bool = True,
                       _bucket: Callable[[int, int], int] = None) -> list:
    """Reachable compiled *span* buckets of the packed admission prefill.

    Derived by evaluating the engine's actual bucketing over every
    admissible prompt length — not a parallel hand-kept list, so if the
    bucketing in :meth:`ServingEngine._prefill_group` rots (PR 6's
    ``seed_slot`` lesson: a raw length reaching a jit boundary compiles
    once per distinct length), the enumerated space explodes and the
    retrace audit fails instead of the fleet silently recompiling.
    ``_bucket`` exists for the auditor's known-bad fixtures."""
    bucket = _bucket or _pow2_bucket
    spans = set()
    for maxlen in range(1, max_seq + 1):
        span = bucket(maxlen, max_seq) if packed else maxlen
        if block_size is not None:
            span += (-(span + offset)) % block_size
        spans.add(span)
    return sorted(spans)


def admit_group_buckets(n_slots: int, *, packed: bool = True,
                        _bucket: Callable[[int, int], int] = None) -> list:
    """Reachable compiled group-row buckets (same derivation rule)."""
    bucket = _bucket or _pow2_bucket
    return sorted({bucket(g, n_slots) if packed else g
                   for g in range(1, n_slots + 1)})


def retrace_key_spaces(*, max_seq: int, n_slots: int,
                       block_size: Optional[int] = None, offset: int = 0,
                       packed: bool = True) -> dict:
    """Static-argument key space per jit-site family, for the retrace
    audit: family name -> list of reachable static keys (one compile
    each), or ``None`` for an unbounded site (always a violation).

    The admission site is the only one whose key space depends on
    runtime data (prompt length, group size); every tick family's keys
    are fixed at engine construction and published through the
    manifest's ``static_keys``, so their space is the singleton the
    manifest already records."""
    spans = admit_span_buckets(max_seq, block_size=block_size,
                               offset=offset, packed=packed)
    gpads = admit_group_buckets(n_slots, packed=packed)
    spaces = {"admit_step": [(s, g) for s in spans for g in gpads]}
    for name, site in audit_manifest.sites().items():
        if site.family == "admit_step":
            continue
        spaces[name] = [site.static_keys]
    return spaces


@dataclasses.dataclass
class _ChainPlan:
    """Host-side admission plan for one request's block chain."""

    chain: list            # block ids covering the prompt (shared + new)
    new_blocks: list       # subset actually stored by this admission
    n_shared: int
    worst_total: int       # §5.1 reservation: blocks the chain may reach


@dataclasses.dataclass
class _PrefillJob:
    """Host cursor for one slot's incrementally outsourced prompt.

    ``stream`` is the token stream actually fed to the mixed tick —
    the request's prompt for a fresh admission, or prompt + generated
    history for a preempted request being resumed (the recompute
    replay).  ``cursor`` counts consumed tokens, ``registered`` the
    prefix-map blocks published so far (a block becomes shareable only
    once the fragment that writes it has been dispatched — a later
    chain must never attend to an unwritten shared block).  With
    ``drop_first`` the final fragment's argmax is a *replayed* token
    the request already emitted before eviction: it seeds the decode
    state but is not re-delivered."""

    req: Request
    max_new_eff: int
    stream: np.ndarray
    cursor: int = 0
    registered: int = 0
    drop_first: bool = False
    # a fleet-migrated request's replay (ServingEngine.adopt): the
    # drop_first cross-check books its mismatches separately so a
    # migration that silently diverged is distinguishable from a local
    # preemption-resume bug
    migrated: bool = False


class OutputValidationError(RuntimeError):
    """The host-side output tripwire (``validate_outputs=True``) caught a
    non-finite or out-of-vocabulary value in a synced emitted buffer —
    NaN/garbage logits upstream.  Carries slot/tick attribution in the
    message; the fleet supervisor treats it as a replica health failure."""


class ServingEngine:
    """Batched greedy decoding with rent/return slot semantics.

    The host owns the pool ledger and the queue; everything per-tick —
    argmax, EOS / max-new retirement, the active mask, cache advancement,
    and (paged) block-chain growth — runs inside one jitted decode chunk
    with a donated cache.  The host syncs once per chunk (and reads
    nothing at admission), which is what turns sequential per-slot
    coordination into streaming throughput.

    With ``paged=True`` the KV cache is a pool of ``n_blocks`` blocks of
    ``block_size`` positions governed by the same rent/release discipline
    (runtime/paging.py): admission rents exactly what the prompt needs
    (sharing identical prefix blocks), reserves the worst-case decode
    remainder so growth can't starve, and retirement returns the chain.

    With ``overcommit=True`` the §5.1 worst-case reservation is *not*
    taken: admission asks only for the blocks a request needs now, so
    occupancy rises to what the pool can physically hold, and when
    growth runs the pool dry mid-flight the supervisor evicts a victim
    (``preempt``) — its chain is clawed back refcount-aware, its request
    parks in ``PHASE_PREEMPTED`` with its full token history, and it
    resumes later by replaying that history through the chunked-prefill
    path, token-exactly (greedy determinism; the engine cross-checks the
    replayed pending token).  ``preempt(slot)`` is also callable
    directly — forced eviction is the mechanism priority scheduling and
    SLA tiers will drive.
    """

    def __init__(self, params, cfg: ArchConfig, *, n_slots: int,
                 max_seq: int, eos_id: int = 1,
                 decode_fn: Optional[Callable] = None,
                 chunk: int = 8,
                 rules: Optional[ShardingRules] = None,
                 mesh: Optional[Mesh] = None,
                 paged: bool = False, block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 prefix_sharing: bool = True,
                 chunked_prefill: bool = False,
                 prefill_chunk_tokens: int = 16,
                 max_prefill_tokens_per_tick: Optional[int] = None,
                 speculative: bool = False, spec_k: int = 4,
                 spec_hist: int = 64,
                 overcommit: bool = False,
                 debug_transfers: bool = False,
                 validate_outputs: bool = False):
        # tensor-parallel tick: with a (data, model) mesh the engine
        # shards attention heads / KV along "model" per the logical-axis
        # rules (divisibility fallback included) and places params, cache
        # and supervisor state accordingly — every tick then lowers with
        # sharded donated caches.  Token-exact vs the single-device
        # engine: attention has no cross-head reduction, the sharded
        # contractions psum disjoint partial sums, and the conformance
        # matrix asserts bit-identical emitted tokens on a >=2-device
        # mesh (CI runs it under 8 forced host devices).
        if mesh is not None and rules is None:
            rules = ShardingRules(mesh)
        self.mesh, self.rules = mesh, rules
        self.params, self.cfg = params, cfg
        self.debug_transfers = debug_transfers
        # health surface (chaos tentpole): the output tripwire validates
        # every synced emitted row on the host (no device sync added),
        # the bound being the padded vocab (padded unembed columns are
        # legal argmax winners on some configs); the fault hook is dead
        # code until `arm_faults` installs a plan (lint-enforced); the
        # per-tick wall clock feeds the fleet's deadline watchdog
        self.validate_outputs = validate_outputs
        self._vocab_bound = int(getattr(cfg, "vocab_padded", cfg.vocab))
        self._faults: Optional[faults_lib.ReplicaFaults] = None
        self._fault_step = 0
        self._poison_pending = False
        self.last_tick_wall_s = 0.0
        self.migrations_in = 0
        self.migrate_replay_mismatches = 0
        self._admit_wall: dict[int, float] = {}   # rid -> admission time
        self.max_seq, self.eos_id, self.chunk = max_seq, eos_id, chunk
        self.pool = CorePool(n_slots)
        self.active: dict[int, Request] = {}
        self._offset = cfg.n_frontend_tokens if cfg.frontend == "vision" \
            else 0
        dtype = jax.tree_util.tree_leaves(params)[0].dtype
        self.layout: Optional[PagedLayout] = None
        if paged:
            if cfg.family not in model_lib.PAGED_FAMILIES:
                raise ValueError(
                    f"paged serving supports {model_lib.PAGED_FAMILIES}, "
                    f"not {cfg.family!r}")
            nb_full = -(-max_seq // block_size)
            if n_blocks is None:       # capacity-equivalent default
                n_blocks = n_slots * nb_full
            self.layout = PagedLayout(block_size, n_blocks)
        self.cache = model_lib.init_cache(cfg, n_slots, max_seq,
                                          dtype=dtype, layout=self.layout)
        self.dstate = init_decode_state(n_slots)
        self._first = jnp.zeros((n_slots,), jnp.int32)
        self._need_first: set[int] = set()
        # pos + 1 of each slot outside `active` as the device keeps it,
        # which the paged decode kernel still walks: a retired row's
        # frozen length, 1 for a slot never used or parked
        self._idle_len = np.ones((n_slots,), np.int64)
        self._chunk_fn =build_decode_chunk(cfg, chunk=chunk, eos_id=eos_id,
                                            rules=rules, decode_fn=decode_fn,
                                            paged=self.layout)
        if self.layout is None:
            self._admit_fn = build_admit_step(cfg, max_seq, rules=rules)
        else:
            self._admit_fn = build_admit_step_paged(cfg, max_seq,
                                                    self.layout, rules=rules)
            self.bstate = paging.init_blocks(n_blocks)
            self._prefix_sharing = prefix_sharing
            # host mirrors of the device block state (refreshed at every
            # chunk sync — admission never blocks on the device)
            self._ref_host = np.zeros((n_blocks,), np.int32)
            self._tables_host = np.full(
                (n_slots, self.layout.max_blocks(max_seq)), -1, np.int32)
            self._prefix_map: dict = {}      # prefix key -> block id
            self._block_hash: dict = {}      # block id -> prefix key
            self._plans: dict[int, _ChainPlan] = {}   # slot -> plan
        self._packed = cfg.family in PACKED_PREFILL_FAMILIES
        self.chunked = chunked_prefill
        self.overcommit = overcommit
        # preemption rides the fragment machinery (resume = replay the
        # parked history through chunked prefill), so any causal-cache
        # family gets it — chunked admission and over-commit merely
        # require it up front
        self._can_preempt = cfg.family in model_lib.PAGED_FAMILIES \
            and not cfg.frontend
        if chunked_prefill and not self._can_preempt:
            raise ValueError(
                f"chunked prefill supports causal attention caches "
                f"{model_lib.PAGED_FAMILIES} without a frontend, not "
                f"{cfg.family!r} (frontend={cfg.frontend!r})")
        if overcommit and not self._can_preempt:
            raise ValueError(
                f"over-commit serving resumes preempted requests through "
                f"the chunked-prefill path: causal attention caches "
                f"{model_lib.PAGED_FAMILIES} without a frontend only, not "
                f"{cfg.family!r} (frontend={cfg.frontend!r})")
        self._jobs: dict[int, _PrefillJob] = {}
        if self._can_preempt:
            if prefill_chunk_tokens < 1:
                raise ValueError("prefill_chunk_tokens must be >= 1")
            if max_prefill_tokens_per_tick is not None \
                    and max_prefill_tokens_per_tick < 1:
                raise ValueError(
                    "max_prefill_tokens_per_tick must be >= 1")
            pchunk = int(prefill_chunk_tokens)
            if speculative and not chunked_prefill:
                # resume fragments ride the spec tick, whose verify
                # width is spec_k + 1 — match it instead of widening
                # every verify forward to the prefill fragment size
                pchunk = max(2, int(spec_k) + 1)
            self._pchunk = pchunk
            self._tick_budget = max_prefill_tokens_per_tick
            self._mixed_fn = build_mixed_tick(
                cfg, chunk_tokens=self._pchunk, eos_id=eos_id, rules=rules,
                paged=self.layout)
            # cold-start fast path: when no slot is decoding there is no
            # fairness to protect, so ONE job gets its fragments packed
            # up to the per-tick token budget through a single-row tick
            # instead of paying n_slots rows per fragment
            budget_eff = self._tick_budget if self._tick_budget is not None \
                else self._pchunk * n_slots
            self._solo_width = max(self._pchunk, min(budget_eff, max_seq))
            self._solo_fn = build_solo_prefill_tick(
                cfg, chunk_tokens=self._solo_width, rules=rules,
                paged=self.layout)
        self.spec = speculative
        if speculative:
            if cfg.family not in model_lib.PAGED_FAMILIES or cfg.frontend:
                raise ValueError(
                    f"speculative decoding rides the chunked-prefill "
                    f"forward: causal attention caches "
                    f"{model_lib.PAGED_FAMILIES} without a frontend only, "
                    f"not {cfg.family!r} (frontend={cfg.frontend!r})")
            if spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if spec_hist < 4:
                raise ValueError("spec_hist must be >= 4 (bigram context "
                                 "+ at least one continuation token)")
            self._spec_k = int(spec_k)
            self._spec_width = max(spec_k + 1,
                                   self._pchunk if self._can_preempt else 0)
            self.draft_state = draft_lib.init_draft_state(n_slots,
                                                          int(spec_hist))
            # the single tick composes with prompt fragments; the chunk
            # runs up to `chunk` verify forwards per host sync once the
            # engine is in the pure-decode phase (PR 1's sync economy)
            self._spec_fn = build_spec_tick(
                cfg, spec_k=self._spec_k, chunk_tokens=self._spec_width,
                eos_id=eos_id, rules=rules, paged=self.layout)
            self._spec_chunk_fn = build_spec_chunk(
                cfg, spec_k=self._spec_k, eos_id=eos_id, iters=chunk,
                rules=rules, paged=self.layout)
        self._finished_instant: list[Request] = []
        # preemption: parked requests keep their slot (PHASE_PREEMPTED)
        # but hold no KV; the re-admission queue resumes them oldest
        # eviction first.  _slot_seq orders admissions for the victim
        # policy's tie-break; _pressure flags a host-side scheduling
        # shortfall (the device-side signal is the stall counter).
        self._parked: dict[int, Request] = {}
        self._park_order: list[int] = []
        self._admit_seq = 0
        self._slot_seq: dict[int, int] = {}
        self._pressure = False
        self._evicted_recently = False
        # async request frontier (priority/SLA tiers): submit() enqueues
        # arrivals without blocking, _admit_frontier() drains them
        # tier-aware between ticks (latency-tier heads jump the queue and
        # may displace throughput-tier victims through preempt()), and
        # poll() surfaces completions.  Displaced victims queue here for
        # replay re-admission over the fleet-migration resume path.
        self._frontier: list[Request] = []
        self._displaced: list[Request] = []
        self._completed: list[Request] = []
        self._frontier_rids: set[int] = set()
        self.displacements = 0
        self.sla = TierAccounting()
        self.preemptions = 0
        self.resumes = 0
        self.preempted_tokens = 0
        self.preempt_replay_mismatches = 0
        # occupancy: running (non-parked) slots per tick, the over-commit
        # bench's numerator/denominator
        self.occ_ticks = 0
        self.occ_slot_ticks = 0
        # accounting: host round-trips vs the one-sync-per-slot-per-tick
        # baseline an un-refactored engine would have paid
        self.host_syncs = 0
        self.baseline_syncs = 0
        self.device_ticks = 0
        self.decode_tokens = 0
        self.frag_tokens = 0       # prompt tokens prefilled through ticks
        # paged: KV pages the decode kernel walks at each chunk's first
        # step, over every row
        self.decode_kv_pages = 0
        # programs compiled (or loaded from the persistent cache) during
        # step(), and their seconds; a warmed engine compiles none
        self.compiles = 0
        self.compile_s = 0.0
        self._dispatch_compiles = 0   # of the last dispatch, for its sync
        self.stalls = 0
        self.shared_block_hits = 0
        self.kv_bytes_allocated = 0
        self.tokens_finished = 0
        # speculative decode economics: verify forwards that had >= 1
        # decoding slot, the decode tokens they emitted, and the
        # drafted/accepted token totals (acceptance rate)
        self.spec_forwards = 0
        self.spec_slot_forwards = 0
        self.spec_decode_tokens = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        # per-slot / per-block KV footprint (all cache leaves that scale
        # with the slot or block count; `pos`/tables bookkeeping excluded)
        if self.layout is None:
            self._slot_bytes = sum(
                leaf.nbytes // n_slots for key, leaf in self.cache.items()
                if key != "pos")
        else:
            self._block_bytes = sum(
                self.cache[k].nbytes // n_blocks for k in ("k", "v"))
        # per-shard KV accounting: the fraction of a KV leaf's bytes one
        # model shard actually holds (1.0 single-device, 1/m head-sharded,
        # 1.0 again when divisibility fell back to replication)
        self._kv_shard_frac = 1.0
        self.model_shards = 1
        if mesh is not None:
            self._place_on_mesh()

    def _place_on_mesh(self) -> None:
        """Place params, cache and supervisor state on the engine mesh.

        Cache leaves follow the logical cache axes (kv heads over "model"
        when divisible, head_dim fallback otherwise); params follow the
        same rule table the cluster supervisor plans with.  Per-slot
        decode/drafter state and the block-pool ledger are *replicated*:
        the pool's bookkeeping is global — every shard rents the same
        block id for its local head slice (replicated-with-local-rent) —
        so rent/release stay one transition, while the pages' bytes split
        across shards (`kv_stats` reports both views).
        """
        from repro.launch import inputs as inputs_lib
        from repro.models.params import _set
        mesh, rules = self.mesh, self.rules
        repl = NamedSharding(mesh, P())
        pspecs: dict = {}
        for d in model_lib.param_defs(self.cfg):
            _set(pspecs, d.path, rules.spec(d.axes, d.shape))
        psh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P))
        self.params = jax.device_put(self.params, psh)
        ax = inputs_lib.cache_axes(self.cfg, paged=self.layout is not None)
        csh = {k: NamedSharding(mesh, rules.spec(ax[k], v.shape))
               if k in ax else repl for k, v in self.cache.items()}
        self.cache = jax.device_put(self.cache, csh)
        self.dstate = jax.device_put(self.dstate, repl)
        self._first = jax.device_put(self._first, repl)
        if self.layout is not None:
            self.bstate = jax.device_put(self.bstate, repl)
        if self.spec:
            self.draft_state = jax.device_put(self.draft_state, repl)
        k = self.cache["k"]
        local = int(np.prod(k.sharding.shard_shape(k.shape)))
        self._kv_shard_frac = local / k.size
        self.model_shards = int(dict(mesh.shape).get("model", 1))

    # -- admission ---------------------------------------------------------
    def admit(self, req: Request) -> bool:
        return self.admit_many([req]) == 1

    def admit_many(self, requests: list[Request]) -> int:
        """Rent slots (and, paged, blocks) and prefill as many of
        `requests` as the pools allow; returns how many were consumed
        from the front of the list.

        Packed admission: one batched padded prefill per call (causal
        families); recurrent families fall back to one exact-length
        prefill per request through the same jitted path.

        With ``chunked_prefill`` the prompt is *not* prefilled at
        admission at all: the slot enters ``PHASE_PREFILL`` and the mixed
        tick feeds it one fragment per tick (paged blocks are rented
        chunk-granularly as fragments land, under the same §5.1
        worst-case reservation taken here).

        Edge cases (all host-side, before any compile):
        * an empty prompt raises ``ValueError`` (a packed prefill row of
          length 0 would gather its "last token" from row -1 — garbage
          as the first token);
        * a prompt longer than ``max_seq`` raises ``ValueError``;
        * a prompt of exactly ``max_seq`` is admitted with an effective
          budget of 1 (the prefill argmax) — no decode write can land
          past the cache;
        * ``max_new <= 0`` completes immediately with empty output.
        """
        # validate the whole batch before renting anything: a rejection
        # must never leave earlier requests granted-but-unprefilled
        for req in requests:
            if len(req.prompt) == 0:
                raise ValueError(
                    f"request {req.rid}: empty prompt; there is no last "
                    f"prompt token to gather first-token logits from — "
                    f"reject upstream")
            if len(req.prompt) + self._offset > self.max_seq:
                raise ValueError(
                    f"request {req.rid}: prompt length {len(req.prompt)}"
                    f"{f' (+{self._offset} frontend tokens)' if self._offset else ''}"
                    f" does not fit max_seq={self.max_seq}; reject or "
                    f"truncate upstream")
        granted: list[Request] = []
        consumed = 0
        for req in requests:
            plen = len(req.prompt) + self._offset
            if req.max_new <= 0:
                req.out = []
                self._finished_instant.append(req)
                consumed += 1
                continue
            slot = self.pool.rent()
            if slot is None:
                break                     # pool exhausted: queue upstream
            if self.layout is not None:
                plan = self._plan_chain(req.prompt, plen,
                                        self._max_new_eff(req, plen),
                                        rent_now=not self.chunked)
                if plan is None:          # block pool exhausted
                    self.pool.release(slot)
                    break
                if self.chunked:
                    self._commit_plan_chunked(slot, plan)
                else:
                    self._commit_plan(slot, plan, req.prompt)
            req.slot = slot
            self._admit_seq += 1
            self._slot_seq[slot] = self._admit_seq
            self._admit_wall[req.rid] = time.perf_counter()
            granted.append(req)
            consumed += 1
        if not granted:
            return consumed
        if self.chunked:
            # no device prefill here: the slot's QT starts in the
            # fragment-feeding phase and the mixed tick does the rest
            for req in granted:
                slot, plen = req.slot, len(req.prompt)
                job = _PrefillJob(
                    req=req, max_new_eff=self._max_new_eff(req, plen),
                    stream=np.asarray(req.prompt, np.int32))
                if self.layout is not None:
                    plan = self._plans[slot]
                    # a fully-shared prefix needs no recompute: fast-
                    # forward past it (but keep >= 1 token so the final
                    # fragment has a last position to take logits from)
                    job.cursor = min(plan.n_shared * self.layout.block_size,
                                     plen - 1)
                    job.registered = plan.n_shared
                self.cache["pos"] = self.cache["pos"].at[slot].set(
                    job.cursor)
                self.active[slot] = req
                self._jobs[slot] = job
                self.pool.set_phase(slot, pool_lib.PHASE_PREFILL)
            return consumed
        groups = [granted] if self._packed else [[r] for r in granted]
        for group in groups:
            self._prefill_group(group)
        for req in granted:
            self.active[req.slot] = req
            self._need_first.add(req.slot)
            self.pool.set_phase(req.slot, pool_lib.PHASE_DECODE)
            if self.spec:
                # the drafter's match window is the consumed stream;
                # the pending first token (device-side argmax) stays out
                self.draft_state = draft_lib.seed_slot(
                    self.draft_state, req.slot, req.prompt)
        return consumed

    def _max_new_eff(self, req: Request, plen: int) -> int:
        """Budget clamp: emitted tokens 2..max_new write at positions
        plen..plen+max_new-2, which must stay inside max_seq."""
        return min(req.max_new, self.max_seq - plen + 1)

    def _worst_blocks(self, plen: int, max_new_eff: int) -> int:
        """The §5.1 worst-case chain: blocks the stream may reach if it
        spends its whole budget (the last token is emitted, not
        written)."""
        return -(-(plen + max_new_eff - 1) // self.layout.block_size)

    def _reserved_blocks(self) -> int:
        """Blocks promised to in-flight chains beyond what they hold now
        (reserved admission's un-rented remainder; 0 under over-commit,
        which takes no reservations)."""
        return sum(
            max(0, p.worst_total - int(np.sum(self._tables_host[s] >= 0)))
            for s, p in self._plans.items())

    def _plan_chain(self, prompt, plen: int, max_new_eff: int,
                    rent_now: bool = True) -> Optional[_ChainPlan]:
        """Pick a token stream's blocks from the host mirror: reuse
        shared prefix blocks, rent new ones, and check the admission
        budget against the pool.  ``prompt`` is the stream actually
        prefilled — the request's prompt, or the replay stream (prompt +
        generated history) when a preempted request resumes.

        Reserved admission checks the §5.1 worst-case chain against the
        unreserved pool, so decode growth can never starve.  With
        ``self.overcommit`` admission asks only for what the stream
        needs *now* — the worst case is checked against the pool's total
        capacity only (a request that couldn't complete even alone is
        deferred, and `run_to_completion` reports its demand), and
        mid-flight shortfalls are the preemption path's job.

        With ``rent_now=False`` (chunked prefill) no new blocks are
        picked — the chain holds only the shared prefix and grows
        chunk-granularly as fragments are outsourced."""
        lo = self.layout
        bs = lo.block_size
        n_full = plen // bs
        shared: list[int] = []
        if self._prefix_sharing:
            for j in range(n_full):
                blk = self._prefix_map.get(self._prefix_key(prompt, j))
                if blk is None:
                    break
                shared.append(blk)
        total_now = -(-plen // bs)
        worst_total = self._worst_blocks(plen, max_new_eff)
        used = int(np.sum(self._ref_host > 0))
        if self.overcommit:
            if worst_total > lo.n_blocks:
                return None     # cannot complete even on an empty pool
            need_now = (total_now if rent_now else len(shared)) \
                - len(shared)
            if need_now > lo.n_blocks - used:
                return None
        else:
            budget = lo.n_blocks - used - self._reserved_blocks()
            if worst_total - len(shared) > budget:
                return None
        if not rent_now:
            return _ChainPlan(chain=list(shared), new_blocks=[],
                              n_shared=len(shared),
                              worst_total=worst_total)
        free_ids = np.flatnonzero(self._ref_host == 0)
        new_blocks = [int(b) for b in free_ids[:total_now - len(shared)]]
        return _ChainPlan(chain=shared + new_blocks, new_blocks=new_blocks,
                          n_shared=len(shared), worst_total=worst_total)

    def _commit_plan(self, slot: int, plan: _ChainPlan, prompt) -> None:
        """Host-mirror bookkeeping for a granted chain.  Prefix keys are
        registered here, *before* the group prefill, so later requests
        in the same admission round already share them (the group
        scatter stores each block exactly once)."""
        self._plans[slot] = plan
        self.shared_block_hits += plan.n_shared
        for b in plan.chain:
            self._ref_host[b] += 1
        row = self._tables_host[slot]
        row[:] = -1
        row[:len(plan.chain)] = plan.chain
        self._register_prefixes(prompt, plan)

    def _commit_plan_chunked(self, slot: int, plan: _ChainPlan) -> None:
        """Chunked admission commits only the *shared prefix*: reference
        it on the device immediately (a retiring source chain must never
        free blocks this request still needs) and seed the slot's block
        table with it; everything else is rented fragment by fragment
        inside the mixed tick (`paging.extend_chains`)."""
        self._plans[slot] = plan
        self.shared_block_hits += plan.n_shared
        row = self._tables_host[slot]
        row[:] = -1
        for b in plan.chain:
            self._ref_host[b] += 1
        row[:len(plan.chain)] = plan.chain
        if plan.chain:
            shared = jnp.asarray(plan.chain, jnp.int32)
            self.bstate = paging.admit_chains(
                self.bstate, shared, jnp.zeros((0,), jnp.int32))
            self.cache["block_tables"] = self.cache["block_tables"] \
                .at[slot, :len(plan.chain)].set(shared)

    def _prefix_key(self, prompt: np.ndarray, j: int):
        """Key for chain block j: its content is a pure function of the
        token prefix it covers (frontend stub tokens are constant)."""
        end = (j + 1) * self.layout.block_size - self._offset
        return (j, np.asarray(prompt[:max(0, end)], np.int32).tobytes())

    def _register_prefixes(self, prompt, plan: _ChainPlan) -> None:
        if not self._prefix_sharing:
            return
        plen = len(prompt) + self._offset
        n_full = plen // self.layout.block_size
        for j in range(plan.n_shared, n_full):
            key = self._prefix_key(prompt, j)
            blk = plan.chain[j]
            self._prefix_map[key] = blk
            self._block_hash[blk] = key

    def _prefill_group(self, group: list[Request]) -> None:
        g = len(group)
        n = self.pool.n
        maxlen = max(len(r.prompt) for r in group)
        span = _pow2_bucket(maxlen, self.max_seq) if self._packed else maxlen
        if self.layout is not None:
            # the paged scatter stores whole blocks: pad the span so the
            # group cache divides into block_size rows
            bs = self.layout.block_size
            span += (-(span + self._offset)) % bs
        # pad the group to a pow2 row count: compiles stay bounded to
        # log2(n_slots) variants per span bucket, while a single trickle
        # admission doesn't pay a full n_slots-row prefill
        gpad = _pow2_bucket(g, n) if self._packed else g
        tokens = np.zeros((gpad, span), np.int32)
        lengths = np.ones((gpad,), np.int32)
        max_new = np.zeros((gpad,), np.int32)
        slots = np.full((gpad,), n, np.int32)   # n = out of range -> dropped
        for i, r in enumerate(group):
            tokens[i, :len(r.prompt)] = r.prompt
            lengths[i] = len(r.prompt)
            max_new[i] = self._max_new_eff(r, len(r.prompt) + self._offset)
            slots[i] = r.slot
        if self.layout is None:
            self.dstate, self.cache, self._first = self._admit_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(lengths),
                jnp.asarray(max_new), jnp.asarray(slots), self.dstate,
                self.cache, self._first)
        else:
            lo = self.layout
            nb_span = (span + self._offset) // lo.block_size
            gtables = np.full((gpad, lo.max_blocks(self.max_seq)), -1,
                              np.int32)
            wtargets = np.full((gpad, nb_span), lo.n_blocks, np.int32)
            for i, r in enumerate(group):
                plan = self._plans[r.slot]
                gtables[i, :len(plan.chain)] = plan.chain
                for j, blk in enumerate(plan.chain):
                    if j >= plan.n_shared:
                        wtargets[i, j] = blk
            (self.dstate, self.cache, self.bstate,
             self._first) = self._admit_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(lengths),
                jnp.asarray(max_new), jnp.asarray(slots),
                jnp.asarray(gtables), jnp.asarray(wtargets), self.dstate,
                self.cache, self.bstate, self._first)
        # un-refactored baseline: one argmax sync per admitted request
        self.baseline_syncs += g

    # -- chunked prefill: fragment scheduler + unified tick ------------------
    @functools.partial(annotate_function, name="serve.schedule")
    def _schedule_fragments(self, width: Optional[int] = None,
                            only_slot: Optional[int] = None):
        """Pick this tick's prompt fragments (host side): one fragment of
        up to ``width`` (default ``prefill_chunk_tokens``) per PREFILLING
        slot, oldest job first, bounded by the per-tick token budget.
        With ``only_slot`` given, only that job is scheduled (the
        cold-start solo path packs one job up to the tick budget).
        Paged jobs also get their fragment's blocks picked from the free
        mirror here — the §5.1 reservation taken at admission guarantees
        the pick succeeds, and the ids are committed on device by the
        tick itself (`paging.extend_chains`), so host and device free
        lists cannot race."""
        n = self.pool.n
        C = self._pchunk if width is None else int(width)
        ft = np.zeros((n, C), np.int32)
        fl = np.zeros((n,), np.int32)
        flast = np.zeros((n,), bool)
        fmax = np.zeros((n,), np.int32)
        fskip = np.zeros((n,), np.int32)
        paged = self.layout is not None
        if paged:
            bs = self.layout.block_size
            frent = np.full((n, C // bs + 2), -1, np.int32)
            fcols = np.zeros((n, C // bs + 2), np.int32)
        budget = self._tick_budget if self._tick_budget is not None \
            else C * n
        finishing: list[int] = []
        for slot, job in list(self._jobs.items()):
            if only_slot is not None and slot != only_slot:
                continue
            if budget <= 0:
                break                 # token budget spent: rest wait a tick
            prompt = job.stream
            plen = len(prompt)
            take = min(C, plen - job.cursor, budget)
            if take <= 0:
                continue
            if paged and self.overcommit:
                # admit on current need: the fragment may only write
                # positions the free pool can cover — a shortfall clamps
                # the fragment (the job waits) and flags pressure so the
                # host loop evicts a victim at the sync
                plan = self._plans[slot]
                need = (job.cursor + take - 1) // bs + 1
                if need > len(plan.chain):
                    free_now = int(np.sum(self._ref_host == 0))
                    cover = (len(plan.chain) + free_now) * bs - job.cursor
                    if cover < take:
                        self._pressure = True
                        take = cover
                        if take <= 0:
                            continue
            ft[slot, :take] = prompt[job.cursor:job.cursor + take]
            fl[slot] = take
            fmax[slot] = job.max_new_eff
            last = job.cursor + take >= plen
            flast[slot] = last
            if paged:
                plan = self._plans[slot]
                fskip[slot] = plan.n_shared * bs
                need = (job.cursor + take - 1) // bs + 1
                k_i = 0
                while len(plan.chain) < need:
                    blk = int(np.flatnonzero(self._ref_host == 0)[0])
                    col = len(plan.chain)
                    self._ref_host[blk] += 1
                    self._tables_host[slot, col] = blk
                    frent[slot, k_i] = blk
                    fcols[slot, k_i] = col
                    plan.chain.append(blk)
                    k_i += 1
                if self._prefix_sharing:
                    # publish prefix-map entries for the full blocks this
                    # fragment completes: a block becomes shareable only
                    # once its writing tick is dispatched
                    done_full = min((job.cursor + take) // bs, plen // bs)
                    for j in range(job.registered, done_full):
                        key = self._prefix_key(prompt, j)
                        self._prefix_map[key] = plan.chain[j]
                        self._block_hash[plan.chain[j]] = key
                    job.registered = max(job.registered, done_full)
            job.cursor += take
            budget -= take
            if last:
                finishing.append(slot)
        out = (ft, fl, flast, fmax, fskip)
        if paged:
            out = out + (fcols, frent)
        return out, finishing

    def _refresh_block_mirrors(self, stalls=0, tables=None,
                               ref=None) -> None:
        """Take in what a paged tick's sync brought back: its stall
        count, and the host mirrors of the device block state, refreshed
        at every sync — admission never blocks on the device.  A
        contiguous tick's sync brings none of these."""
        if tables is None:
            return
        self.stalls += int(stalls)
        self._tables_host = np.asarray(tables).copy()
        self._ref_host = np.asarray(ref).copy()

    def _decoding_slots(self) -> list[int]:
        """Active slots currently in the decode phase (not mid-prefill)."""
        if not self._jobs:
            return list(self.active)
        return [s for s in self.active if s not in self._jobs]

    def _finish_jobs(self, finishing: list[int]) -> dict[int, _PrefillJob]:
        """PREFILL -> DECODE transitions for slots whose final fragment
        just ran; returns {slot: job} so the emission loop can apply the
        resume replay-token bookkeeping (``drop_first``)."""
        fin: dict[int, _PrefillJob] = {}
        for slot in finishing:
            job = self._jobs.pop(slot)
            fin[slot] = job
            self.pool.set_phase(slot, pool_lib.PHASE_DECODE)
            self.baseline_syncs += 1
            if self.spec:
                # the drafter's match window is the consumed stream —
                # for a resumed request that is prompt + replayed
                # history, exactly what it held before eviction
                self.draft_state = draft_lib.seed_slot(
                    self.draft_state, slot, job.stream)
        return fin

    def _checked_row(self, req: Request, slot: int, row):
        """Host-side output tripwire over one *already-synced* emitted
        row: NaN/inf for float buffers, vocab-range for the int32 token
        buffers the ticks actually emit.  Raises
        :class:`OutputValidationError` with slot/tick attribution —
        before the row can reach ``req.out``, so a poisoned replica's
        host-side token history stays clean for migration replay.  Reads
        only host memory: no device sync is added (the PR 8 transfer
        audit stays clean)."""
        if self._poison_pending:
            # an armed NaN fault poisoned the device cache; at the int32
            # token boundary the corruption surfaces as an out-of-range
            # bit pattern in the next synced row (see runtime/faults.py)
            row = np.array(row, copy=True)
            if row.size:
                row[0] = faults_lib.POISON_TOKEN
            self._poison_pending = False
        if not self.validate_outputs:
            return row
        arr = np.asarray(row)
        if np.issubdtype(arr.dtype, np.floating):
            if not np.all(np.isfinite(arr)):
                raise OutputValidationError(
                    f"non-finite emitted value for slot {slot} (rid "
                    f"{req.rid}) at device tick {self.device_ticks}")
        else:
            bad = arr[(arr != NO_TOKEN)
                      & ((arr < 0) | (arr >= self._vocab_bound))]
            if bad.size:
                raise OutputValidationError(
                    f"invalid token {int(bad[0])} emitted for slot {slot} "
                    f"(rid {req.rid}) at device tick {self.device_ticks}: "
                    f"outside [0, {self._vocab_bound}) — NaN/garbage "
                    f"logits upstream")
        return row

    def _emit_row(self, req: Request, slot: int, row,
                  fin: dict[int, _PrefillJob]) -> int:
        """Deliver one emitted row to `req`; returns how many *decode*
        tokens it carried (a finishing fragment's first token is prefill
        output, and a resumed job's replayed token is dropped — already
        delivered before eviction — after an exactness check)."""
        row = self._checked_row(req, slot, row)
        new_toks = [int(t) for t in row if t != NO_TOKEN]
        job = fin.get(slot)
        if job is not None and job.drop_first and new_toks:
            replay = new_toks.pop(0)
            if not req.out or replay != req.out[-1]:
                if job.migrated:
                    self.migrate_replay_mismatches += 1
                else:
                    self.preempt_replay_mismatches += 1
        req.out.extend(new_toks)
        return 0 if slot in fin else len(new_toks)

    def _solo_step(self) -> list[Request]:
        """Cold-start packed prefill: no slot is decoding, so one job's
        fragments are packed up to the per-tick budget and run through a
        single-row tick — no fairness to protect, no n_slots-row tax."""
        slot = next(iter(self._jobs))          # oldest job first
        sched, finishing = self._schedule_fragments(
            width=self._solo_width, only_slot=slot)
        s1 = slice(slot, slot + 1)
        ft, fl, flast, fmax, fskip = sched[:5]
        with self._dispatch("solo_prefill", fl):
            if self.layout is None:
                self.dstate, self.cache, emitted = self._solo_fn(
                    self.params, self.dstate, self.cache, jnp.int32(slot),
                    jnp.asarray(ft[s1]), jnp.asarray(fl[s1]),
                    jnp.asarray(flast[s1]), jnp.asarray(fmax[s1]))
            else:
                fcols, frent = sched[5:]
                (self.dstate, self.cache, self.bstate,
                 emitted) = self._solo_fn(
                    self.params, self.dstate, self.cache, self.bstate,
                    jnp.int32(slot), jnp.asarray(ft[s1]),
                    jnp.asarray(fl[s1]), jnp.asarray(flast[s1]),
                    jnp.asarray(fmax[s1]), jnp.asarray(fskip[s1]),
                    jnp.asarray(fcols), jnp.asarray(frent))
        em, active_mask, *block = self._sync(emitted, self.dstate.active)
        self.device_ticks += 1
        finished: list[Request] = []
        with TraceAnnotation("serve.emit"):
            self._refresh_block_mirrors(*block)
            fin = self._finish_jobs(finishing)
            for s in finishing:                    # at most [slot]
                req = self.active[s]
                self._emit_row(req, s, em, fin)
                if not active_mask[s]:             # max_new == 1 retires now
                    finished.append(req)
                    del self.active[s]
                    self._retire_slot(s, req)
        return finished

    def _spec_chunk_step(self) -> list[Request]:
        """Pure-decode speculation: up to ``chunk`` draft/verify/accept
        cycles inside one jitted loop — one host sync."""
        with self._dispatch("spec_chunk"):
            if self.layout is None:
                (self.dstate, self.draft_state, self.cache, emitted, fwd,
                 slot_fwd, drafted, accepted) = self._spec_chunk_fn(
                    self.params, self.dstate, self.draft_state, self.cache)
                stalls = 0
            else:
                (self.dstate, self.draft_state, self.cache, self.bstate,
                 emitted, fwd, slot_fwd, drafted, accepted,
                 stalls) = self._spec_chunk_fn(
                    self.params, self.dstate, self.draft_state, self.cache,
                    self.bstate)
        (em, active_mask, first, fwd, slot_fwd, drafted, accepted,
         *block) = self._sync(emitted, self.dstate.active, self._first, fwd,
                              slot_fwd, drafted, accepted, stalls=stalls)
        self.device_ticks += int(fwd)
        self.spec_forwards += int(fwd)
        self.spec_slot_forwards += int(slot_fwd)
        self.spec_drafted += int(drafted)
        self.spec_accepted += int(accepted)
        with TraceAnnotation("serve.emit"):
            self._refresh_block_mirrors(*block)
            return self._emit_rows(em, active_mask, first)

    def _spec_step(self) -> list[Request]:
        """One speculative tick: every DECODING slot drafts ahead and
        gets up to ``spec_k + 1`` tokens verified in the shared forward;
        PREFILLING slots keep consuming prompt fragments; one host
        sync."""
        # pure decode goes through _spec_chunk_step; this tick only runs
        # while prompt fragments (admission or resume) are outsourced
        assert self._jobs
        W = self._spec_width
        n_decoding = len(self.active) - len(self._jobs)
        sched, finishing = self._schedule_fragments()
        ft, fl, flast, fmax = sched[:4]
        if W > self._pchunk:
            ft = np.pad(ft, ((0, 0), (0, W - self._pchunk)))
        with self._dispatch("spec", fl):
            if self.layout is None:
                (self.dstate, self.draft_state, self.cache, emitted,
                 drafted, accepted) = self._spec_fn(
                    self.params, self.dstate, self.draft_state, self.cache,
                    jnp.asarray(ft), jnp.asarray(fl), jnp.asarray(flast),
                    jnp.asarray(fmax))
                stalls = 0
            else:
                fskip, fcols, frent = sched[4:]
                (self.dstate, self.draft_state, self.cache, self.bstate,
                 emitted, drafted, accepted, stalls) = self._spec_fn(
                    self.params, self.dstate, self.draft_state, self.cache,
                    self.bstate, jnp.asarray(ft), jnp.asarray(fl),
                    jnp.asarray(flast), jnp.asarray(fmax),
                    jnp.asarray(fskip), jnp.asarray(fcols),
                    jnp.asarray(frent))
        em, active_mask, first, drafted, accepted, *block = self._sync(
            emitted, self.dstate.active, self._first, drafted, accepted,
            stalls=stalls)
        self.device_ticks += 1
        if n_decoding:
            self.spec_forwards += 1
            self.spec_slot_forwards += n_decoding
            self.spec_drafted += int(drafted)
            self.spec_accepted += int(accepted)
        with TraceAnnotation("serve.emit"):
            self._refresh_block_mirrors(*block)
            return self._emit_rows(em, active_mask, first, finishing)

    def _mixed_step(self) -> list[Request]:
        """One unified prefill/decode tick: every PREFILLING slot eats a
        fragment, every DECODING slot one token; one host sync."""
        sched, finishing = self._schedule_fragments()
        ft, fl, flast, fmax = sched[:4]
        with self._dispatch("mixed", fl):
            if self.layout is None:
                self.dstate, self.cache, emitted = self._mixed_fn(
                    self.params, self.dstate, self.cache, jnp.asarray(ft),
                    jnp.asarray(fl), jnp.asarray(flast), jnp.asarray(fmax))
                stalls = 0
            else:
                fskip, fcols, frent = sched[4:]
                (self.dstate, self.cache, self.bstate, emitted,
                 stalls) = self._mixed_fn(
                    self.params, self.dstate, self.cache, self.bstate,
                    jnp.asarray(ft), jnp.asarray(fl), jnp.asarray(flast),
                    jnp.asarray(fmax), jnp.asarray(fskip),
                    jnp.asarray(fcols), jnp.asarray(frent))
        em, active_mask, first, *block = self._sync(
            emitted, self.dstate.active, self._first, stalls=stalls)
        self.device_ticks += 1
        with TraceAnnotation("serve.emit"):
            self._refresh_block_mirrors(*block)
            return self._emit_rows(em, active_mask, first, finishing)

    @contextlib.contextmanager
    def _dispatch(self, family: str, frag_lens: Optional[np.ndarray] = None,
                  kv_pages: int = 0):
        """``serve.dispatch`` around one tick's host->device uploads and
        jitted call, ``frag_lens`` being its prompt fragments' lengths
        and ``kv_pages`` the KV pages its decode kernel walks;
        counts the programs compiled meanwhile for the tick's sync."""
        frag = 0 if frag_lens is None else int(frag_lens.sum())
        self.frag_tokens += frag
        self.decode_kv_pages += kv_pages
        n0 = _compiled[0]
        with TraceAnnotation("serve.dispatch", family=family,
                             decode_rows=len(self.active) - len(self._jobs),
                             frag_tokens=frag, kv_pages=kv_pages):
            yield
        self._dispatch_compiles = _compiled[0] - n0

    def _sync(self, *arrays, stalls=0) -> tuple:
        """``serve.sync``: the tick's one host sync, of ``arrays``.
        Paged, the tick's ``stalls`` and the block state after it come
        along, last, for :meth:`_refresh_block_mirrors`."""
        if self.layout is not None:
            arrays += (stalls, self.cache["block_tables"],
                       self.bstate.refcount)
        with TraceAnnotation("serve.sync", compiles=self._dispatch_compiles):
            host = jax.device_get(arrays)
        self.host_syncs += 1
        return host

    def _emit_rows(self, em, active_mask, first,
                   finishing=()) -> list[Request]:
        """Deliver a batched tick's synced rows: PREFILL -> DECODE for
        the slots whose final fragment ran (its argmax is the first
        token, or, resuming, the replayed token ``_emit_row`` drops),
        then every slot past its prompt gets its row, and the slots the
        tick retired are retired."""
        fin = self._finish_jobs(finishing)
        for slot, req in list(self.active.items()):
            if slot in self._jobs:
                continue               # mid-prefill: nothing emitted yet
            if slot in self._need_first:
                # a monolithically admitted slot delivers its
                # admission-prefill first token here, in order
                req.out.append(int(first[slot]))
                self._need_first.discard(slot)
            n_dec = self._emit_row(req, slot, em[slot], fin)
            self.decode_tokens += n_dec
            self.baseline_syncs += n_dec
            if self.spec:
                self.spec_decode_tokens += n_dec
            if not active_mask[slot]:
                # hand off through _finished_instant and retire BEFORE
                # dropping from `active`: if a corrupt ledger makes the
                # release raise mid-loop, every request finished this
                # tick is still reachable — rescued or drained by the
                # fleet's quarantine, whose replay re-derives any tokens
                # the raise discarded
                self._finished_instant.append(req)
                self._retire_slot(slot, req)
                del self.active[slot]
        finished, self._finished_instant = self._finished_instant, []
        return finished

    # -- one decode chunk over all active slots -----------------------------
    def step(self) -> list[Request]:
        """Advance every active slot up to `chunk` tokens; one host sync.

        With ``debug_transfers=True`` the whole tick runs under
        ``jax.transfer_guard_device_to_host("disallow")``: the budgeted
        per-tick sync is an *explicit* ``jax.device_get`` (as is every
        pool-ledger read), so it passes, while any stray implicit
        device->host transfer smuggled into the serving path — an
        ``int()``/``bool()``/``np.asarray`` on a device array — raises
        instead of silently serializing the dispatch stream.  The
        static auditor's transfer harness runs engines in this mode."""
        if not self.debug_transfers:
            return self._step()
        with jax.transfer_guard_device_to_host("disallow"):
            return self._step()

    def _step(self) -> list[Request]:
        """Advance every active slot up to `chunk` tokens; one host sync.

        With chunked prefill, while any slot is still consuming prompt
        fragments (admission *or* a preempted request's resume replay)
        the engine ticks the unified prefill/decode step instead (one
        token per decoding slot, one fragment per prefilling slot,
        bounded latency); once every prompt is absorbed it returns to
        multi-token decode chunks.

        Over-commit supervision brackets the tick: parked requests are
        re-admitted up front when the pool can take them back, and a
        tick that ran the pool dry (device stall or host scheduling
        shortfall) evicts one victim at the sync."""
        compiled0 = tuple(_compiled)
        finished: list[Request] = []
        with TraceAnnotation("serve.tick", i=self.occ_ticks):
            if self._finished_instant:
                # drained optimistically; a raise below restores them so
                # the fleet's quarantine rescue still delivers them
                # exactly once
                finished, self._finished_instant = \
                    self._finished_instant, []
            try:
                finished = finished + self._tick()
            except BaseException:
                self._finished_instant = finished + self._finished_instant
                raise
            if self._frontier_rids:
                self._frontier_epilogue(finished)
        self.compiles += _compiled[0] - compiled0[0]
        self.compile_s += _compiled[1] - compiled0[1]
        return finished

    def _tick(self) -> list[Request]:
        """One supervised tick: frontier admission, parked resume, the
        jitted device step, then over-commit pressure relief."""
        finished: list[Request] = []
        if self._frontier or self._displaced or self._parked:
            with TraceAnnotation("serve.admit", queued=len(self._frontier)):
                if self._frontier or self._displaced:
                    self._admit_frontier()
                if self._parked:
                    self._resume_parked(force=not self.active)
        if not self.active:
            return finished
        if self._faults is not None:
            # chaos hook: fires only between jitted ticks, only when a
            # plan is armed (lint/fault-hook enforces this stays guarded)
            self._fire_faults(self._faults)
            self._fault_step += 1
        self.occ_ticks += 1
        self.occ_slot_ticks += len(self.active)
        stall_mark = self.stalls
        t0 = time.perf_counter()
        if self._jobs and not self._decoding_slots():
            # nobody decoding -> no fairness to protect: pack one job's
            # fragments up to the tick budget through the solo tick
            finished += self._solo_step()
        elif self.spec:
            if self._jobs:
                finished += self._spec_step()
            else:
                finished += self._spec_chunk_step()
        elif self._jobs:
            finished += self._mixed_step()
        else:
            finished += self._decode_step()
        self.last_tick_wall_s = time.perf_counter() - t0
        if self.overcommit and (self._pressure or self.stalls > stall_mark):
            # the tick ran the block pool dry: claw chains back until a
            # block actually came free — a fully-shared victim relieves
            # nothing (evict_chain frees 0), so parking it alone would
            # spend a replay without moving the pressure
            self._pressure = False
            with TraceAnnotation("serve.preempt"):
                while True:
                    free0 = int(np.sum(self._ref_host == 0))
                    if self.preempt() is None:
                        break
                    if int(np.sum(self._ref_host == 0)) > free0:
                        break
        return finished

    def _kv_len(self, slot: int, req: Request) -> int:
        """``pos + 1`` of an active row at its next decode step: the
        prompt and every emitted token, the newest written by that
        step."""
        return (len(req.prompt) + self._offset + len(req.out)
                + (slot in self._need_first))

    def _kv_pages_walked(self) -> int:
        """Paged: the KV pages the decode kernel walks at a chunk's first
        step, ``ceil(min(pos + 1, max_seq) / block_size)`` over every
        slot, active or not, from the host's state."""
        if self.layout is None:
            return 0
        bs = self.layout.block_size
        lens = self._idle_len.copy()
        for slot, req in self.active.items():
            lens[slot] = self._kv_len(slot, req)
        nb = -(-self.max_seq // bs)
        return int(np.sum(np.minimum(-(-lens // bs), nb)))

    def _decode_step(self) -> list[Request]:
        """The multi-token decode chunk (no prefill fragments pending)."""
        with self._dispatch("decode", kv_pages=self._kv_pages_walked()):
            if self.layout is None:
                self.dstate, self.cache, emitted, iters = self._chunk_fn(
                    self.params, self.dstate, self.cache)
                stalls = 0
            else:
                (self.dstate, self.cache, self.bstate, emitted, iters,
                 stalls) = self._chunk_fn(self.params, self.dstate,
                                          self.cache, self.bstate)
        em, active_mask, first, iters, *block = self._sync(
            emitted, self.dstate.active, self._first, iters, stalls=stalls)
        self.device_ticks += int(iters)
        with TraceAnnotation("serve.emit"):
            # the chunk's on-device block growth refreshes the mirrors
            self._refresh_block_mirrors(*block)
            return self._emit_rows(em, active_mask, first)

    # -- preemption: evict under KV pressure, resume by replay --------------
    def _drop_chain_host(self, slot: int, evict: bool) -> None:
        """Drop `slot`'s block chain on device *and* in the host mirrors
        (prefix-map upkeep included) — the shared tail of retirement and
        eviction.  Refcount-aware on both sides: a shared prefix block
        another chain references survives."""
        plan = self._plans.pop(slot)
        chain = self._tables_host[slot]
        chain = chain[chain >= 0]
        self.kv_bytes_allocated += \
            (len(chain) - plan.n_shared) * self._block_bytes
        if evict:
            self.bstate, tables, _ = paging.evict_chain(
                self.bstate, self.cache["block_tables"], slot)
        else:
            self.bstate, tables = paging.release_chain(
                self.bstate, self.cache["block_tables"], slot)
        self.cache = dict(self.cache, block_tables=tables)
        for b in chain:
            self._ref_host[b] -= 1
            if self._ref_host[b] == 0:
                key = self._block_hash.pop(int(b), None)
                if key is not None and self._prefix_map.get(key) == int(b):
                    del self._prefix_map[key]
        self._tables_host[slot] = -1

    def _pick_victim(self) -> Optional[int]:
        """The eviction policy: throughput tier before latency tier
        (otherwise pressure eviction would immediately claw back the
        slot a latency arrival just displaced for — on an untiered
        stream the tier key is constant and the policy is unchanged),
        then fewest tokens generated, ties broken toward the latest
        admission (LIFO under equal progress).  The last running slot
        is never evicted — the maximal-progress request always retires
        and frees its chain, which is what makes over-commit terminate
        instead of thrash."""
        if len(self.active) <= 1:
            return None
        return min(self.active,
                   key=lambda s: (self.active[s].tier == "latency",
                                  len(self.active[s].out),
                                  -self._slot_seq.get(s, 0)))

    def preempt(self, slot: Optional[int] = None) -> Optional[int]:
        """Supervisor-initiated eviction: claw back a slot's rented KV
        and park its request (PHASE_PREEMPTED) with its full token
        history for a later recompute-based resume.  Call between steps
        (the host owns synced state there).  With ``slot=None`` the
        victim policy picks; returns the parked request's rid, or
        ``None`` when nothing is evictable."""
        if not self._can_preempt:
            raise RuntimeError(
                "preemption needs the chunked-prefill resume path "
                "(causal attention cache, no frontend)")
        if slot is None:
            slot = self._pick_victim()
            if slot is None:
                return None
        elif slot not in self.active:
            raise ValueError(f"slot {slot} has no active request")
        req = self.active.pop(slot)
        self._jobs.pop(slot, None)
        self._need_first.discard(slot)
        # device: the slot goes dark — exactly the shape a never-admitted
        # slot has, so the next tick cannot read or write through it
        self.dstate = self.dstate._replace(
            active=self.dstate.active.at[slot].set(False))
        self.cache["pos"] = self.cache["pos"].at[slot].set(0)
        self._idle_len[slot] = 1
        if self.layout is not None:
            self._drop_chain_host(slot, evict=True)
        if self.spec:
            self.draft_state = draft_lib.evict_slot(self.draft_state, slot)
        self._parked[slot] = req
        self._park_order.append(slot)
        self.pool.set_phase(slot, pool_lib.PHASE_PREEMPTED)
        self.preemptions += 1
        self.preempted_tokens += len(req.out)
        self._evicted_recently = True
        return req.rid

    def _resume_stream(self, req: Request):
        """The replay stream for a parked request: prompt + everything
        generated *except* the pending last token (its KV row was never
        written — it is what the final replay fragment's argmax
        reproduces), plus the remaining device budget."""
        plen = len(req.prompt) + self._offset
        eff = self._max_new_eff(req, plen)
        if not req.out:
            return np.asarray(req.prompt, np.int32), eff, False
        stream = np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(req.out[:-1], np.int32)])
        # the device counts n_out from 1 at the PREFILL -> DECODE
        # transition, so the replayed budget is the *remaining* tokens
        # plus the replayed one
        return stream, eff - len(req.out) + 1, True

    def _resume_parked(self, force: bool = False) -> None:
        """Re-admit parked requests (oldest eviction first) through the
        chunked-prefill path.  A one-step damper after an eviction keeps
        a resume from stealing back the blocks the eviction just freed
        for the pressured runners; ``force`` overrides it when nothing
        else can run."""
        if self._evicted_recently and not force:
            self._evicted_recently = False
            return
        while self._park_order:
            slot = self._park_order[0]
            req = self._parked[slot]
            stream, max_new_eff, drop = self._resume_stream(req)
            job = _PrefillJob(req=req, max_new_eff=max_new_eff,
                              stream=stream, drop_first=drop)
            if self.layout is not None:
                plan = self._plan_chain(stream, len(stream) + self._offset,
                                        max_new_eff, rent_now=False)
                if plan is None:
                    break            # no capacity yet; FIFO order holds
                if self.overcommit and not force \
                        and plan.n_shared * self.layout.block_size \
                        < len(stream) \
                        and not np.any(self._ref_host == 0):
                    break            # replay would stall on its first
                    #                  unshared fragment: wait for blocks
                self._commit_plan_chunked(slot, plan)
                # a fully-shared replay prefix needs no recompute (but
                # keep >= 1 token for the final fragment's logits)
                job.cursor = min(plan.n_shared * self.layout.block_size,
                                 len(stream) - 1)
                job.registered = plan.n_shared
            self._park_order.pop(0)
            del self._parked[slot]
            self.cache["pos"] = self.cache["pos"].at[slot].set(job.cursor)
            self.active[slot] = req
            self._jobs[slot] = job
            self.pool.set_phase(slot, pool_lib.PHASE_PREFILL)
            self._admit_seq += 1
            self._slot_seq[slot] = self._admit_seq
            self.resumes += 1

    def _retire_slot(self, slot: int, req: Request) -> None:
        """Return the core — and, paged, the block chain — to the pool
        (§4.3 terminate)."""
        self.tokens_finished += len(req.prompt) + len(req.out)
        if self.layout is None:
            self.kv_bytes_allocated += self._slot_bytes
            self.pool.release(slot)
            return
        self._idle_len[slot] = self._kv_len(slot, req)
        self._drop_chain_host(slot, evict=False)
        self.pool.release(slot)

    # -- chaos & health ------------------------------------------------------
    def arm_faults(self, faults) -> None:
        """Arm a :class:`runtime.faults.ReplicaFaults` schedule.  Until
        this is called the fault hooks in the tick path are dead code —
        ``self._faults`` stays ``None`` and every hook is behind that
        guard (the ``lint/fault-hook`` rule enforces it stays that way,
        and that no compiled tick ever branches on fault state)."""
        self._faults = faults

    def _fire_faults(self, faults) -> None:
        """Apply every due fault event (host-side, between ticks)."""
        for ev in faults.due(self._fault_step):
            if ev.kind == "tick_exception":
                raise faults_lib.InjectedFault(
                    f"injected tick exception at step {self._fault_step}")
            if ev.kind == "hang":
                time.sleep(ev.hang_s)
            elif ev.kind == "nan_poison":
                self.cache = faults_lib.poison_cache(self.cache)
                self._poison_pending = True
            elif ev.kind == "ledger_corruption":
                faults_lib.corrupt_pool_ledger(self.pool)

    def health_check(self) -> Optional[str]:
        """Sample the host-side slot-pool ledger invariants; returns a
        reason string when the replica should be quarantined, ``None``
        when healthy.  Reads only the host ledger mirror — no device
        sync — so the fleet can afford it every tick."""
        reason = pool_lib.invariant_violation(self.pool.state)
        if reason is not None:
            return f"slot-pool ledger: {reason}"
        return None

    def _replay_admit(self, req: Request, *, migrated: bool) -> bool:
        """Rent a *fresh* slot and replay ``req``'s prompt + generated
        history through the chunked-prefill resume path — the shared
        core of fleet migration (:meth:`adopt`) and tier-displacement
        re-admission.  Token-exact by greedy determinism; the replayed
        pending token is cross-checked in ``_emit_row`` (mismatches book
        into ``migrate_replay_mismatches`` or
        ``preempt_replay_mismatches`` by origin).  Returns False without
        side effects when there is no capacity right now."""
        slot = self.pool.rent()
        if slot is None:
            return False
        stream, max_new_eff, drop = self._resume_stream(req)
        job = _PrefillJob(req=req, max_new_eff=max_new_eff,
                          stream=stream, drop_first=drop, migrated=migrated)
        if self.layout is not None:
            plan = self._plan_chain(stream, len(stream) + self._offset,
                                    max_new_eff, rent_now=False)
            if plan is None:
                self.pool.release(slot)
                return False
            self._commit_plan_chunked(slot, plan)
            job.cursor = min(plan.n_shared * self.layout.block_size,
                             len(stream) - 1)
            job.registered = plan.n_shared
        self.cache["pos"] = self.cache["pos"].at[slot].set(job.cursor)
        req.slot = slot
        self.active[slot] = req
        self._jobs[slot] = job
        self.pool.set_phase(slot, pool_lib.PHASE_PREFILL)
        self._admit_seq += 1
        self._slot_seq[slot] = self._admit_seq
        self._admit_wall[req.rid] = time.perf_counter()
        return True

    def adopt(self, req: Request) -> bool:
        """Adopt an in-flight request drained from a quarantined sibling:
        replay prompt + generated-so-far through the chunked-prefill
        resume path (the same machinery preemption uses), token-exact by
        greedy determinism — the replayed pending token is cross-checked
        in ``_emit_row`` and any divergence counts in
        ``migrate_replay_mismatches``.  Returns False (without side
        effects) when this engine has no capacity right now."""
        if not self._can_preempt:
            raise RuntimeError(
                "migration needs the chunked-prefill resume path: "
                "construct the engine with chunked=True")
        if not self._replay_admit(req, migrated=True):
            return False
        self.migrations_in += 1
        return True

    # -- priority tiers: the async request frontier --------------------------
    @property
    def has_work(self) -> bool:
        """Anything left for an open-loop driver: queued arrivals,
        displaced victims awaiting re-admission, in-flight or parked
        requests, or finished-but-unreported ones."""
        return bool(self._frontier or self._displaced or self.active
                    or self._parked or self._finished_instant)

    def submit(self, req: Request, now: Optional[float] = None) -> None:
        """Async frontier entry: enqueue an arrival without blocking.
        Admission happens tier-aware at the next :meth:`step` (a
        latency-tier arrival jumps the queue and may displace
        throughput-tier victims); completions surface through
        :meth:`poll`.  Stamps the request into the per-tier SLO ledger
        (:class:`~repro.runtime.accounting.TierAccounting`) — pass
        ``now`` to replay a recorded arrival trace."""
        self.sla.arrive(req.rid, req.tier, now=now)
        self._frontier_rids.add(req.rid)
        self._frontier.append(req)

    def poll(self) -> list[Request]:
        """Drain finished frontier-submitted requests (non-blocking)."""
        done, self._completed = self._completed, []
        return done

    @functools.partial(annotate_function, name="serve.epilogue")
    def _frontier_epilogue(self, finished: list[Request]) -> None:
        """Post-tick SLO stamping + completion routing for
        frontier-submitted requests.  Host lists and one
        ``perf_counter`` only — the tick's sync economy is untouched."""
        now = time.perf_counter()
        for req in self.active.values():
            if req.rid in self._frontier_rids:
                self.sla.observe(req.rid, len(req.out), now=now)
        for req in finished:
            if req.rid in self._frontier_rids:
                self.sla.observe(req.rid, len(req.out), now=now)
                self.sla.finish(req.rid)
                self._frontier_rids.discard(req.rid)
                self._completed.append(req)

    def _admit_frontier(self) -> None:
        """Drain the frontier tier-first (host side, between ticks):
        latency-tier arrivals admit ahead of queue order — displacing
        throughput-tier victims when the pools are full — then displaced
        victims re-enter before fresh throughput arrivals (they already
        hold generated tokens; replaying them promptly is what keeps
        their streams short), then throughput arrivals admit FIFO until
        one fails."""
        keep: list[Request] = []
        blocked = False
        for req in self._frontier:
            if req.tier != "latency":
                keep.append(req)
                continue
            if blocked or not self.admit_displacing(req):
                keep.append(req)
                blocked = True
        self._frontier = keep
        if blocked:
            return          # a latency head is starved: nothing jumps it
        while self._displaced:
            if not self._replay_admit(self._displaced[0], migrated=False):
                return
            self._displaced.pop(0)
            self.resumes += 1
        while self._frontier:
            if not self.admit(self._frontier[0]):
                return
            self._frontier.pop(0)

    def admit_displacing(self, req: Request) -> bool:
        """The tiered admission controller: try a plain admit; when a
        *latency-tier* arrival cannot rent a slot or blocks, displace
        throughput-tier victims through the public :meth:`preempt` hook
        (KV clawback) plus a full slot release, until the arrival fits
        or no throughput-tier victim remains.  A latency-tier arrival
        never displaces a latency-tier slot."""
        if self.admit(req):
            return True
        if req.tier != "latency" or not self._can_preempt:
            return False
        while True:
            victim = self._pick_displacement_victim()
            if victim is None:
                return False
            self._displace(victim)
            if self.admit(req):
                return True

    def _pick_displacement_victim(self) -> Optional[int]:
        """Displacement victim for a latency-tier arrival: throughput
        tier ONLY — by construction a latency arrival never evicts a
        latency slot (the property the conformance suite asserts).
        Parked throughput requests go first (they hold a slot but no
        KV, so displacing them frees a core without clawing back any
        chain); among active ones the over-commit victim policy applies
        (fewest tokens generated, ties to the latest admission)."""
        for slot in self._park_order:
            if self._parked[slot].tier != "latency":
                return slot
        cand = [s for s, r in self.active.items() if r.tier != "latency"]
        if not cand:
            return None
        return min(cand, key=lambda s: (len(self.active[s].out),
                                        -self._slot_seq.get(s, 0)))

    def _displace(self, slot: int) -> Request:
        """Fully evict ``slot``'s throughput-tier request — KV *and*
        core — so a latency-tier arrival can rent both.  An active
        victim goes through the public :meth:`preempt` hook first
        (chain clawback + park bookkeeping), then the parked request is
        pulled off its slot and queued for replay re-admission over the
        fleet-migration resume path."""
        if slot not in self._parked:
            self.preempt(slot)
        req = self._parked.pop(slot)
        self._park_order.remove(slot)
        self.pool.release(slot)
        req.slot = None
        self._displaced.append(req)
        self.displacements += 1
        return req

    def run_to_completion(self, requests: list[Request], max_ticks=10_000,
                          max_wall_s: Optional[float] = None):
        """Continuous batching: admit whenever slots free up, decode in
        device-resident chunks.  Returns (done, device decode ticks).

        Raises ``RuntimeError`` when ``max_ticks`` is exhausted with
        requests still pending or active — the pre-fix behavior silently
        returned only the finished subset, so a too-small budget looked
        like a successful (shorter) run.  Partial outputs stay on the
        undrained ``Request`` objects for inspection.  ``max_wall_s``
        bounds host wall clock the same way (a hung tick burns no device
        ticks, so ``max_ticks`` alone cannot catch it)."""
        pending = list(requests)
        done = []
        start_ticks = self.device_ticks
        t_start = time.perf_counter()
        while (pending or self.active or self._parked or self._displaced
               or self._finished_instant) and \
                self.device_ticks - start_ticks < max_ticks:
            n = self.admit_many(pending)
            del pending[:n]
            if not self.active and not self._parked \
                    and not self._displaced \
                    and not self._finished_instant:
                if pending:    # no capacity rentable and none draining
                    raise RuntimeError(self._stuck_report(pending))
                break
            done += self.step()
            if max_wall_s is not None \
                    and time.perf_counter() - t_start > max_wall_s:
                raise RuntimeError(self._stuck_report(
                    pending,
                    reason=f"max_wall_s={max_wall_s} exceeded with "
                           f"{len(self.active)} active, "
                           f"{len(self._parked)} preempted and "
                           f"{len(pending)} pending requests undrained"))
        if self._finished_instant:     # complete, just not yet reported
            done += self._finished_instant
            self._finished_instant = []
        if pending or self.active or self._parked or self._displaced:
            rids = sorted([r.rid for r in self.active.values()] +
                          [r.rid for r in self._parked.values()] +
                          [r.rid for r in self._displaced] +
                          [r.rid for r in pending])
            raise RuntimeError(
                f"max_ticks={max_ticks} exhausted with {len(self.active)} "
                f"active, {len(self._parked)} preempted and {len(pending)} "
                f"pending requests undrained (rids {rids}); partial "
                f"outputs remain on the Request objects")
        return done, self.device_ticks - start_ticks

    def _stuck_report(self, pending: list[Request],
                      reason: Optional[str] = None) -> str:
        """Per-request block demand vs pool capacity for the stuck-pool
        error — plus per-request in-flight ages and the replica's health
        state, so a wall-clock timeout or a quarantine is diagnosable
        from the message alone."""
        lines = [reason if reason is not None else
                 f"{len(pending)} requests stuck: pool has no rentable "
                 f"slot/blocks and no active request to drain"]
        lines.append(f"slot pool: {self.pool.n} slots, "
                     f"{self.pool.available} available")
        now = time.perf_counter()
        in_flight = (list(self.active.values()) +
                     list(self._parked.values()) + list(self._displaced))
        for r in in_flight[:8]:
            age = now - self._admit_wall.get(r.rid, now)
            lines.append(f"  in flight rid {r.rid}: {len(r.out)} tokens "
                         f"out, {age:.2f}s since admission")
        if len(in_flight) > 8:
            lines.append(f"  ... and {len(in_flight) - 8} more in flight")
        lines.append(f"health: {self.health_check() or 'ok'}; "
                     f"last tick {self.last_tick_wall_s * 1e3:.1f}ms")
        if self.layout is not None:
            bs = self.layout.block_size
            free = int(np.sum(self._ref_host == 0))
            lines.append(
                f"block pool: {self.layout.n_blocks} blocks of "
                f"{bs} positions, {free} free, "
                f"{self._reserved_blocks()} reserved "
                f"(admission={'overcommit' if self.overcommit else 'reserved'})")
            for r in pending[:8]:
                plen = len(r.prompt) + self._offset
                now = -(-plen // bs)
                worst = self._worst_blocks(plen, self._max_new_eff(r, plen))
                lines.append(
                    f"  rid {r.rid}: prompt {plen} tokens -> needs {now} "
                    f"blocks now, {worst} worst-case, vs "
                    f"{self.layout.n_blocks} total")
            if len(pending) > 8:
                lines.append(f"  ... and {len(pending) - 8} more")
        return "\n".join(lines)

    # -- accounting ---------------------------------------------------------
    def reset_stats(self) -> None:
        """Zero the accounting counters (pool/cache state untouched).
        Benches warm the jit caches on the engine they will time — each
        engine owns its own jitted closures, so warming a sibling engine
        warms nothing — then reset before the measured run."""
        self.host_syncs = self.baseline_syncs = 0
        self.device_ticks = self.decode_tokens = 0
        self.frag_tokens = self.compiles = 0
        self.decode_kv_pages = 0
        self.compile_s = 0.0
        self.stalls = 0
        self.shared_block_hits = 0
        self.kv_bytes_allocated = 0
        self.tokens_finished = 0
        self.spec_forwards = self.spec_slot_forwards = 0
        self.spec_decode_tokens = 0
        self.spec_drafted = self.spec_accepted = 0
        self.preemptions = self.resumes = 0
        self.preempted_tokens = self.preempt_replay_mismatches = 0
        self.migrations_in = self.migrate_replay_mismatches = 0
        self.displacements = 0
        self.occ_ticks = self.occ_slot_ticks = 0
        if self.layout is not None:
            # the block high-water mark restarts from what is in use now
            pool = self.bstate.pool
            self.bstate = self.bstate._replace(
                pool=pool._replace(peak_used=pool_lib.used(pool)))

    def sync_stats(self) -> dict:
        """Host-sync economy vs a per-slot-per-tick engine (same run),
        the prompt tokens prefilled through ticks, the KV pages the
        decode kernel walked at each chunk's first step (paged),
        and the programs compiled (or loaded from the persistent cache)
        inside step() with their seconds."""
        tokens = max(1, self.decode_tokens)
        return {
            "host_syncs": self.host_syncs,
            "baseline_syncs": self.baseline_syncs,
            "device_ticks": self.device_ticks,
            "decode_tokens": self.decode_tokens,
            "frag_tokens": self.frag_tokens,
            "decode_kv_pages": self.decode_kv_pages,
            "compiles": self.compiles,
            "compile_s": self.compile_s,
            "host_syncs_per_100_tokens": 100.0 * self.host_syncs / tokens,
            "baseline_syncs_per_100_tokens":
                100.0 * self.baseline_syncs / tokens,
            "sync_reduction_x": self.baseline_syncs / max(1, self.host_syncs),
        }

    def spec_stats(self) -> dict:
        """Speculative decode economics.  ``tokens_per_forward`` is
        decode tokens emitted per *slot-forward* (one decoding slot in
        one verify tick) — exactly 1.0 for the non-speculative engine,
        ``1 + accepted drafts`` here, so it is the per-slot decode
        multiplier the drafter buys.  ``acceptance_rate`` is accepted /
        proposed draft tokens."""
        return {
            "spec_k": getattr(self, "_spec_k", 0),
            "spec_forwards": int(self.spec_forwards),
            "spec_slot_forwards": int(self.spec_slot_forwards),
            "spec_decode_tokens": int(self.spec_decode_tokens),
            "tokens_per_forward":
                self.spec_decode_tokens / max(1, self.spec_slot_forwards),
            "drafted": int(self.spec_drafted),
            "accepted": int(self.spec_accepted),
            "acceptance_rate":
                self.spec_accepted / max(1, self.spec_drafted),
        }

    def occupancy_stats(self) -> dict:
        """Over-commit economics: the mean fraction of slots actually
        running per tick (parked slots excluded — they hold no KV), the
        eviction/resume counts, and what the evictions cost in replayed
        tokens.  ``preempt_replay_mismatches`` must stay 0: greedy
        determinism makes every resume replay its history token-exactly,
        and the engine checks the replayed pending token against the one
        delivered before eviction."""
        return {
            "overcommit": bool(self.overcommit),
            "ticks": int(self.occ_ticks),
            "n_slots": int(self.pool.n),
            "slot_ticks": int(self.occ_slot_ticks),
            "occupancy": self.occ_slot_ticks
            / max(1, self.occ_ticks * self.pool.n),
            "preemptions": int(self.preemptions),
            "resumes": int(self.resumes),
            "preempted_tokens_recomputed": int(self.preempted_tokens),
            "preempt_replay_mismatches":
                int(self.preempt_replay_mismatches),
            "migrations_in": int(self.migrations_in),
            "migrate_replay_mismatches":
                int(self.migrate_replay_mismatches),
        }

    def kv_stats(self) -> dict:
        """KV-cache economics over the *finished* requests: bytes the
        engine actually allocated for them per token they produced.
        Contiguous slots pay `max_seq` rows per admission regardless of
        the sequence; paged chains pay per rented (non-shared) block.

        Byte totals are *global* (summed across the engine's model
        shards): the block/slot ledger is replicated-with-local-rent, so
        one rented block holds ``kv_shard_fraction`` of its bytes on each
        shard and the global figure is their sum.  ``*_per_shard`` fields
        give the single-shard view (what one device actually stores);
        fleet-wide aggregation across replicas is the
        ``FleetSupervisor.kv_stats`` sum over these per-engine ledgers.
        """
        out = {
            "layout": "paged" if self.layout is not None else "contiguous",
            "kv_bytes_allocated": int(self.kv_bytes_allocated),
            "tokens_finished": int(self.tokens_finished),
            "kv_bytes_per_token":
                self.kv_bytes_allocated / max(1, self.tokens_finished),
            "model_shards": int(self.model_shards),
            "kv_shard_fraction": float(self._kv_shard_frac),
            "kv_bytes_per_token_per_shard":
                self.kv_bytes_allocated * self._kv_shard_frac
                / max(1, self.tokens_finished),
        }
        if self.layout is not None:
            out.update(
                block_size=self.layout.block_size,
                n_blocks=self.layout.n_blocks,
                shared_block_hits=int(self.shared_block_hits),
                stalls=int(self.stalls),
                peak_blocks=int(self.bstate.pool.peak_used),
                blocks_in_use=int(np.sum(self._ref_host > 0)),
                block_bytes_per_shard=
                    int(self._block_bytes * self._kv_shard_frac),
            )
        else:
            out["slot_bytes"] = int(self._slot_bytes)
            out["slot_bytes_per_shard"] = \
                int(self._slot_bytes * self._kv_shard_frac)
        return out

    def load(self) -> dict:
        """Host-side routing signal for the fleet supervisor: rentable
        slots, rentable KV blocks net of the §5.1 reservation (what a new
        admission could actually claim — under over-commit nothing is
        reserved, so the raw free count stands), and the preemption
        pressure signals.  Parked requests hold a re-admission claim on
        blocks the ledger calls free; a pressure flag means the last tick
        ran the pool dry — a preemption-aware router sends new work
        elsewhere first.  Reads only host mirrors: routing never syncs
        the device."""
        free_blocks = None
        if self.layout is not None:
            free_blocks = int(np.sum(self._ref_host == 0))
            if not self.overcommit:
                free_blocks = max(0, free_blocks - self._reserved_blocks())
        return {
            "free_slots": int(self.pool.available),
            "free_blocks": free_blocks,
            "parked": len(self._parked),
            "pressure": bool(self._pressure),
        }
