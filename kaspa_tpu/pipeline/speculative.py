"""Speculative chain-state precompute: chain verification off the virtual lock.

The flight recorder's critical-path tables (PR 7) attribute the bulk of
per-block wall time to ``pipeline.virtual``, and the profile underneath is
unambiguous: `_resolve_virtual` serially redoes `_calculate_utxo_state`
for every chain candidate — mergeset replay, the batched script checks,
the muhash device product — while the stage workers idle.  This module
moves that compute onto the stage workers, as the reference moves it onto
rayon (virtual_processor/processor.rs calculate_utxo_state rayon pools):

- When a block's body commits and its selected parent's UTXO state is
  *reachable* — the live ``utxo_position``, or a pending speculative entry
  for the parent (chained speculation) — the stage worker immediately
  computes the block's chain-verification context and caches it keyed by
  ``(block, selected_parent)``.
- `_verify_chain_block` (virtual worker) pops the entry on a hit and goes
  straight to the five header checks + commit; on a miss it recomputes
  synchronously.  Hit and miss paths produce bit-identical state.
- Script checks route through the block's own ``BatchScriptChecker`` into
  the coalescing dispatcher (`ops/dispatch.py`), so concurrently
  speculating blocks merge into one device super-batch.

Safety invariants (these are what make hit == miss bit-identical):

1. Every consensus-state read happens in ``_begin`` **under the pipeline's
   commit lock** — the same lock serializing `_resolve_virtual`, header
   commits and every `_move_utxo_position` — so speculation observes
   exactly the frozen state the synchronous path would.  The device waits
   (script super-batch, muhash product) run outside the lock and touch
   only entry-private data (the staged jobs, a cloned multiset).
2. Script checks are staged *optimistically*: every staged tx is assumed
   accepted.  If any staged check fails after the async dispatch resolves,
   the whole entry is discarded — the synchronous fallback recomputes and
   reaches the identical (disqualify) verdict the honest path would.
3. The cache key ``(block, selected_parent)`` is position-proof: the UTXO
   state at a given position is a pure function of the position, so an
   entry survives reorgs away-and-back and is consumed whenever
   `_verify_chain_block` runs with ``utxo_position == selected_parent``.
4. A *chained* entry (parent state read from another pending entry's
   optimistic diff instead of the live set) is only consumable after that
   parent entry itself committed via the cache — which proves the
   optimistic parent diff equals the committed one.  A parent that fell
   back to the synchronous path leaves the child entry unconsumed
   (invalidated), never wrongly trusted.
5. Toccata-active blocks are never speculated: their VM-fallback lane
   reads reachability through the seq-commit accessor on pool threads,
   which is only safe while the dispatching thread holds the commit lock
   (the synchronous path does; the speculative wait phase deliberately
   does not).
"""

from __future__ import annotations

import threading

from kaspa_tpu.utils.sync import ranked_lock
from dataclasses import dataclass, field

from kaspa_tpu.consensus.processes.transaction_validator import FLAG_FULL
from kaspa_tpu.consensus.stores import StatusesStore
from kaspa_tpu.consensus.utxo import UtxoView
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import DEFAULT_LATENCY_BUCKETS, REGISTRY

_HITS = REGISTRY.counter(
    "speculative_hits", help="chain verifications served from the speculative precompute cache"
)
_MISSES = REGISTRY.counter(
    "speculative_misses", help="chain verifications that recomputed synchronously (no usable entry)"
)
_INVALIDATIONS = REGISTRY.counter_family(
    "speculative_invalidations", "reason",
    help="speculative entries discarded before use (script failure, uncommitted parent, error)",
)
_PRECOMPUTES = REGISTRY.counter(
    "speculative_precomputes", help="speculative chain-state contexts computed by stage workers"
)
_CHAIN_COMPUTED = REGISTRY.counter(
    "speculative_chain_blocks_computed",
    help="chain-state contexts precompute_chain computed inside a cycle (one increment a segment)",
)
_CHAIN_DISCARDED = REGISTRY.counter(
    "speculative_chain_blocks_discarded",
    help="of speculative_chain_blocks_computed, contexts not published: the block whose staged spend failed and every block behind it, computed again synchronously",
)
_INELIGIBLE = REGISTRY.counter_family(
    "speculative_ineligible", "reason",
    help="blocks that skipped speculation at begin time (position unreachable, toccata, dup)",
)
_WAIT = REGISTRY.histogram(
    "speculative_wait_seconds", DEFAULT_LATENCY_BUCKETS,
    help="off-lock device wait per speculative precompute (scripts + muhash)",
)


@dataclass
class _Entry:
    block: bytes
    selected_parent: bytes
    ctx: dict
    # the state view this entry's descendants chain onto: selected-parent
    # base composed with this entry's (optimistic == committed) diff
    view: UtxoView
    parent_entry: "_Entry | None"
    # position at the bottom of the entry's chain — the live utxo_position
    # every read in the chain was frozen against
    base_position: bytes


@dataclass
class _Pending:
    block: bytes
    selected_parent: bytes
    gd: object
    ctx: dict
    base: object
    parent_entry: _Entry | None
    base_position: bytes
    handle: object  # DispatchHandle
    txs: list
    own_staged: list
    trace_ctx: object = None
    script_failed: bool = field(default=False)


class SpeculativeVerifier:
    """One per ConsensusPipeline; attached as ``consensus.speculative``."""

    # chained entries nest UtxoViews one level per ancestor; bound the walk
    MAX_CHAIN_DEPTH = 16
    MAX_ENTRIES = 256

    def __init__(self, consensus, commit_lock):
        self.consensus = consensus
        self._commit_lock = commit_lock
        self._mu = ranked_lock("pipeline.speculative", reentrant=False)
        self._entries: dict[tuple[bytes, bytes], _Entry] = {}  # insertion-ordered for LRU bound
        self._by_block: dict[bytes, _Entry] = {}

    # ------------------------------------------------------------------
    # producer side (stage workers)
    # ------------------------------------------------------------------

    def run(self, block_hash: bytes, trace_ctx=None) -> None:
        """Full speculation attempt for one body-complete block.  Never
        raises: speculation is an optimization, every failure degrades to
        the synchronous path."""
        try:
            with trace.span("speculative.precompute", parent=trace_ctx):
                pending = self._begin(block_hash)
                if pending is None:
                    return
                self._wait(pending)
                self._finish(pending)
        except Exception:  # noqa: BLE001 - never let speculation fail a block
            _INVALIDATIONS.inc("error")

    def _begin(self, block_hash: bytes) -> _Pending | None:
        """Collect phase, under the commit lock: frozen-state reads, the
        optimistic mergeset replay, async script submission."""
        c = self.consensus
        with self._commit_lock.locked_for("speculate"):
            if c.storage.statuses.get(block_hash) != StatusesStore.STATUS_UTXO_PENDING_VERIFICATION:
                _INELIGIBLE.inc("status")
                return None
            gd = c.storage.ghostdag.get(block_hash)
            sp = gd.selected_parent
            header = c.storage.headers.get(block_hash)
            if c.params.toccata_active(header.daa_score):
                _INELIGIBLE.inc("toccata")
                return None
            with self._mu:
                if (block_hash, sp) in self._entries:
                    _INELIGIBLE.inc("duplicate")
                    return None
                parent_entry = None if sp == c.utxo_position else self._by_block.get(sp)
            if sp == c.utxo_position:
                base = c.utxo_set
                seed = c.multisets[sp]
                base_position = sp
            elif parent_entry is not None:
                # the chain of views bottoms out on the live utxo_set; the
                # composed reads stay correct while the live position sits
                # anywhere ON that chain (base, or a committed prefix block
                # — applying an entry's own diff to the base leaves reads
                # through its view unchanged), and diverge the moment it
                # reorgs onto a different branch
                depth, cur, on_chain = 1, parent_entry, {parent_entry.block}
                while cur.parent_entry is not None:
                    cur = cur.parent_entry
                    on_chain.add(cur.block)
                    depth += 1
                    if depth > self.MAX_CHAIN_DEPTH:
                        _INELIGIBLE.inc("depth")
                        return None
                on_chain.add(cur.base_position)
                if c.utxo_position not in on_chain:
                    _INELIGIBLE.inc("position")
                    return None
                base = parent_entry.view
                seed = parent_entry.ctx["multiset"]
                base_position = cur.base_position
            else:
                _INELIGIBLE.inc("position")
                return None

            checker = c.transaction_validator.new_checker()
            # graftlint: allow(blocking-under-lock) -- unreachable sync branch: checker is supplied, so _validate_transactions inside never takes its synchronous dispatch() path here
            ctx = c._calculate_utxo_state(
                gd, header.daa_score, base=base, seed_multiset=seed, checker=checker, cause="stage"
            )
            # check-5 staging (own txs over the block's own view): same
            # checker, so one async submission covers the whole block
            txs = c.storage.block_transactions.get(block_hash)
            own_view = UtxoView(base, ctx["mergeset_diff"])
            own_staged = c._validate_transactions(  # graftlint: allow(blocking-under-lock) -- unreachable sync branch: _begin passes checker=dispatch_async, _validate_transactions only calls dispatch() when no async checker is supplied
                txs, own_view, header.daa_score, FLAG_FULL,
                checker=checker, token_tag=("own",), position_anchor=sp,
            )
            handle = checker.dispatch_async()
        return _Pending(
            block=block_hash, selected_parent=sp, gd=gd, ctx=ctx, base=base,
            parent_entry=parent_entry, base_position=base_position,
            handle=handle, txs=txs, own_staged=own_staged,
        )

    def _wait(self, p: _Pending) -> None:
        """Device phase, no locks held: join the (coalesced) script
        super-batch, then reduce the entry-private muhash product."""
        from time import perf_counter_ns

        t0 = perf_counter_ns()
        with trace.span("speculative.wait"):
            results = p.handle.result()
            for token in p.ctx["staged_tokens"]:
                if results.get(token) is not None:
                    p.script_failed = True
            for token, _tx, _e, _f in p.own_staged:
                if results.get(token) is not None:
                    p.script_failed = True
            if not p.script_failed:
                p.ctx["multiset"].add_transactions_batch(p.ctx.pop("multiset_items"))
        _WAIT.observe((perf_counter_ns() - t0) * 1e-9)

    def _finish(self, p: _Pending) -> None:
        """Publish phase: cache the entry, or discard on any optimism
        mismatch (the synchronous fallback reaches the same verdict)."""
        if p.script_failed:
            _INVALIDATIONS.inc("script")
            return
        if len(p.own_staged) < len(p.txs) - 1:
            # a non-coinbase tx failed pre-script validation: the block will
            # be disqualified either way; let the honest path do it
            _INVALIDATIONS.inc("own_txs")
            return
        p.ctx.pop("staged_tokens", None)
        entry = _Entry(
            block=p.block,
            selected_parent=p.selected_parent,
            ctx=p.ctx,
            view=UtxoView(p.base, p.ctx["mergeset_diff"]),
            parent_entry=p.parent_entry,
            base_position=p.base_position,
        )
        self._publish(entry)

    def _publish(self, entry: _Entry) -> None:
        with self._mu:
            self._entries[(entry.block, entry.selected_parent)] = entry
            self._by_block[entry.block] = entry
            while len(self._entries) > self.MAX_ENTRIES:
                oldest = next(iter(self._entries))
                old = self._entries.pop(oldest)
                if self._by_block.get(old.block) is old:
                    del self._by_block[old.block]
        _PRECOMPUTES.inc()

    # ------------------------------------------------------------------
    # in-cycle batched precompute (virtual worker, commit lock held)
    # ------------------------------------------------------------------

    def precompute_chain(self, chain: list[bytes]) -> None:
        """Batched precompute for a pending selected-chain segment, called
        by `_ensure_chain_utxo_valid` before its per-block verify loop (the
        commit lock is already held; LockCtx wraps an RLock).

        The stage-time path speculates one block per checker; here the
        cycle already knows the exact chain it must verify, so every
        *missing* (block, selected_parent) context is computed chained —
        block i+1's mergeset replays over block i's optimistic view — and
        all their script checks go to the device as ONE coalesced
        dispatch.  Without this, each cache miss inside the cycle pays a
        full synchronous dispatch serially under the commit lock, and the
        misses compound: a long cycle starves stage-time speculation
        (workers stall on the lock, then find the position moved), which
        makes the next cycle long too.

        Publication is prefix-only: a script failure at block i poisons
        the views every later block chained on, so i and everything after
        fall back to the synchronous path (which reaches the honest
        disqualify verdict)."""
        c = self.consensus
        pendings, published = [], 0
        try:
            gd0 = c.storage.ghostdag.get(chain[0])
            # identical to what _verify_chain_block(chain[0]) does first;
            # doing it here freezes the base the whole segment chains on
            c._move_utxo_position(gd0.selected_parent)
            checker = c.transaction_validator.new_checker()
            prev_block = gd0.selected_parent
            prev_view = None
            prev_seed = None
            reused = 0  # stage-time entries the segment chained on
            with trace.span("speculative.chain_precompute", blocks=len(chain)) as span:
                for b in chain:
                    gd = c.storage.ghostdag.get(b)
                    sp = gd.selected_parent
                    if sp != prev_block:
                        break
                    if c.storage.statuses.get(b) != StatusesStore.STATUS_UTXO_PENDING_VERIFICATION:
                        break
                    header = c.storage.headers.get(b)
                    if c.params.toccata_active(header.daa_score):
                        break
                    with self._mu:
                        existing = self._entries.get((b, sp))
                    if existing is not None:
                        # stage-time hit: chain the rest of the segment on it
                        prev_block, prev_view, prev_seed = b, existing.view, existing.ctx["multiset"]
                        reused += 1
                        continue
                    base = prev_view if prev_view is not None else c.utxo_set
                    seed = prev_seed if prev_seed is not None else c.multisets[sp]
                    ctx = c._calculate_utxo_state(
                        gd, header.daa_score, base=base, seed_multiset=seed,
                        checker=checker, token_ns=b, cause="segment",
                    )
                    # muhash finalized eagerly: the next block's seed must
                    # already contain this mergeset
                    ctx["multiset"].add_transactions_batch(ctx.pop("multiset_items"))
                    txs = c.storage.block_transactions.get(b)
                    view = UtxoView(base, ctx["mergeset_diff"])
                    own_staged = c._validate_transactions(
                        txs, view, header.daa_score, FLAG_FULL,
                        checker=checker, token_tag=("own", b), position_anchor=sp,
                    )
                    pendings.append((b, sp, ctx, view, txs, own_staged))
                    prev_block, prev_view, prev_seed = b, view, ctx["multiset"]
                span.set(computed=len(pendings), reused=reused, jobs=checker.queued_jobs(), published=0)
                if not pendings:
                    return
                results = checker.dispatch_async().result()
                for b, sp, ctx, view, txs, own_staged in pendings:
                    failed = (
                        any(results.get(t) is not None for t in ctx["staged_tokens"])
                        or any(results.get(t) is not None for t, _tx, _e, _f in own_staged)
                        or len(own_staged) < len(txs) - 1
                    )
                    if failed:
                        _INVALIDATIONS.inc("script")
                        break
                    ctx.pop("staged_tokens", None)
                    # parent_entry=None / base_position=sp is the conservative
                    # encoding: later chaining onto this entry requires the live
                    # position to be the entry's block or its selected parent —
                    # both idempotent read positions for its view stack
                    self._publish(_Entry(
                        block=b, selected_parent=sp, ctx=ctx, view=view,
                        parent_entry=None, base_position=sp,
                    ))
                    published += 1
                span.set(published=published)
        except Exception:  # noqa: BLE001 - precompute is an optimization only
            _INVALIDATIONS.inc("error")
        finally:
            # what was computed and not published is computed again by the
            # synchronous path: the failed block, those behind it, a segment
            # that ended in an error
            _CHAIN_COMPUTED.inc(len(pendings))
            _CHAIN_DISCARDED.inc(len(pendings) - published)

    # ------------------------------------------------------------------
    # consumer side (virtual worker, inside _verify_chain_block)
    # ------------------------------------------------------------------

    def take(self, block: bytes, selected_parent: bytes) -> _Entry | None:
        """Pop a usable entry for (block, position==selected_parent), or
        None (synchronous recompute).  Counts the hit/miss."""
        with self._mu:
            entry = self._entries.pop((block, selected_parent), None)
            if entry is not None and self._by_block.get(block) is entry:
                del self._by_block[block]
        if entry is None:
            _MISSES.inc()
            return None
        # no parent-commit-path guard is needed here: a published entry's ctx
        # is a pure function of (block, selected_parent) — publication proves
        # every staged script passed, so the optimistic diffs it chained on
        # equal the committed ones whichever path (cache or synchronous)
        # actually committed them — and the caller just moved utxo_position
        # to selected_parent, which is exactly the state the ctx was
        # computed against
        _HITS.inc()
        return entry

    @staticmethod
    def snapshot() -> dict:
        """Process-wide speculation counters (sim/roundcheck surface)."""
        hits = _HITS.value
        misses = _MISSES.value
        total = hits + misses
        return {
            "hits": hits,
            "misses": misses,
            "precomputes": _PRECOMPUTES.value,
            "invalidations": _INVALIDATIONS.snapshot(),
            "ineligible": _INELIGIBLE.snapshot(),
            "hit_rate": round(hits / total, 4) if total else None,
        }
