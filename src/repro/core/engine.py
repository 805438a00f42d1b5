"""SpecPV generation engine (paper Algorithm 1).

Host-driven loop (vLLM-style) over jitted step functions:

  prefill (chunked) -> [ draft -> verify(mode) -> accept -> commit ]*

Mode automaton (host side, §3.3):
  - context below the partial budget        -> Full verification
  - budget first exceeded                   -> Refresh (full verify +
                                               partial-cache initialisation)
  - buffer has room for one more step       -> Partial verification
  - buffer would overflow                   -> Refresh

State architectures (ssm/hybrid) run chain speculation with native
(windowed/recurrent) verification — partial verification is inapplicable
(DESIGN.md §Arch-applicability).

Continuous-batching support (see docs/architecture.md, docs/serving.md):
batch rows are independent slots.  The per-slot mode is an *operand*,
not control flow: ``step_fused`` runs ONE masked jitted step over any
subset of rows with a per-row mode vector ``[B] int8``
(MODE_FULL/MODE_REFRESH/MODE_PARTIAL) — a tick whose slots want three
different modes still costs a single dispatch.  ``step`` (lock-step)
and ``step_rows`` (grouped per-mode, kept for A/B) are thin wrappers
over the same fused path.  ``prefill_begin_slot`` /
``prefill_step_into_slot`` / ``prefill_finalize_slot`` make per-slot
prefill *resumable*, so the serving scheduler can interleave one prefill
chunk at a time with decode steps (Sarathi/vLLM-style chunked prefill)
instead of stalling every in-flight request for a whole admission.
``prefill_into_slot`` is the blocking wrapper over the same cursor
machinery — both paths run the identical absolute chunk schedule, so
outputs are bit-identical either way.

Lossless stochastic serving rides the same one-dispatch tick: each slot
carries a private PRNG stream (``EngineState.keys`` [B, 2], derived from
the request seed at admission) and a temperature row (``temps`` [B]),
both step operands — greedy rows (temperature 0) select the argmax
acceptance path bit-identically to an all-greedy tick, sampled rows run
SpecInfer multi-round rejection (``core.sampling``), and per-request
``draft="chain"`` slots mask acceptance to the tree's rank-0 chain
(``TreeSpec.chain_mask``) so chain and tree drafts verify together.
"""
from __future__ import annotations

import contextlib
import functools
from collections import defaultdict
from dataclasses import dataclass, field, replace as dc_replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SpecPVConfig, DraftConfig
from repro.models import api
from repro.models import common as cm
from repro.core import draft as dr
from repro.core import tree as tr
from repro.core import verify as vf
from repro.utils import pytree_dataclass, cdiv
from repro.utils.spans import span
from repro.kvcache import cache as kvc
from repro.kvcache.offload import TierManager, TrafficMeter, \
    full_step_bytes, partial_step_bytes, routed_refresh_bytes


@pytree_dataclass
class EngineState:
    cache: Any
    dcache: Any
    pkv_k: Any
    pkv_v: Any
    pkv_pos: Any
    buf_len: jax.Array          # [B]
    pending: jax.Array          # [B, Pmax]
    pending_len: jax.Array      # [B]
    seq_len: jax.Array          # [B]
    ext_tokens: jax.Array       # [B, E]
    ext_feats: jax.Array        # [B, E, 3d]
    ext_len: jax.Array          # [B]
    keys: jax.Array             # [B, 2] per-slot PRNG streams (sampling)
    temps: jax.Array            # [B] per-slot sampling temperature
    # zero-copy partial routing (empty [B, 0, 0, 0] when disabled):
    # per-slot, per-layer, per-kv-head selected LOGICAL block ids,
    # [B, L_attn, Hk, NS] int32 with -1 = unused selection slot.  The
    # physical routing is derived in-jit by gathering the slot's live
    # page table — valid across CoW repoints (bit-identical copies) and
    # protected from demotion/rebinding by the allocator's partial pins.
    pkv_blocks: jax.Array


def request_token_need(prompt_len: int, max_new_tokens: int,
                       buffer_size: int, emax: int) -> int:
    """Tokens of full-cache capacity a request needs end to end: prompt
    + first token + generation budget + the commit overshoot margin (a
    refresh can write buffer_size + tree-path entries past the current
    length).  Single source of truth for page sizing — the engine's
    ``pages_needed`` and the benchmarks both derive from it."""
    return prompt_len + 1 + max_new_tokens + buffer_size + 2 * emax + 2


# per-row verification modes: the SpecPV automaton as an operand of the
# fused step (``SpecPVEngine.step_fused``) instead of control flow
MODE_FULL, MODE_REFRESH, MODE_PARTIAL = 0, 1, 2
MODE_IDS = {"full": MODE_FULL, "refresh": MODE_REFRESH,
            "partial": MODE_PARTIAL}
MODE_NAMES = {v: k for k, v in MODE_IDS.items()}


@dataclass
class StepOutput:
    tokens: np.ndarray          # [B, D+1] accepted tokens (path + bonus)
    counts: np.ndarray          # [B] number of valid tokens (= accept+1)
    accept_len: np.ndarray      # [B]
    mode: str                   # single mode name, or "fused" for a mix
    modes: Optional[np.ndarray] = None  # [B] int8 per-row mode (fused path)


@dataclass
class PrefillCursor:
    """Resumable per-slot prefill state (chunked-prefill interleaving).

    One cursor tracks one in-flight admission between
    ``prefill_begin_slot`` and ``prefill_finalize_slot``.  Each
    ``prefill_step_into_slot`` call advances it by exactly one chunk;
    ``off`` is the *absolute* token offset of the next chunk, and chunk
    boundaries stay absolute multiples of ``chunk`` (a resumed prefill
    runs the identical chunk schedule as a blocking one, so outputs are
    bit-identical).  ``row_cache``/``row_dcache`` carry the slot's
    private cache keys between chunks — for paged engines these are the
    per-row keys only (page table, length, cross rows); the shared pools
    live in the batched ``EngineState`` and are rebound after every
    chunk.  The paged fields record the admission-time page plan (host
    page tables incl. the decode reserve, prefix-cache attach state, and
    the chain entries registered so far for mid-prefill LRU
    re-stamping)."""
    slot: int
    prompt: np.ndarray
    chunk: int
    extra: Optional[Dict]
    off: int                            # absolute offset of the next chunk
    prev_feat: Any                      # [1, 3d] fused boundary feature
    row_cache: Dict                     # per-row cache keys (or the whole
    row_dcache: Dict                    # batch-1 cache when not paged)
    logits_last: Any = None             # last chunk's logits (first token)
    # paged bookkeeping (None / zero when the engine is contiguous)
    pt_host: Optional[np.ndarray] = None
    dpt_host: Optional[np.ndarray] = None
    total_pages: int = 0
    n_match: int = 0                    # prefix-cache blocks attached
    n_full: int = 0                     # full prompt blocks (registrable)
    chain_keys: List[bytes] = field(default_factory=list)
    chain_entries: List[Any] = field(default_factory=list)
    # whole-prompt tail-entry hit: the cursor is born exhausted and
    # finalise boots straight from the stored first token (no logits)
    boot_token: Optional[int] = None
    # per-request sampling knobs (resolved at begin, committed to the
    # slot at finalise): temperature 0 = greedy; `seed` derives the
    # slot's PRNG stream; draft "chain" masks verification to the
    # tree's rank-0 chain
    temperature: float = 0.0
    seed: int = 0
    draft: str = "tree"
    request_id: str = ""                # metadata of the cursor's spans

    @property
    def done(self) -> bool:
        return self.off >= len(self.prompt)

    @property
    def next_tokens(self) -> int:
        """Tokens the next ``prefill_step_into_slot`` call will process
        (0 when done) — the scheduler's per-tick budget accounting."""
        if self.done:
            return 0
        end = min(len(self.prompt),
                  (self.off // self.chunk + 1) * self.chunk)
        return end - self.off


# ---------------------------------------------------------------------------
# per-slot (batch-row) state surgery — continuous batching support.
#
# Every EngineState leaf carries the batch on axis 0 except the full-cache
# dict (axis 1, see kvcache.cache.CACHE_BATCH_AXIS) and the pkv arrays
# (axis 1: [L, B, Hk, P, Dh]).  The PRNG streams are per-slot rows
# ([B, 2] in `keys`) — there is deliberately no batch-free key: a shared
# key would make one slot's draws depend on who else is in the batch.
# ---------------------------------------------------------------------------

_PKV_FIELDS = ("pkv_k", "pkv_v", "pkv_pos")       # batch on axis 1
_ROW_FIELDS = ("buf_len", "pending", "pending_len", "seq_len",
               "ext_tokens", "ext_feats", "ext_len",
               "keys", "temps", "pkv_blocks")     # batch on axis 0


def merge_state_rows(mask, new: EngineState, old: EngineState) -> EngineState:
    """Keep rows of `new` where mask is True, rows of `old` elsewhere."""
    kw = dict(
        cache=kvc.merge_cache_rows(mask, new.cache, old.cache),
        dcache=kvc.merge_draft_rows(mask, new.dcache, old.dcache))
    for f in _PKV_FIELDS:
        nf, of = getattr(new, f), getattr(old, f)
        kw[f] = kvc.select_rows(mask, nf, of, 1) if nf.ndim > 1 else nf
    for f in _ROW_FIELDS:
        kw[f] = kvc.select_rows(mask, getattr(new, f), getattr(old, f), 0)
    return EngineState(**kw)


def write_state_slot(st: EngineState, sub: EngineState, slot) -> EngineState:
    """Write a batch-1 state `sub` into batch row `slot` of `st` (request
    admission after chunked prefill-into-slot, or slot reset)."""
    kw = dict(
        cache=kvc.write_cache_slot(st.cache, sub.cache, slot),
        dcache=kvc.write_draft_slot(st.dcache, sub.dcache, slot))
    for f in _PKV_FIELDS:
        sf, bf = getattr(sub, f), getattr(st, f)
        kw[f] = kvc.write_row(bf, sf, slot, 1) if bf.ndim > 1 else bf
    for f in _ROW_FIELDS:
        kw[f] = kvc.write_row(getattr(st, f), getattr(sub, f), slot, 0)
    return EngineState(**kw)


class SpecPVEngine:
    def __init__(self, cfg: ModelConfig, spec: SpecPVConfig,
                 dcfg: DraftConfig, params, draft_params, *,
                 batch: int, max_len: int,
                 partial_verification: Optional[bool] = None,
                 draft_chain: Optional[bool] = None,
                 temperature: float = 0.0,
                 paged: bool = False,
                 num_pages: Optional[int] = None,
                 num_draft_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 tiered: bool = False,
                 tier_lossless: bool = False,
                 tier_codec: str = "int8",
                 zero_copy: bool = False,
                 mesh=None):
        """``paged=True`` (attention archs only) backs the full KV cache
        with a shared block pool + per-slot page tables: resident memory
        scales with tokens actually held instead of batch x max_len, and
        the serving scheduler gates admission on free pages.  Greedy
        outputs are token-identical to the contiguous layout (the
        default, kept for A/B).  ``num_pages`` sizes the pool; the
        default (batch * max_len/block + 1, incl. the reserved null
        page) matches contiguous capacity so ``generate`` always fits.

        Paged engines also page the *draft* cache over a second,
        same-page-count pool (1 layer vs L, so ~1/L the bytes), and —
        unless ``prefix_cache=False`` — share block-aligned prompt
        prefixes copy-on-write across requests: ``prefill_into_slot``
        attaches cached leading blocks by page-table reference (zero
        prefill FLOPs for the shared prefix) and registers freshly
        prefilled blocks back; pages are refcounted, freed only when the
        last holder releases them, and idle cached prefixes are evicted
        LRU under pool pressure.

        ``tiered=True`` (paged only) adds host residency for cold trunk
        pages (``kvcache.offload.TierManager``): after each refresh the
        slot's committed blocks are demoted to host RAM as int8
        (``tier_codec="fp8"`` casts to e4m3 at the same byte footprint;
        raw fp when ``tier_lossless=True`` — bit-identical round-trip),
        their
        device pages recycled, and they are prefetched back one
        mode-transition ahead of the next refresh (synchronous promote
        when a refresh arrives early).  The trunk pool can then be sized
        near the *hot* working set — decode-reserve blocks + promotion
        headroom — instead of every live token.  ``num_draft_pages``
        sizes the draft pool independently (default: ``num_pages``);
        the draft cache is read every step and never tiered, so a
        tiered deployment keeps a full-size draft pool (~1/L the bytes
        per page) under a shrunken trunk pool.

        ``zero_copy=True`` (paged only) makes the partial KV a
        page-table-routed *view* over the trunk pool: a refresh stores
        the retrieval-selected logical block ids per layer/kv-head
        (``EngineState.pkv_blocks``) and pins the selected physical
        pages (``PageAllocator.pin_slot_pages`` — CoW sources, never
        freed/rebound/demoted), and partial steps stream those pool
        pages directly plus the small dense tail buffer.  The dense
        partial arrays shrink to the buffer alone.  Greedy outputs are
        token-identical to the gathered baseline (the default, kept
        for A/B).

        ``mesh`` (a ``jax.sharding.Mesh`` with a ``data`` and/or
        ``model`` axis) shards the serving engine: batch rows split into
        contiguous per-shard slot ranges over ``data`` (each shard draws
        pages only from its own range of the pool — see
        ``PageAllocator`` — so no host materializes the whole cache or
        batch), trunk weights shard over ``model`` per ``ShardingRules``,
        and the engine state is placed with matching ``NamedSharding``s
        so the one fused dispatch per tick runs SPMD across the mesh
        (docs/architecture.md#mesh--sharding)."""
        self.cfg = cfg
        self.spec = spec
        self.dcfg = dcfg
        self.params = params
        self.dparams = draft_params
        self.batch = batch
        self.max_len = max_len
        self.temperature = temperature
        self.is_attn = cfg.is_attention_arch
        assert not (paged and not self.is_attn), \
            "paged KV is attention-only (state archs keep O(1) state)"
        self.paged = bool(paged)
        self._nb_seq = cdiv(max_len, spec.block_size)
        self.num_pages = (num_pages if num_pages is not None
                          else batch * self._nb_seq + 1)
        self.num_draft_pages = (num_draft_pages if num_draft_pages is not None
                                else self.num_pages)
        # ---- mesh / sharding (single-host when mesh is None) ----------
        self.mesh = mesh
        self._rules = None
        self.data_shards = 1
        self.model_shards = 1
        if mesh is not None:
            from repro.distributed.sharding import ShardingRules
            self._rules = ShardingRules(mesh)
            self.model_shards = self._rules.model_size
            ds = self._rules.data_size
            # graceful degradation (sharding.py _divisible): an
            # indivisible batch keeps the slot registry unsharded
            if ds > 1 and batch % ds == 0:
                self.data_shards = ds
        self._page_alloc = (kvc.PageAllocator(self.num_pages,
                                              shards=self.data_shards,
                                              slot_shard=self.shard_of_slot)
                            if self.paged else None)
        self._draft_alloc = (kvc.PageAllocator(self.num_draft_pages,
                                               shards=self.data_shards,
                                               slot_shard=self.shard_of_slot)
                             if self.paged else None)
        assert not (tiered and not self.paged), \
            "tiered KV residency needs the paged cache (paged=True)"
        self._tier = (TierManager(self._page_alloc, lossless=tier_lossless,
                                  codec=tier_codec)
                      if self.paged and tiered else None)
        self._prefix = (kvc.PrefixCache(spec.block_size)
                        if self.paged and prefix_cache else None)
        # slots with fork-derived sharing still alive: only these can
        # hold a shared page inside a write window (admission sharing
        # never does — full prefix blocks sit below every write window
        # and a tail-entry attach COPIES its block), so pre-step CoW
        # scans exactly this set — empty set, zero cost
        self._forked_slots: set = set()
        self._prefill_skipped_tokens = 0
        if partial_verification is None:
            partial_verification = self.is_attn
        self.partial_enabled = partial_verification and self.is_attn
        # zero-copy partial verification: the partial KV is a routed
        # VIEW over the paged trunk pool (per-slot selected block ids +
        # allocator pins) instead of a gathered copy — a refresh writes
        # O(budget) indices, not O(L x budget x block) bytes.  Greedy
        # outputs stay token-identical to the gathered baseline
        # (docs/architecture.md#zero-copy-partial-kv).
        assert not (zero_copy and not self.paged), \
            "zero-copy partial verification needs the paged cache " \
            "(paged=True); the contiguous layout keeps the gather path"
        self.zero_copy = bool(zero_copy and self.partial_enabled)
        self._ns_blocks = spec.partial_budget_tokens // spec.block_size
        if draft_chain is None:
            draft_chain = not self.is_attn
        branch = ((1,) * dcfg.tree_depth if draft_chain
                  else dcfg.tree_branch[: dcfg.tree_depth])
        self.tree = tr.TreeSpec.from_branch(branch)
        # chain-in-tree: per-request chain drafts mask acceptance to the
        # tree's leftmost (rank-0) chain instead of using a second layout
        self._chain_mask = self.tree.chain_mask()
        self._tree_branching = any(bf > 1 for bf in branch)
        # host mirrors of the per-slot sampling knobs (the device copies
        # live in EngineState.temps / .keys); `step_fused` derives the
        # tick's has_sampled/has_chain variant flags from these
        self._slot_temp = np.full((batch,), float(temperature), np.float32)
        self._slot_chain = np.zeros((batch,), bool)
        self.pmax = spec.buffer_size            # max pending (refresh input)
        self.emax = self.tree.max_path          # max draft-extend per step
        self.traffic = TrafficMeter()
        if self._tier is not None:
            self._tier.traffic = self.traffic   # demote/promote link bytes
        self._pkv_active = False
        self._pkv_active_rows = np.zeros((batch,), bool)   # per-slot automaton
        self.dispatches = 0             # jitted engine steps executed
        self.prefill_dispatches = 0     # jitted prefill chunks launched
        # span counters (repro.utils.spans); a ContinuousScheduler hands
        # the engine its own stats, so both land in one mapping
        self.stats: Dict[str, float] = defaultdict(float)
        self._prefix_dedups = 0         # duplicate blocks collapsed
        if self._rules is not None and jax.device_count() > 1:
            # place trunk + draft weights once; GSPMD propagates the
            # shardings through every jitted step from the operands
            from repro.distributed.sharding import param_shardings
            self.params = jax.device_put(
                self.params, param_shardings(self._rules, self.params))
            self.dparams = jax.device_put(
                self.dparams, param_shardings(self._rules, self.dparams))
        self._build_jits()
        # the destination state dies at the call site (callers rebind), so
        # donate it instead of materialising a second copy of the caches
        self._write_slot = jax.jit(write_state_slot, donate_argnums=(0,))
        self._neutral_sub: Optional[EngineState] = None

    # ------------------------------------------------------------------
    def _build_jits(self):
        cfg, spec, dcfg, tree = self.cfg, self.spec, self.dcfg, self.tree

        def keep_pools(cache, dcache):
            """Pin the shared pools of a prefill step's output to their
            placement (``_pool_sharding``), as ``_keep_placement`` does
            for the fused step."""
            if self.mesh is None or not self.paged:
                return cache, dcache
            wsc = jax.lax.with_sharding_constraint
            return (dict(cache, **{n: wsc(cache[n], self._pool_sharding(
                        cache[n], 1, lane_dense=n in ("k", "v")))
                                   for n in kvc.PAGED_POOL_KEYS}),
                    dict(dcache, **{n: wsc(dcache[n], self._pool_sharding(
                        dcache[n], 0)) for n in kvc.DRAFT_POOL_KEYS}))

        # cache/dcache die at the call site (the chunk loop rebinds), so
        # donate them — for paged engines this keeps the shared pool from
        # being copied once per prefill chunk
        @functools.partial(jax.jit, donate_argnums=(2, 3))
        def _prefill_chunk(params, dparams, cache, dcache, tokens,
                           prev_feat, extra):
            logits, feats, cache = api.prefill(cfg, params, tokens, cache,
                                               extra=extra, spec=spec)
            fused = feats.fused_input()                       # [B, T, 3d]
            shifted = jnp.concatenate([prev_feat[:, None], fused[:, :-1]],
                                      axis=1)
            b, t = tokens.shape
            valid = jnp.ones((b, t), bool)
            dcache, h_last, dlogits = dr.draft_extend(
                cfg, dcfg, dparams, params, dcache, tokens, shifted, valid)
            # the full fused chunk is returned (not just the last column)
            # so the host loop can harvest block-boundary features for
            # prefix-cache registration; prev_feat is fused[:, -1]
            return (*keep_pools(cache, dcache), logits, fused)

        self._prefill_chunk = _prefill_chunk

        # fused multi-cursor prefill: every open cursor's next chunk is
        # packed into ONE ragged [K, Tmax] dispatch — per-row absolute
        # offsets ride in `length` and per-row real token counts in
        # `t_valid` (trailing zero-pads are excluded from KV writes,
        # summaries and length advancement).  Contiguous engines pass a
        # LIST of per-cursor batch-1 cache dicts, concatenated along the
        # batch axes inside the jit and split back on return; paged
        # engines pass one dict over the shared pools with stacked
        # per-row tables.  Keyed by (K, Tmax) via ordinary jit shape
        # specialisation — K is bounded by the engine batch.
        @functools.partial(jax.jit, donate_argnums=(2, 3))
        def _prefill_chunk_fused(params, dparams, cache, dcache, tokens,
                                 t_valid, prev_feat):
            rows = isinstance(cache, (list, tuple))
            if rows:
                cache_in = {n: jnp.concatenate(
                    [r[n] for r in cache],
                    axis=kvc.CACHE_BATCH_AXIS.get(n, 0)) for n in cache[0]}
                dcache_in = {n: jnp.concatenate([r[n] for r in dcache],
                                                axis=0) for n in dcache[0]}
            else:
                cache_in, dcache_in = cache, dcache
            logits, feats, cache_out = api.prefill(
                cfg, params, tokens, cache_in, spec=spec, t_valid=t_valid)
            fused = feats.fused_input()                   # [K, Tmax, 3d]
            shifted = jnp.concatenate([prev_feat[:, None], fused[:, :-1]],
                                      axis=1)
            kb, t = tokens.shape
            valid = jnp.arange(t)[None] < t_valid[:, None]
            dcache_out, h_last, dlogits = dr.draft_extend(
                cfg, dcfg, dparams, params, dcache_in, tokens, shifted,
                valid)
            # per-row boundary feature at the last REAL token — the
            # ragged counterpart of the serial path's fused[:, -1]
            last = jnp.clip(t_valid - 1, 0)
            feat_last = jnp.take_along_axis(fused, last[:, None, None],
                                            axis=1)[:, 0]  # [K, 3d]
            if not rows:
                cache_out, dcache_out = keep_pools(cache_out, dcache_out)
            else:
                def srow(a, i, ax):
                    return jax.lax.slice_in_dim(a, i, i + 1, axis=ax)
                cache_out = [
                    {n: srow(cache_out[n], i, kvc.CACHE_BATCH_AXIS.get(n, 0))
                     for n in cache_out} for i in range(len(cache))]
                dcache_out = [{n: srow(dcache_out[n], i, 0)
                               for n in dcache_out}
                              for i in range(len(dcache))]
            return (cache_out, dcache_out, logits, fused, feat_last)

        self._prefill_chunk_fused = _prefill_chunk_fused

        sample = self.temperature > 0.0

        def _split_keys(st: EngineState, active):
            """Per-slot stream advance: one 3-way split per row per tick
            (draft draws, accept draws, next state).  Only live sampled
            rows advance their stream — a slot's stream position is a
            pure function of its own (seed, steps-sampled) history, never
            of batch composition, admission order or tick mode mix."""
            keys3 = jax.vmap(lambda k: jax.random.split(k, 3))(st.keys)
            adv = active & (st.temps > 0.0)
            keys_next = jnp.where(adv[:, None], keys3[:, 2], st.keys)
            return keys3[:, 0], keys3[:, 1], keys_next

        def _accept(tree_tokens, aux, out, vin, st, key_accept, *,
                    has_sampled: bool, node_valid):
            """Row-select between greedy argmax acceptance and lossless
            speculative sampling.  Greedy rows (temps == 0) take the
            greedy result bit-identically to an all-greedy tick; the
            sampled lanes ride `st.temps` as an operand."""
            path, acc, bonus, _ = tr.greedy_tree_accept(
                tree, tree_tokens, out.logits, vin["root_slot"],
                vin["node_slots"], node_valid=node_valid)
            if has_sampled:
                from repro.core.sampling import tree_speculative_sample
                sampled = st.temps > 0.0
                # discarded greedy lanes still flow through the sampled
                # math: temp 1.0 keeps their softmax finite (no NaNs)
                path_s, acc_s, bonus_s = tree_speculative_sample(
                    tree, tree_tokens, aux, out.logits, vin["root_slot"],
                    vin["node_slots"], key_accept,
                    temperature=jnp.where(sampled, st.temps, 1.0),
                    node_valid=node_valid)
                path = jnp.where(sampled[:, None], path_s, path)
                acc = jnp.where(sampled, acc_s, acc)
                bonus = jnp.where(sampled, bonus_s, bonus)
            return path, acc, bonus

        def _post_accept(st, vin, out, tree_tokens, path, acc, bonus):
            """Shared ext-queue + seq_len bookkeeping. Returns pieces."""
            b = bonus.shape[0]
            d = tree.depth
            path_valid = path >= 0
            path_toks = jnp.take_along_axis(
                tree_tokens, jnp.maximum(path, 0), axis=1)
            path_toks = jnp.where(path_valid, path_toks, 0)
            # new tokens in order: path (acc) then bonus at slot acc
            newtoks = jnp.zeros((b, d + 1), jnp.int32)
            newtoks = newtoks.at[:, :d].set(path_toks)
            newtoks = jnp.where(
                jnp.arange(d + 1)[None] == acc[:, None],
                bonus[:, None], jnp.pad(newtoks[:, : d + 1], ((0, 0), (0, 0))))
            # ext feats: fused at [root_slot, path_slots..] — node_slots
            # carries the per-row layout, so this needs no width knowledge
            fused = out.features.fused_input()                # [B, S, 3d]
            path_slots = jnp.where(
                path_valid,
                jnp.take_along_axis(vin["node_slots"],
                                    jnp.maximum(path, 0), axis=1), 0)
            fslots = jnp.concatenate([vin["root_slot"][:, None], path_slots],
                                     axis=1)                  # [B, D+1]
            ext_feats = jnp.take_along_axis(fused, fslots[..., None], axis=1)
            ext_len = acc + 1
            seq_len = st.seq_len + acc + 1
            return newtoks, ext_feats, ext_len, seq_len

        def _step_fused(params, dparams, st: EngineState, active, modes,
                        is_chain, *,
                        has_full: bool, has_partial: bool,
                        has_refresh: bool, has_sampled: bool,
                        has_chain: bool):
            """One fused multi-mode step over per-row `modes` [B] int8.

            The static flags encode the tick's mode *mix* (which
            branches exist at all), never which row runs what — so a
            tick dispatches exactly one jitted step no matter how its
            slots' automata diverge.  Per-row behaviour rides on the
            mode vector: drafting is mode-invariant and runs once,
            verification row-selects its context source
            (``api.decode(mode="fused")``), and the commits/refresh are
            masked epilogues.  Rows keep the exact operand layouts of
            their single-mode step (``vf.build_verify_inputs_fused``),
            so greedy outputs stay bit-identical to the grouped path.

            ``has_sampled``/``has_chain`` extend the mix the same way:
            per-row temperature (``st.temps``), PRNG streams
            (``st.keys``) and the chain/tree draft shape (`is_chain`
            [B] bool, masking acceptance to the tree's rank-0 chain)
            are all operands, so any greedy/sampled/chain/tree mix is
            still ONE dispatch — and the all-greedy variant traces the
            exact graph of a sampling-free build."""
            b = self.batch
            if has_sampled:
                key_draft, key_accept, keys_next = _split_keys(st, active)
            else:
                key_draft = key_accept = None
                keys_next = st.keys
            # device-side phase scopes: the ops' metadata (``tf_op`` in a
            # profiler trace) names the phase of the step they belong to
            with jax.named_scope("draft"):
                dcache, tree_tokens, aux = dr.draft_phase(
                    cfg, dcfg, dparams, params, tree, st.dcache,
                    st.ext_tokens, st.ext_feats, st.ext_len, active=active,
                    sample_key=key_draft,
                    temperature=(st.temps if has_sampled else 0.0))
            node_valid = None
            if has_chain:
                node_valid = (~is_chain[:, None]
                              | jnp.asarray(self._chain_mask)[None, :])

            is_partial = modes == MODE_PARTIAL
            is_refresh = modes == MODE_REFRESH
            last_tok = jnp.take_along_axis(
                st.pending, jnp.maximum(st.pending_len - 1, 0)[:, None],
                axis=1)[:, 0]
            if has_refresh:
                # refresh rows verify their whole pending run; everyone
                # else collapses to one pend slot holding the newest
                # bonus — per-row widths inside one static shape
                pend_in = jnp.where(
                    is_refresh[:, None], st.pending,
                    jnp.zeros_like(st.pending).at[:, 0].set(last_tok))
                plen_in = jnp.where(is_refresh, st.pending_len, 1)
                p_eff = jnp.where(is_refresh, self.pmax, 1).astype(jnp.int32)
            else:
                pend_in = last_tok[:, None]
                plen_in = jnp.ones((b,), jnp.int32)
                p_eff = jnp.ones((b,), jnp.int32)

            decode_kind = ("fused" if has_full and has_partial
                           else ("full" if has_full else "partial"))
            with jax.named_scope("verify"):
                vin = vf.build_verify_inputs_fused(
                    tree, pend_in, plen_in, p_eff, tree_tokens, st.seq_len,
                    active=active)
                out = api.decode(
                    cfg, params, vin["tokens"], vin["positions"], st.cache,
                    mode=decode_kind, self_mask=vin["self_mask"],
                    pkv=(st.pkv_k, st.pkv_v, st.pkv_pos), spec=spec,
                    emit_queries=has_refresh,
                    partial_rows=(is_partial if decode_kind == "fused"
                                  else None),
                    # zero-copy: route partial rows' retrieved body
                    # through the live page table ([B, L, Hk, NS] ->
                    # [L, B, Hk, NS])
                    pkv_blocks=(jnp.moveaxis(st.pkv_blocks, 0, 1)
                                if self.zero_copy and has_partial else None))

            with jax.named_scope("accept"):
                path, acc, bonus = _accept(
                    tree_tokens, aux, out, vin, st, key_accept,
                    has_sampled=has_sampled, node_valid=node_valid)
                newtoks, ext_feats, ext_len, seq_len = _post_accept(
                    st, vin, out, tree_tokens, path, acc, bonus)

            with jax.named_scope("commit"):
                slots, slot_valid = vf.commit_slots(tree, vin["pend_valid"],
                                                    path, p_eff)
                ck, cv = vf.gather_new_kv(out.new_kv, slots, slot_valid)
            count = plen_in + acc

            cache = st.cache
            pkv_k, pkv_v, pkv_pos = st.pkv_k, st.pkv_v, st.pkv_pos
            pkv_blocks = st.pkv_blocks
            buf_len = st.buf_len
            if has_partial:
                # partial rows append their accepted run to the pkv
                # buffer.  The compaction puts valid entries first and a
                # partial row commits at most 1 + depth of them, so the
                # buffer write is sliced to that width — the exact shape
                # a single-mode partial step uses (and the guarantee the
                # buffer-overflow guard in mode_for is sized for).
                wb = 1 + tree.depth
                cpos = jnp.take_along_axis(vin["positions"],
                                           slots[:, :wb], axis=1)
                count_buf = (jnp.where(is_partial, count, 0)
                             if has_full else count)
                # zero-copy: the dense arrays hold only the buffer, so
                # appends start at offset 0 instead of past the body
                body_len = 0 if self.zero_copy else spec.partial_budget_tokens
                with jax.named_scope("commit"):
                    nk, nv, npos, nbl = vf.append_buffer(
                        pkv_k, pkv_v, pkv_pos, body_len, buf_len,
                        ck[:, :, :wb], cv[:, :, :wb], cpos, count_buf)
                if has_full:   # non-partial rows keep their pkv bits
                    selp = is_partial[None, :, None, None]
                    pkv_k = jnp.where(selp[..., None], nk, pkv_k)
                    pkv_v = jnp.where(selp[..., None], nv, pkv_v)
                    pkv_pos = jnp.where(selp, npos, pkv_pos)
                    buf_len = jnp.where(is_partial, nbl, buf_len)
                else:
                    pkv_k, pkv_v, pkv_pos, buf_len = nk, nv, npos, nbl
            if has_full:
                # full/refresh rows commit exact KV to the full cache;
                # partial rows pass count 0 — their masked write lands
                # beyond `length` (never read, overwritten by their own
                # next refresh) and their summaries recompute to the
                # same bits, so length/summaries stay untouched
                count_full = (jnp.where(is_partial, 0, count)
                              if has_partial else count)
                with jax.named_scope("commit"):
                    cache = vf.append_full_cache(cache, ck, cv, count_full,
                                                 spec)
            if has_refresh:
                # masked epilogue: rebuild refresh rows' partial cache
                # from this step's queries (Quest retrieval over the
                # just-committed cache), leave everyone else's alone
                t_sz = tree.size
                node_w = jnp.zeros((b, t_sz))
                node_w = jnp.where(
                    (jnp.arange(t_sz)[None, None, :]
                     == jnp.maximum(path, 0)[:, :, None])
                    & (path >= 0)[:, :, None], 1.0, 0.0).sum(1)
                s_all = vin["tokens"].shape[1]
                qw = jnp.zeros((b, s_all), jnp.float32)
                qw = qw.at[:, : pend_in.shape[1]].set(
                    vin["pend_valid"].astype(jnp.float32))
                qw = jax.vmap(lambda qr, idx, w: qr.at[idx].add(w))(
                    qw, vin["node_slots"], node_w)
                if self.zero_copy:
                    # routed refresh: write the selected logical block
                    # ids (O(budget) indices) and reset the tail buffer
                    # — no gathered body is ever materialised.  The
                    # host wrapper pins the selected physical pages
                    # right after this dispatch returns.
                    with jax.named_scope("refresh"):
                        nbi = vf.refresh_partial_blocks(
                            cfg, spec, out.queries, qw, cache)
                    nbi = jnp.moveaxis(nbi, 0, 1)   # [B, L_attn, Hk, NS]
                    selb = is_refresh[:, None, None, None]
                    pkv_blocks = jnp.where(selb, nbi, pkv_blocks)
                    selr = is_refresh[None, :, None, None]
                    pkv_pos = jnp.where(selr, -1, pkv_pos)
                    buf_len = jnp.where(is_refresh, 0, buf_len)
                else:
                    with jax.named_scope("refresh"):
                        pk, pv, ppos = vf.refresh_partial_from_queries(
                            cfg, spec, out.queries, qw, cache)
                    pad = spec.buffer_size
                    rk = jnp.pad(pk,
                                 ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
                    rv = jnp.pad(pv,
                                 ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
                    rpos = jnp.pad(ppos, ((0, 0), (0, 0), (0, 0), (0, pad)),
                                   constant_values=-1)
                    selr = is_refresh[None, :, None, None]
                    pkv_k = jnp.where(selr[..., None], rk, pkv_k)
                    pkv_v = jnp.where(selr[..., None], rv, pkv_v)
                    pkv_pos = jnp.where(selr, rpos, pkv_pos)
                    buf_len = jnp.where(is_refresh, 0, buf_len)

            pending_f = jnp.zeros_like(st.pending).at[:, 0].set(bonus)
            if has_partial:
                pending_p = jax.vmap(
                    lambda p_, n_, o_: jax.lax.dynamic_update_slice(
                        p_, n_, (o_,)))(st.pending, newtoks, st.pending_len)
                plen_p = st.pending_len + acc + 1
                if has_full:
                    pending = jnp.where(is_partial[:, None], pending_p,
                                        pending_f)
                    pending_len = jnp.where(is_partial, plen_p, 1)
                else:
                    pending, pending_len = pending_p, plen_p
            else:
                pending = pending_f
                pending_len = jnp.ones((b,), jnp.int32)

            st2 = EngineState(
                cache=cache, dcache=dcache, pkv_k=pkv_k, pkv_v=pkv_v,
                pkv_pos=pkv_pos, buf_len=buf_len, pending=pending,
                pending_len=pending_len, seq_len=seq_len,
                ext_tokens=newtoks, ext_feats=ext_feats, ext_len=ext_len,
                keys=keys_next, temps=st.temps, pkv_blocks=pkv_blocks)
            return st2, (newtoks, acc + 1, acc)

        def _step_state(params, dparams, st: EngineState, active):
            b = self.batch
            if sample:
                key_draft, key_accept, keys_next = _split_keys(st, active)
            else:
                key_draft = key_accept = None
                keys_next = st.keys
            dcache, tree_tokens, aux = dr.draft_phase(
                cfg, dcfg, dparams, params, tree, st.dcache, st.ext_tokens,
                st.ext_feats, st.ext_len, active=active,
                sample_key=key_draft,
                temperature=(st.temps if sample else 0.0))
            pend_in = st.pending[:, :1]
            plen_in = jnp.ones((b,), jnp.int32)
            vin = vf.build_verify_inputs(tree, pend_in, plen_in, tree_tokens,
                                         st.seq_len, active=active)
            out = api.decode(cfg, params, vin["tokens"], vin["positions"],
                             st.cache, self_mask=vin["self_mask"], spec=spec)
            path, acc, bonus = _accept(
                tree_tokens, aux, out, vin, st, key_accept,
                has_sampled=sample, node_valid=None)
            newtoks, ext_feats, ext_len, seq_len = _post_accept(
                st, vin, out, tree_tokens, path, acc, bonus)
            # advance state with [x_b] ++ accepted path (valid = 1 + acc)
            adv_toks = jnp.concatenate([pend_in, jnp.where(
                path >= 0, jnp.take_along_axis(tree_tokens,
                                               jnp.maximum(path, 0), axis=1),
                0)], axis=1)
            adv_valid = (jnp.arange(1 + tree.depth)[None]
                         < (1 + acc)[:, None]) & active[:, None]
            cache = api.advance(cfg, params, adv_toks, st.cache, adv_valid)
            pending = jnp.zeros_like(st.pending)
            pending = pending.at[:, 0].set(bonus)
            st2 = EngineState(
                cache=cache, dcache=dcache, pkv_k=st.pkv_k, pkv_v=st.pkv_v,
                pkv_pos=st.pkv_pos, buf_len=st.buf_len, pending=pending,
                pending_len=jnp.ones((b,), jnp.int32), seq_len=seq_len,
                ext_tokens=newtoks, ext_feats=ext_feats, ext_len=ext_len,
                keys=keys_next, temps=st.temps, pkv_blocks=st.pkv_blocks)
            return st2, (newtoks, acc + 1, acc)

        if self.is_attn:
            # every attention step — lock-step, grouped, or mixed — runs
            # through the SAME fused impl; variants are keyed only by the
            # tick's mode MIX (which masked branches exist at all), so a
            # tick is always exactly one jitted dispatch.  The row merge
            # runs inside the jit and the input state is donated, so
            # untouched rows are preserved without materialising a
            # second copy of the caches.
            self._fused_impl = _step_fused
            self._fused_jits: Dict[Tuple[bool, ...], Any] = {}
        else:
            # no masked variant: continuous batching is attention-only
            # (merge_state_rows assumes the attention cache layout)
            self._step_state = jax.jit(_step_state)

    def _on_mesh(self):
        """The engine's mesh as the ambient mesh while a model step
        traces and runs: XLA cannot partition a Pallas kernel, so the
        kernel wrappers (``kernels.ops``) shard_map themselves over it."""
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _fused_fn(self, has_full: bool, has_partial: bool,
                  has_refresh: bool, has_sampled: bool = False,
                  has_chain: bool = False):
        """The jitted fused-step variant for a mode/sampling mix (built
        lazily — only mixes that actually occur compile).  The variant
        key says which masked branches exist at all, never which row
        runs what; (has_sampled=False, has_chain=False) traces the exact
        all-greedy tree graph a sampling-free build would."""
        key = (has_full, has_partial, has_refresh, has_sampled, has_chain)
        fn = self._fused_jits.get(key)
        if fn is None:
            impl = functools.partial(self._fused_impl, has_full=has_full,
                                     has_partial=has_partial,
                                     has_refresh=has_refresh,
                                     has_sampled=has_sampled,
                                     has_chain=has_chain)

            def run(params, dparams, st, active, modes, is_chain):
                st2, out = impl(params, dparams, st, active, modes,
                                is_chain)
                return (self._keep_placement(merge_state_rows(active, st2,
                                                              st)), out)

            fn = jax.jit(run, donate_argnums=(2,))
            self._fused_jits[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _init_pkv(self, b: int):
        cfg, spec = self.cfg, self.spec
        hk, dh = cfg.num_kv_heads, cfg.head_dim_
        if not self.is_attn:
            z = jnp.zeros((0,))
            return z, z, z
        from repro.models.dense import attn_layer_count
        l_attn = attn_layer_count(cfg.layer_kinds())
        # zero-copy: the retrieved body lives in the pool (routed via
        # pkv_blocks), so the dense arrays carry only the tail buffer
        p_slots = (spec.buffer_size if self.zero_copy
                   else spec.partial_budget_tokens + spec.buffer_size)
        pkv_k = jnp.zeros((l_attn, b, hk, p_slots, dh), cm.dt(cfg.dtype))
        pkv_v = jnp.zeros_like(pkv_k)
        pkv_pos = jnp.full((l_attn, b, hk, p_slots), -1, jnp.int32)
        return pkv_k, pkv_v, pkv_pos

    def _init_pkv_blocks(self, b: int):
        """Per-slot routed-selection table [B, L_attn, Hk, NS] int32
        (-1 = unused slot); an empty [B, 0, 0, 0] placeholder when
        zero-copy routing is off so every EngineState keeps one leaf
        layout."""
        if not self.zero_copy:
            return jnp.zeros((b, 0, 0, 0), jnp.int32)
        from repro.models.dense import attn_layer_count
        l_attn = attn_layer_count(self.cfg.layer_kinds())
        return jnp.full((b, l_attn, self.cfg.num_kv_heads,
                         self._ns_blocks), -1, jnp.int32)

    def _init_cache(self, b: int, *, full_alloc: bool = False) -> Dict:
        """Fresh cache dict.  Paged with ``full_alloc``: every row gets
        its whole max_len worth of pages up front (lock-step
        ``generate`` — memory parity with the contiguous layout; the
        serving path allocates per request instead)."""
        if not self.paged:
            return api.init_cache(self.cfg, b, self.max_len, self.spec)
        cache = api.init_cache(self.cfg, b, self.max_len, self.spec,
                               paged=True, num_pages=self.num_pages)
        if full_alloc:
            al = self._page_alloc
            self._clear_prefix()        # a reset pool invalidates entries
            al.reset()
            if self._tier is not None:
                self._tier.reset()      # host copies of a dead pool
            if b * self._nb_seq > al.capacity:
                raise ValueError(
                    f"paged generate needs {b * self._nb_seq} pages but the "
                    f"pool holds {al.capacity}; raise num_pages or use the "
                    "continuous scheduler (per-request allocation)")
            pt = np.zeros((b, self._nb_seq), np.int32)
            for i in range(b):
                pt[i] = al.alloc(i, self._nb_seq)
            cache["page_table"] = jnp.asarray(pt)
        return cache

    def _init_dcache(self, b: int, *, full_alloc: bool = False) -> Dict:
        """Fresh draft cache; paged engines page it over the second pool
        (same page count as the trunk — one draft layer, so ~1/L the
        bytes of the trunk pool)."""
        if not self.paged:
            return dr.init_draft_cache(self.cfg, b, self.max_len)
        dcache = dr.init_paged_draft_cache(self.cfg, b, self.max_len,
                                           self.spec.block_size,
                                           self.num_draft_pages)
        if full_alloc:
            al = self._draft_alloc
            al.reset()
            pt = np.zeros((b, self._nb_seq), np.int32)
            for i in range(b):
                pt[i] = al.alloc(i, self._nb_seq)
            dcache["page_table"] = jnp.asarray(pt)
        return dcache

    def prefill(self, prompt: np.ndarray, chunk: int = 256,
                extra: Optional[Dict] = None) -> EngineState:
        """Whole-batch chunked prefill; returns the boot state for the
        lock-step ``generate``/``step`` loop (chunk boundaries are
        absolute multiples of `chunk`, see docs/architecture.md)."""
        assert prompt.shape[0] == self.batch
        self._pkv_active = False
        self._pkv_active_rows[:] = False
        self._slot_temp[:] = self.temperature
        self._slot_chain[:] = False
        return self._prefill_state(prompt, chunk, extra)

    def _prefill_state(self, prompt: np.ndarray, chunk: int = 256,
                       extra: Optional[Dict] = None) -> EngineState:
        """Whole-batch chunked prefill (the lock-step ``generate`` path;
        per-slot admission goes through the resumable cursor machinery
        instead, see ``prefill_begin_slot``)."""
        cfg = self.cfg
        b, s0 = prompt.shape
        cache = self._init_cache(b, full_alloc=self.paged)
        dcache = self._init_dcache(b, full_alloc=self.paged)
        prev_feat = jnp.zeros((b, 3 * cfg.d_model), cm.dt(cfg.dtype))
        logits_last = None
        off = 0
        while off < s0:
            end = min(s0, (off // chunk + 1) * chunk)
            toks = jnp.asarray(prompt[:, off: end])
            with self._on_mesh():
                cache, dcache, logits_last, fused = self._prefill_chunk(
                    self.params, self.dparams, cache, dcache, toks,
                    prev_feat, extra)
            prev_feat = fused[:, -1]
            off = end
        return self._boot_state(cache, dcache, logits_last, prev_feat, s0)

    @staticmethod
    def _seed_keys(seed: int, b: int) -> Tuple[jax.Array, jax.Array]:
        """Per-row PRNG streams from a request seed: (k_first [b, 2] —
        the first-token draw, k_stream [b, 2] — the decode stream seeded
        into ``EngineState.keys``).  Derivation depends on nothing but
        (seed, row count), so a request's stream is identical whether it
        boots via full prefill or a tail-entry hit, alone or batched."""
        base = jax.random.split(jax.random.PRNGKey(seed), b)
        pairs = jax.vmap(lambda k: jax.random.split(k, 2))(base)
        return pairs[:, 0], pairs[:, 1]

    def _boot_state(self, cache: Dict, dcache: Dict, logits_last,
                    prev_feat, s0: int, *,
                    temperature: Optional[float] = None,
                    seed: int = 0) -> EngineState:
        """Post-prefill engine state: sample/argmax the first token from
        the final chunk's logits and seed the pending/extend queues.
        Shared by the batch path and the per-slot cursor finalise, so the
        two construct bit-identical automaton state."""
        temp = self.temperature if temperature is None else float(temperature)
        b = prev_feat.shape[0]
        k_first, k_stream = self._seed_keys(seed, b)
        if temp > 0:
            bonus0 = jax.vmap(jax.random.categorical)(
                k_first, logits_last / temp).astype(jnp.int32)
        else:
            bonus0 = jnp.argmax(logits_last, axis=-1).astype(jnp.int32)
        return self._boot_state_from_token(
            cache, dcache, bonus0, prev_feat, s0, keys=k_stream,
            temps=jnp.full((b,), temp, jnp.float32))

    def _boot_state_from_token(self, cache: Dict, dcache: Dict, bonus0,
                               prev_feat, s0: int, *, keys=None,
                               temps=None) -> EngineState:
        """Boot from an already-known first token (the tail-entry fast
        path stores the greedy argmax at registration, so a whole-prompt
        prefix hit rebuilds the identical automaton state with zero
        prefill FLOPs).  ``keys``/``temps`` seed the slot's PRNG stream
        and temperature rows (defaults: seed-0 streams, the engine
        temperature)."""
        cfg = self.cfg
        b = prev_feat.shape[0]
        bonus0 = jnp.asarray(bonus0, jnp.int32)
        if keys is None:
            keys = self._seed_keys(0, b)[1]
        if temps is None:
            temps = jnp.full((b,), self.temperature, jnp.float32)

        pend = jnp.zeros((b, self.pmax), jnp.int32).at[:, 0].set(bonus0)
        ext_tokens = jnp.zeros((b, self.emax), jnp.int32).at[:, 0].set(bonus0)
        ext_feats = jnp.zeros((b, self.emax, 3 * cfg.d_model),
                              cm.dt(cfg.dtype)).at[:, 0].set(prev_feat)
        pkv_k, pkv_v, pkv_pos = self._init_pkv(b)
        # distinct buffers per field: the state may be donated wholesale
        # (slot writes), and donation rejects pytrees with aliased leaves
        return EngineState(
            cache=cache, dcache=dcache, pkv_k=pkv_k, pkv_v=pkv_v,
            pkv_pos=pkv_pos, buf_len=jnp.zeros((b,), jnp.int32),
            pending=pend, pending_len=jnp.ones((b,), jnp.int32),
            seq_len=jnp.full((b,), s0 + 1, jnp.int32),
            ext_tokens=ext_tokens, ext_feats=ext_feats,
            ext_len=jnp.ones((b,), jnp.int32),
            keys=jnp.asarray(keys), temps=jnp.asarray(temps, jnp.float32),
            pkv_blocks=self._init_pkv_blocks(b))

    # ------------------------------------------------------------------
    # per-slot state management (continuous batching)
    # ------------------------------------------------------------------
    def _neutral_state(self, b: int, *, row_cache: bool = False
                       ) -> EngineState:
        """An all-dead state: every row holds one placeholder token so no
        index underflows, and the caches are empty.  ``row_cache`` (paged
        reset sub-state) carries only the per-row cache keys — the shared
        pool stays with the batched state."""
        cfg, spec = self.cfg, self.spec
        if row_cache:
            assert self.paged and b == 1
            cache: Dict = {"page_table": jnp.zeros((1, self._nb_seq),
                                                   jnp.int32),
                           "length": jnp.zeros((1,), jnp.int32)}
            dcache: Dict = {"page_table": jnp.zeros((1, self._nb_seq),
                                                    jnp.int32),
                            "length": jnp.zeros((1,), jnp.int32)}
        else:
            cache = self._init_cache(b)
            dcache = self._init_dcache(b)
        pkv_k, pkv_v, pkv_pos = self._init_pkv(b)
        # distinct buffers per field (donation-safe, see _prefill_state)
        return EngineState(
            cache=cache, dcache=dcache, pkv_k=pkv_k, pkv_v=pkv_v,
            pkv_pos=pkv_pos, buf_len=jnp.zeros((b,), jnp.int32),
            pending=jnp.zeros((b, self.pmax), jnp.int32),
            pending_len=jnp.ones((b,), jnp.int32),
            seq_len=jnp.ones((b,), jnp.int32),
            ext_tokens=jnp.zeros((b, self.emax), jnp.int32),
            ext_feats=jnp.zeros((b, self.emax, 3 * cfg.d_model),
                                cm.dt(cfg.dtype)),
            ext_len=jnp.ones((b,), jnp.int32),
            keys=self._seed_keys(0, b)[1],
            temps=jnp.zeros((b,), jnp.float32),
            pkv_blocks=self._init_pkv_blocks(b))

    def empty_state(self) -> EngineState:
        """Batched state with every slot dead (continuous-scheduler boot)."""
        self._pkv_active_rows[:] = False
        self._slot_temp[:] = 0.0
        self._slot_chain[:] = False
        if self.paged:
            self._clear_prefix()
            self._page_alloc.reset()
            self._draft_alloc.reset()
            self._forked_slots.clear()
            if self._tier is not None:
                self._tier.reset()
        make = functools.partial(self._neutral_state, self.batch)
        if self.mesh is None:
            return make()
        # created directly in its shardings, so no device ever holds the
        # whole state; every later step keeps them (``_keep_placement``)
        return jax.jit(make, out_shardings=self.state_shardings(
            jax.eval_shape(make)))()

    def _clear_prefix(self) -> None:
        if self._prefix is not None:
            self._prefix.clear(self._page_alloc, self._draft_alloc)

    def clear_slot_rows(self, st: EngineState, slot: int) -> EngineState:
        """Zero a slot's *device* rows (page table -> null page, neutral
        automaton scalars) without touching the host allocator.  Masked
        steps execute every batch row and route each row's cache writes
        through its own table/offsets, so an inactive row must never
        keep a stale table: a mid-prefill slot's real table lives in its
        ``PrefillCursor`` while the device row stays neutral.  Consumes
        `st` (buffers donated) — callers must rebind."""
        if self._neutral_sub is None:
            self._neutral_sub = self._neutral_state(1, row_cache=self.paged)
        self._pkv_active_rows[slot] = False
        self._slot_temp[slot] = 0.0
        self._slot_chain[slot] = False
        return self._write_slot(st, self._neutral_sub, jnp.int32(slot))

    def reset_slot(self, st: EngineState, slot: int) -> EngineState:
        """Evict a request: zero the slot's cache rows and automaton
        (paged: clear the slot's page-table rows and release its page
        references — only pages whose refcount drops to zero return to
        the free list; pages still shared with another slot or pinned by
        the prefix cache stay resident.  Pool contents are left stale,
        they are never read once unmapped).  Consumes `st` (buffers
        donated) — callers must rebind."""
        if self.paged:
            self._page_alloc.free_slot(slot)
            self._draft_alloc.free_slot(slot)
            self._forked_slots.discard(slot)
            if self._tier is not None:
                self._tier.drop_slot(slot)
        return self.clear_slot_rows(st, slot)

    # ---- page accounting (host side; no-ops when not paged) ----------
    def pages_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Pages a request needs end to end (see request_token_need),
        assuming a cold prefix cache."""
        toks = request_token_need(prompt_len, max_new_tokens, self.pmax,
                                  self.emax)
        return min(cdiv(toks, self.spec.block_size), self._nb_seq)

    def prefix_match_blocks(self, prompt: np.ndarray,
                            touch: bool = False,
                            shard: Optional[int] = None) -> int:
        """Probe: leading full blocks of `prompt` the prefix cache can
        currently serve (capped one block short of the prompt so the
        tail prefill is never empty).  ``touch`` re-stamps the chain MRU
        — admission gating uses it so a same-tick LRU eviction cannot
        reclaim the blocks it just counted on.  ``shard`` restricts the
        match to entries resident on that data shard."""
        if self._prefix is None:
            return 0
        bs = self.spec.block_size
        entries = self._prefix.match(np.asarray(prompt),
                                     (len(prompt) - 1) // bs,
                                     touch=touch, count=False)
        return len(self._shard_chain(entries, shard))

    def pages_needed_shared(self, prompt: np.ndarray, max_new_tokens: int,
                            touch: bool = False,
                            shard: Optional[int] = None,
                            temperature: Optional[float] = None) -> int:
        """Sharing-aware admission accounting: fresh pages the request
        would need right now — the cold-count minus the blocks the
        prefix cache already holds (those attach by reference).  A
        whole-prompt tail-entry hit discounts every *full* block; the
        tail block itself stays billed (its attach is a fresh-page
        copy, so the page bill matches ``_attach_tail_slot`` exactly —
        admission can never leave the slot owing a page).  ``shard``
        makes the discount per-shard-honest: only entries a slot on
        that shard could actually attach count.  ``temperature`` is the
        *request's* temperature (default: the engine's) — tail-entry
        discounts only apply to greedy requests, whose first token the
        entry stored; non-tail block sharing is temperature-blind (the
        prompt prefill is deterministic either way)."""
        temp = self.temperature if temperature is None else float(temperature)
        need = self.pages_needed(len(prompt), max_new_tokens)
        if self._prefix is not None and temp == 0.0:
            tail = self._prefix.match_tail(np.asarray(prompt), touch=touch,
                                           count=False)
            if tail is not None and (shard is None
                                     or self._tail_on_shard(tail, shard)):
                bs = self.spec.block_size
                return max(need - len(prompt) // bs, 0)
        return max(need - self.prefix_match_blocks(prompt, touch=touch,
                                                   shard=shard), 0)

    def free_pages(self, shard: Optional[int] = None) -> int:
        """Fresh pages available for admission (paged engines are gated
        on the tighter of the trunk and draft pools).  With a sharded
        pool, pass ``shard`` to gate against one shard's range — a
        request admitted to a shard can only ever draw that shard's
        pages."""
        if not self.paged:
            return 1 << 30
        if shard is None or self.data_shards == 1:
            return min(self._page_alloc.free, self._draft_alloc.free)
        return min(self._page_alloc.free_in(shard),
                   self._draft_alloc.free_in(shard))

    # ---- sharded serving (single-host when mesh is None) -------------
    def shard_of_slot(self, slot: int) -> int:
        """The data-mesh shard owning batch row `slot`.  Contiguous
        ranges (``slot * shards // batch``) match how a ``data``-axis
        NamedSharding splits the batch dimension, so a slot's rows,
        pages and host bytes all live on the same device."""
        return slot * self.data_shards // self.batch

    def shard_slots(self, shard: int) -> range:
        """The batch rows owned by `shard` (contiguous)."""
        b, n = self.batch, self.data_shards
        return range(shard * b // n, (shard + 1) * b // n)

    def _shard_chain(self, entries, shard: Optional[int]):
        """Truncate a matched prefix chain at the first entry whose page
        lives off `shard`: a cross-shard attach would reference pages a
        data-parallel host does not hold, breaking per-host residency.
        (Hash-equal blocks re-prefill per shard instead — each shard
        converges on its own physical copy via the dedupe path.)"""
        if shard is None or self.data_shards == 1:
            return entries
        out = []
        for e in entries:
            if self._page_alloc.page_shard(e.page) != shard:
                break
            out.append(e)
        return out

    def _tail_on_shard(self, tail, shard: int) -> bool:
        """May this whole-prompt tail hit serve a slot on `shard`?"""
        if self.data_shards == 1:
            return True
        chain, e = tail
        return (all(self._page_alloc.page_shard(c.page) == shard
                    for c in chain)
                and self._page_alloc.page_shard(e.page) == shard)

    def state_shardings(self, st: EngineState) -> Optional[EngineState]:
        """NamedShardings matching `st` for the engine's mesh (None when
        unsharded).  Per-row operands (page tables, lengths, modes,
        pending/extend queues, the partial cache's batch axis) shard
        over ``data``; the paged pools shard their *page* axis over
        ``data`` (the allocator's contiguous per-shard ranges line up
        with the device split, so each host physically holds exactly
        the pages its slots may reference) and, like the partial cache,
        their kv-head axis over ``model`` (see ``_pool_sharding``);
        contiguous full caches shard batch over ``data`` and sequence
        over ``model``.  Jitting
        the fused step with these as input shardings is what makes the
        one dispatch per tick an SPMD dispatch — one launch *per host*,
        each covering only its slot range."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        bax = "data" if self.data_shards > 1 else None
        max_ = "model" if self.model_shards > 1 else None

        def ns(*spec):
            return NamedSharding(self.mesh, P(*spec))

        def div(n, shards):
            return shards > 1 and n % shards == 0

        pool_spec = self._pool_sharding
        cache_sh = {}
        for k2, v in st.cache.items():
            if k2 in kvc.PAGED_POOL_KEYS and self.paged:  # [L, NP, ...]
                cache_sh[k2] = pool_spec(v, 1, lane_dense=k2 in ("k", "v"))
            elif k2 in ("k", "v", "kmax", "kmin"):      # [L, B, S|NB, ...]
                sax = max_ if div(v.shape[2], self.model_shards) else None
                cache_sh[k2] = ns(None, bax, sax, None, None)
            elif k2 == "page_table":
                cache_sh[k2] = ns(bax, None)
            elif k2 in ("length",):
                cache_sh[k2] = ns(bax)
            elif k2 in ("cross_k", "cross_v"):
                cache_sh[k2] = ns(None, bax, *([None] * (v.ndim - 2)))
            else:
                cache_sh[k2] = ns(*([None] * v.ndim))
        dcache_sh = {}
        for k2, v in st.dcache.items():
            if k2 in kvc.DRAFT_POOL_KEYS and self.paged:
                dcache_sh[k2] = pool_spec(v, 0)         # [NPd, ...]
            elif k2 in ("k", "v"):                      # [B, S, Hk, Dh]
                dcache_sh[k2] = ns(bax, None, None, None)
            elif k2 == "page_table":
                dcache_sh[k2] = ns(bax, None)
            elif k2 == "length":
                dcache_sh[k2] = ns(bax)
            else:
                dcache_sh[k2] = ns(*([None] * v.ndim))

        def rowlike(a):                                 # [B, ...] fields
            return ns(bax, *([None] * (a.ndim - 1)))

        def pkv_spec(a):                                # [L, B, Hk, ...]
            if a.ndim < 3:                              # no-attn placeholder
                return ns(*([None] * a.ndim))
            hax = max_ if div(a.shape[2], self.model_shards) else None
            return ns(None, bax, hax, *([None] * (a.ndim - 3)))

        return EngineState(
            cache=cache_sh, dcache=dcache_sh,
            pkv_k=pkv_spec(st.pkv_k), pkv_v=pkv_spec(st.pkv_v),
            pkv_pos=pkv_spec(st.pkv_pos),
            buf_len=rowlike(st.buf_len), pending=rowlike(st.pending),
            pending_len=rowlike(st.pending_len),
            seq_len=rowlike(st.seq_len),
            ext_tokens=rowlike(st.ext_tokens),
            ext_feats=rowlike(st.ext_feats), ext_len=rowlike(st.ext_len),
            keys=rowlike(st.keys), temps=rowlike(st.temps),
            pkv_blocks=rowlike(st.pkv_blocks))

    def _pool_sharding(self, v, page_axis: int, lane_dense: bool = False):
        """NamedSharding of a paged pool array (trunk ``[L, NP, ...]``
        or draft ``[NP, ...]``): pages over ``data`` (per-shard page
        ranges), kv heads over ``model`` — tensor-parallel attention
        reads only its own heads, which is how the kernels run under a
        mesh (``kernels.ops._per_shard``).  The heads are the second to
        last axis, or with ``lane_dense`` (the trunk's K/V pool
        ``[L, NP, block, Hk*Dh]``) the last one, split only into whole
        heads."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = [None] * v.ndim
        if self.data_shards > 1 and v.shape[page_axis] % self.data_shards == 0:
            spec[page_axis] = "data"
        heads = self.cfg.num_kv_heads if lane_dense else v.shape[-2]
        if self.model_shards > 1 and heads % self.model_shards == 0:
            spec[-1 if lane_dense else -2] = "model"
        return NamedSharding(self.mesh, P(*spec))

    def _keep_placement(self, st: EngineState) -> EngineState:
        """Inside a jitted step: pin the output state to the boot
        placement (``state_shardings``), so the next step's inputs keep
        it — no resharding, no recompile, and donated buffers alias."""
        sh = self.state_shardings(st)
        return st if sh is None else jax.lax.with_sharding_constraint(st, sh)

    def page_capacity(self) -> int:
        return self._page_alloc.capacity if self.paged else 1 << 30

    def reclaim_pages(self, n: int) -> int:
        """LRU-evict idle cached prefixes until `n` pages are freed (or
        no unreferenced entry remains).  Returns trunk pages freed."""
        if self._prefix is None or n <= 0:
            return 0
        return self._prefix.evict_lru(self._page_alloc, self._draft_alloc, n)

    # ---- tiered residency (no-ops when untiered) ---------------------
    @property
    def tiered(self) -> bool:
        return self._tier is not None

    def tier_stats(self) -> Dict[str, int]:
        """Demote/promote/prefetch counters ({} when untiered)."""
        return self._tier.stats() if self._tier is not None else {}

    def _refresh_within(self, pending_len: int, steps: int = 1) -> bool:
        """Could this slot's automaton demand a refresh within `steps`
        more partial steps, under worst-case acceptance (every step
        grows pending by the longest tree path + bonus)?  The prefetch
        trigger: issued one mode-transition ahead, the host->device copy
        overlaps the remaining partial step(s)."""
        return self.mode_for(pending_len + steps * (self.emax + 1),
                             self.spec.partial_budget_tokens + 1,
                             True) == "refresh"

    def tier_admit_margin(self, prompt_len: int) -> int:
        """Extra free pages (beyond the request's own fresh-page bill)
        tiered admission must leave so no live slot's promotion debt can
        outgrow what the pool can ever seat again.  A long-context
        request repays ``prompt_len // block`` pages at its first
        refresh-demotion, so only the *excess* of the worst live debt
        over that repayment must stay free; a request that may never
        cross the partial budget (no refresh, no demotion) reserves the
        full worst debt.  Guarantees every deferred refresh eventually
        seats: free pages can always climb back to the worst debt once
        other slots re-demote."""
        if self._tier is None:
            return 0
        cold_new = (prompt_len // self.spec.block_size
                    if prompt_len > self.spec.partial_budget_tokens else 0)
        return max(self._page_alloc.max_hosted() - cold_new, 0)

    def tier_ready_rows(self, rows: np.ndarray, modes: np.ndarray,
                        force: bool = True) -> Tuple[np.ndarray, int]:
        """Defer full-cache rows whose promotion cannot seat this tick:
        returns (rows mask minus the deferred slots, number deferred).
        A deferred slot simply skips the tick — other slots' post-refresh
        demotions return pages, and ``tier_admit_margin`` bounds every
        debt, so it seats within a tick or two.  If *every* active row
        would defer and ``force`` is set, the smallest debt steps anyway:
        its promote then reclaims idle prefixes or raises loudly instead
        of the scheduler spinning forever.  Callers that made progress
        elsewhere this tick pass ``force=False``: an open chunked-prefill
        cursor holds its whole worst-case page bill until its first
        refresh-demotion (``prefill_begin_slot`` seats everything up
        front), so while one is pumping the pool can be legitimately too
        tight for ANY promotion — the cursor's completion returns the
        pages, and forcing a promote meanwhile would be the exhaustion
        it exists to avoid."""
        if self._tier is None:
            return rows, 0
        al = self._page_alloc
        budget = al.free + al.idle      # promote reclaims idle prefixes
        out = rows.copy()
        deferred = []
        for i in np.nonzero(rows)[0]:
            i = int(i)
            if modes[i] == MODE_PARTIAL:
                continue
            need = al.hosted_count(i)
            if need == 0:
                continue
            if need <= budget:
                budget -= need
            else:
                out[i] = False
                deferred.append((need, i))
        if deferred and not out.any() and force:
            _, i = min(deferred)
            out[i] = True
            deferred = [d for d in deferred if d[1] != i]
        return out, len(deferred)

    def _tier_promote_rows(self, st: EngineState, rows: np.ndarray,
                           modes: np.ndarray) -> EngineState:
        """Pre-dispatch promotion: every stepping row about to read the
        full cache (FULL/REFRESH) gets its hosted pages seated first —
        prefetched segments land free, the rest fall back to synchronous
        transfer (the early-refresh path)."""
        al = self._page_alloc
        for i in np.nonzero(rows)[0]:
            i = int(i)
            if modes[i] == MODE_PARTIAL:
                continue
            need = al.hosted_count(i)
            if need == 0:
                continue
            if need > al.free:
                self.reclaim_pages(need - al.free)
            st = dc_replace(st, cache=self._tier.promote_slot(st.cache, i))
        return st

    def _tier_epilogue(self, st: EngineState, rows: np.ndarray,
                       modes: np.ndarray) -> EngineState:
        """Post-dispatch residency pass: rows that just refreshed return
        to partial mode, so their committed blocks go cold — demote them
        (recycling the device pages); partial rows whose automaton says
        the next refresh is at most one step away start their prefetch."""
        lengths = pending = None
        for i in np.nonzero(rows)[0]:
            i = int(i)
            if modes[i] == MODE_REFRESH and i not in self._forked_slots:
                if lengths is None:
                    lengths = np.asarray(st.cache["length"])
                st = dc_replace(st, cache=self._tier.demote_slot(
                    st.cache, i, int(lengths[i])))
            elif (modes[i] == MODE_PARTIAL
                    and self._page_alloc.hosted_count(i)):
                if pending is None:
                    pending = np.asarray(st.pending_len)
                if self._refresh_within(int(pending[i])):
                    self._tier.prefetch_slot(i)
        return st

    def release_slot_pages(self, slot: int) -> None:
        """Release an evicted slot's page references ahead of the
        deferred row reset, so same-tick admission sees any pages whose
        refcount dropped to zero."""
        if self.paged:
            self._page_alloc.free_slot(slot)
            self._draft_alloc.free_slot(slot)
            self._forked_slots.discard(slot)
            if self._tier is not None:
                self._tier.drop_slot(slot)

    def reset_high_water(self) -> None:
        """Zero the page high-water marks (benchmark warmup)."""
        if self.paged:
            for al in (self._page_alloc, self._draft_alloc):
                al.high_water = 0
                al.resident_high_water = 0
                al.high_water_by = [0] * al.shards

    def reset_prefix_stats(self) -> None:
        """Zero the prefix-cache hit/reuse counters (benchmark warmup);
        cached entries themselves are untouched."""
        self._prefill_skipped_tokens = 0
        self._prefix_dedups = 0
        if self._prefix is not None:
            self._prefix.reset_stats()

    def page_stats(self) -> Dict[str, int]:
        al = self._page_alloc
        if al is None:
            return {}
        out = dict(num_pages=self.num_pages, capacity=al.capacity,
                   in_use=al.in_use, idle=al.idle, committed=al.committed,
                   high_water=al.high_water,
                   resident_high_water=al.resident_high_water,
                   draft_num_pages=self.num_draft_pages,
                   draft_in_use=self._draft_alloc.in_use,
                   draft_high_water=self._draft_alloc.high_water,
                   contiguous_pages=self.batch * self._nb_seq,
                   block_size=self.spec.block_size,
                   pinned_pages=al.pinned_pages)
        if self.data_shards > 1:
            out["data_shards"] = self.data_shards
            out["peak_pages_per_host"] = al.peak_pages_per_host
            for s in range(al.shards):
                out[f"high_water_shard_{s}"] = al.high_water_by[s]
        if self._tier is not None:
            out.update(self._tier.stats())
        return out

    def prefix_stats(self) -> Dict[str, int]:
        """Prefix-cache counters ({} when sharing is off): hit/seen
        blocks, tokens whose prefill was skipped, entries resident."""
        if self._prefix is None:
            return {}
        out = self._prefix.stats()
        out["prefill_tokens_skipped"] = self._prefill_skipped_tokens
        out["dedups"] = self._prefix_dedups
        return out

    def save_prefix_state(self, st: EngineState) -> Optional[dict]:
        """Host-side snapshot of the prefix cache *with* pool bytes,
        suitable for re-attachment after an engine rebuild
        (``restore_prefix_state``).  None when sharing is off."""
        if self._prefix is None:
            return None

        def page_bytes(page: int, draft_page: int) -> dict:
            return {
                "trunk": {k: np.asarray(st.cache[k][:, page])
                          for k in kvc.PAGED_POOL_KEYS},
                "draft": {k: np.asarray(st.dcache[k][draft_page])
                          for k in kvc.DRAFT_POOL_KEYS},
            }

        return self._prefix.save_state(page_bytes)

    def restore_prefix_state(self, st: EngineState, snap: Optional[dict],
                             shard: int = 0
                             ) -> Tuple[EngineState, int]:
        """Re-seat a ``save_prefix_state`` snapshot into this (possibly
        freshly built) engine: each surviving entry gets cache-only
        pages from shard ``shard`` of both pools and its KV blob written
        back, after the chain-hash re-verification ``load_state``
        performs.  Returns (state, entries restored); consumes `st`."""
        if self._prefix is None or snap is None or not self.paged:
            return st, 0
        cache = dict(st.cache)
        dcache = dict(st.dcache)

        def seat_pages(d: dict, sh: int) -> Tuple[int, int]:
            (page,) = self._page_alloc.alloc_cache(1, sh)
            try:
                (dpage,) = self._draft_alloc.alloc_cache(1, sh)
            except RuntimeError:
                self._page_alloc.dec_ref([page], cache=True)
                raise
            for k, blob in d["pages"]["trunk"].items():
                cache[k] = cache[k].at[:, page].set(blob)
            for k, blob in d["pages"]["draft"].items():
                dcache[k] = dcache[k].at[dpage].set(blob)
            return page, dpage

        n = self._prefix.load_state(snap, self._page_alloc,
                                    self._draft_alloc, seat_pages,
                                    shard=shard)
        if n:
            st = dc_replace(st, cache=cache, dcache=dcache)
        return st, n

    # ------------------------------------------------------------------
    # resumable per-slot prefill (chunked-prefill interleaving)
    # ------------------------------------------------------------------
    def prefill_begin_slot(self, st: EngineState, slot: int,
                           prompt: np.ndarray, chunk: int = 256,
                           extra: Optional[Dict] = None,
                           max_new_tokens: Optional[int] = None,
                           temperature: Optional[float] = None,
                           seed: int = 0, draft: str = "tree",
                           request_id: str = ""
                           ) -> Tuple[EngineState, PrefillCursor]:
        """Open a resumable prefill of `prompt` into batch row `slot`.
        Returns (state, cursor); drive the cursor with
        ``prefill_step_into_slot`` (one chunk per call) and commit it
        with ``prefill_finalize_slot``.  Consumes `st` — callers must
        rebind.

        ``temperature``/``seed``/``draft`` are the request's sampling
        knobs (default: the engine temperature, seed 0, tree drafts) —
        ``prefill_finalize_slot`` commits them to the slot, deriving its
        private PRNG stream from the seed so the token stream is
        reproducible regardless of batch composition or admission
        order.  ``draft="chain"`` serves the slot with single-chain
        verification (acceptance masked to the tree's rank-0 chain) in
        the same fused tick as tree slots.  ``request_id`` only labels
        the cursor's prefill spans.

        All admission-time page accounting happens here, up front: the
        prefix cache is consulted (matched leading blocks attach by
        page-table reference — their prefill is skipped entirely) and
        the *whole* page plan — fresh prompt blocks plus the decode
        reserve sized by ``max_new_tokens`` (default: the remaining
        max_len budget) — is allocated immediately, so later steps can
        never fail on pool exhaustion no matter what is admitted in
        between.  Raises RuntimeError (with the attach rolled back) when
        the pools cannot cover the request even after LRU prefix
        eviction — callers should gate admission on
        ``free_pages()``/``pages_needed_shared()`` first.

        The slot's device rows are cleared (page table -> null page):
        masked decode steps may run between chunks, and an inactive row
        must never route its masked writes through a stale table."""
        prompt = np.asarray(prompt)
        cfg = self.cfg
        temp = (self.temperature if temperature is None
                else float(temperature))
        knobs = dict(temperature=temp, seed=int(seed), draft=draft,
                     request_id=request_id)
        if not self.paged:
            cur = PrefillCursor(
                slot=slot, prompt=prompt, chunk=chunk, extra=extra, off=0,
                prev_feat=jnp.zeros((1, 3 * cfg.d_model), cm.dt(cfg.dtype)),
                row_cache=self._init_cache(1),
                row_dcache=self._init_dcache(1), **knobs)
            return self.clear_slot_rows(st, slot), cur

        al, dal = self._page_alloc, self._draft_alloc
        al.free_slot(slot)                      # stale pages, if any
        dal.free_slot(slot)
        self._forked_slots.discard(slot)        # fresh request, no fork
        if self._tier is not None:
            self._tier.drop_slot(slot)          # stale host copies too
        bs = self.spec.block_size
        budget = (max_new_tokens if max_new_tokens is not None
                  else max(self.max_len - len(prompt), 0))
        total_pages = self.pages_needed(len(prompt), budget)

        # ---- prefix-cache consult: attach matched leading blocks ------
        # the chain hash keys on prompt tokens only, but with modality
        # conditioning (`extra`) the trunk KV past a cross-attention
        # layer depends on the encoder states too — sharing would attach
        # KV computed under another request's conditioning
        assert extra is None or self._prefix is None, \
            "prefix sharing cannot key per-request `extra` conditioning; " \
            "build the engine with prefix_cache=False"
        # whole-prompt fast path: every full block chains AND the final
        # partial block's exact tokens are registered — attach all of it
        # (the tail page speculatively, CoW covers the divergent writes)
        # and boot from the stored first token with ZERO prefill FLOPs
        # tail entries store a *greedy* first token, so the zero-FLOP
        # boot only serves greedy requests; sampled requests still share
        # their full prompt blocks below (prefill is deterministic)
        tail = (self._prefix.match_tail(prompt)
                if self._prefix is not None and temp == 0.0
                else None)
        if tail is not None and not self._tail_on_shard(
                tail, self.shard_of_slot(slot)):
            tail = None                 # entry lives on another shard
        if tail is not None:
            return self._attach_tail_slot(st, slot, prompt, chunk, extra,
                                          total_pages, tail, knobs)
        # attach BEFORE any reclaim: slot-referenced pages are never LRU
        # eviction candidates, so reclaiming for the fresh remainder
        # cannot cannibalise the chain this admission just matched
        entries = (self._prefix.match(prompt, (len(prompt) - 1) // bs)
                   if self._prefix is not None else [])
        entries = self._shard_chain(entries, self.shard_of_slot(slot))
        n_match = len(entries)
        pt_host = np.zeros((self._nb_seq,), np.int32)
        dpt_host = np.zeros((self._nb_seq,), np.int32)
        prev_feat = None
        if n_match:
            al.attach(slot, [e.page for e in entries])
            dal.attach(slot, [e.draft_page for e in entries])
            pt_host[:n_match] = [e.page for e in entries]
            dpt_host[:n_match] = [e.draft_page for e in entries]
            prev_feat = jnp.asarray(entries[-1].feat)[None]
        fresh = total_pages - n_match
        shard = self.shard_of_slot(slot)
        if fresh > self.free_pages(shard):
            self.reclaim_pages(fresh - self.free_pages(shard))
        if fresh > self.free_pages(shard):
            al.free_slot(slot)              # roll the attach back
            dal.free_slot(slot)
            raise RuntimeError(
                f"slot {slot}: request needs {fresh} fresh pages "
                f"({n_match} shared), {al.free_in(shard)}/"
                f"{dal.free_in(shard)} free (trunk/draft, shard {shard}) "
                f"of {al.shard_capacity(shard)}")
        if n_match:
            self._prefill_skipped_tokens += n_match * bs
        start_len = n_match * bs
        assert start_len < len(prompt), \
            "prefix match must leave a non-empty tail"
        if fresh:                           # tail blocks + decode reserve
            pt_host[n_match:total_pages] = al.alloc(slot, fresh)
            dpt_host[n_match:total_pages] = dal.alloc(slot, fresh)
        if prev_feat is None:
            prev_feat = jnp.zeros((1, 3 * cfg.d_model), cm.dt(cfg.dtype))

        row_cache: Dict = {"page_table": jnp.asarray(pt_host)[None],
                           "length": jnp.full((1,), start_len, jnp.int32)}
        for n in ("cross_k", "cross_v"):
            if n in st.cache:
                row_cache[n] = st.cache[n][:, slot: slot + 1]
        row_dcache: Dict = {"page_table": jnp.asarray(dpt_host)[None],
                            "length": jnp.full((1,), start_len, jnp.int32)}
        n_full = len(prompt) // bs
        cur = PrefillCursor(
            slot=slot, prompt=prompt, chunk=chunk, extra=extra,
            off=start_len, prev_feat=prev_feat,
            row_cache=row_cache, row_dcache=row_dcache,
            pt_host=pt_host, dpt_host=dpt_host, total_pages=total_pages,
            n_match=n_match, n_full=n_full,
            chain_keys=(self._prefix.chain_keys(prompt, n_full)
                        if self._prefix is not None and n_full > n_match
                        else []),
            chain_entries=list(entries), **knobs)
        return self.clear_slot_rows(st, slot), cur

    @staticmethod
    def _copy_pool_page(cache: Dict, src: int, dst: int, *,
                        draft: bool) -> Dict:
        """Device-side copy of one physical page's contents — every pool
        key (KV and, for the trunk, the physical-page summaries) — from
        page `src` to page `dst`.  Single source of the copy used by the
        tail-entry attach and registration paths (``prepare_cow`` keeps
        its own batched form)."""
        out = dict(cache)
        keys = kvc.DRAFT_POOL_KEYS if draft else kvc.PAGED_POOL_KEYS
        for n in keys:
            a = out[n]
            out[n] = (a.at[dst].set(a[src]) if draft
                      else a.at[:, dst].set(a[:, src]))
        return out

    def _attach_tail_slot(self, st: EngineState, slot: int,
                          prompt: np.ndarray, chunk: int,
                          extra: Optional[Dict], total_pages: int,
                          tail, knobs: Optional[Dict] = None
                          ) -> Tuple[EngineState, PrefillCursor]:
        """Whole-prompt tail-entry hit: attach the full-block chain by
        page-table reference, materialise the final partial block as a
        device page COPY of the cached one, skip prefill entirely, and
        boot from the entry's stored boundary feature + greedy first
        token.  The tail block is copied (not ref-shared) because it
        sits exactly where this slot's first decode commit lands —
        copying at admission keeps the invariant that only *forked*
        slots ever hold a shared page inside a write window (so
        ``prepare_cow`` stays a free no-op for admission sharing) and
        leaves no deferred page debt: the tail block is billed as a
        fresh page by ``pages_needed_shared``, exactly like a non-tail
        prefix hit's first uncached block."""
        entries, te = tail
        al, dal = self._page_alloc, self._draft_alloc
        n_match = len(entries)
        pt_host = np.zeros((self._nb_seq,), np.int32)
        dpt_host = np.zeros((self._nb_seq,), np.int32)
        al.attach(slot, [e.page for e in entries])
        dal.attach(slot, [e.draft_page for e in entries])
        pt_host[: n_match] = [e.page for e in entries]
        dpt_host[: n_match] = [e.draft_page for e in entries]
        fresh = total_pages - n_match          # incl. the tail block
        if fresh > min(al.free, dal.free):
            self.reclaim_pages(fresh - min(al.free, dal.free))
        if fresh > min(al.free, dal.free):
            al.free_slot(slot)              # roll the attach back
            dal.free_slot(slot)
            raise RuntimeError(
                f"slot {slot}: request needs {fresh} fresh pages "
                f"({n_match} shared), {al.free}/{dal.free} "
                f"free (trunk/draft) of {al.capacity}")
        pt_host[n_match: total_pages] = al.alloc(slot, fresh)
        dpt_host[n_match: total_pages] = dal.alloc(slot, fresh)
        # device-side page copy: the slot's private tail block takes the
        # cached page's KV + summaries (draft likewise)
        st = dc_replace(
            st,
            cache=self._copy_pool_page(st.cache, te.page,
                                       int(pt_host[n_match]), draft=False),
            dcache=self._copy_pool_page(st.dcache, te.draft_page,
                                        int(dpt_host[n_match]), draft=True))
        self._prefill_skipped_tokens += len(prompt)
        row_cache: Dict = {"page_table": jnp.asarray(pt_host)[None],
                           "length": jnp.full((1,), len(prompt), jnp.int32)}
        for n in ("cross_k", "cross_v"):
            if n in st.cache:
                row_cache[n] = st.cache[n][:, slot: slot + 1]
        row_dcache: Dict = {"page_table": jnp.asarray(dpt_host)[None],
                            "length": jnp.full((1,), len(prompt),
                                               jnp.int32)}
        cur = PrefillCursor(
            slot=slot, prompt=prompt, chunk=chunk, extra=extra,
            off=len(prompt), prev_feat=jnp.asarray(te.feat)[None],
            row_cache=row_cache, row_dcache=row_dcache,
            pt_host=pt_host, dpt_host=dpt_host, total_pages=total_pages,
            n_match=n_match, n_full=n_match, boot_token=te.first_token,
            **(knobs or {}))
        return self.clear_slot_rows(st, slot), cur

    def _register_tail(self, st: EngineState, cur: PrefillCursor
                       ) -> EngineState:
        """Register a finished prompt's final *partial* block as a
        whole-prompt tail entry, then immediately hand the registering
        slot a private copy of that block (``cow_write`` + pool page
        copy): the slot's next decode commit writes into this very
        block, and the cached KV must stay frozen for future attaches.
        Skipped for block-aligned prompts, incomplete chains, sampled
        requests (the stored first token is the greedy argmax), or when
        no page is free for the copy."""
        if (not self.paged or self._prefix is None
                or cur.temperature != 0.0):
            return st
        bs = self.spec.block_size
        prompt = cur.prompt
        n_full = len(prompt) // bs
        rem = len(prompt) - n_full * bs
        al, dal = self._page_alloc, self._draft_alloc
        if rem == 0 or self.free_pages(self.shard_of_slot(cur.slot)) < 1:
            return st
        if n_full and len(cur.chain_entries) < n_full:
            return st          # chain incomplete: the tail'd be orphaned
        parent = (cur.chain_entries[-1].key if n_full
                  else kvc.PrefixCache._ROOT)
        e = self._prefix.register_tail(
            parent, prompt[n_full * bs:], n_full,
            int(cur.pt_host[n_full]), int(cur.dpt_host[n_full]),
            np.asarray(cur.prev_feat[0]),
            int(np.asarray(jnp.argmax(cur.logits_last, axis=-1))[0]),
            al, dal)
        if e is None:
            return st
        for ent in cur.chain_entries:   # parent never older than the tail
            ent.tick = e.tick
        old, new = al.cow_write(cur.slot, n_full)
        cache = self._copy_pool_page(st.cache, old, new, draft=False)
        dold, dnew = dal.cow_write(cur.slot, n_full)
        dcache = self._copy_pool_page(st.dcache, dold, dnew, draft=True)
        cur.pt_host[n_full] = new
        cur.dpt_host[n_full] = dnew
        cur.row_cache = dict(cur.row_cache,
                             page_table=cur.row_cache["page_table"]
                             .at[0, n_full].set(new))
        cur.row_dcache = dict(cur.row_dcache,
                              page_table=cur.row_dcache["page_table"]
                              .at[0, n_full].set(dnew))
        return dc_replace(st, cache=cache, dcache=dcache)

    def prefill_step_into_slot(self, st: EngineState, cur: PrefillCursor
                               ) -> Tuple[EngineState, int]:
        """Advance `cur` by exactly one chunk.  Chunk boundaries stay
        absolute multiples of ``cur.chunk`` regardless of where the
        cursor resumes, so an interleaved prefill runs the identical
        chunk schedule (and produces bit-identical caches) as a blocking
        one.  Returns (state, tokens processed).  Consumes `st` — paged
        pools are written in place and rebound into the batched state
        after every chunk, so masked decode steps may run between calls.

        Freshly completed prompt blocks are registered into the prefix
        cache *as they finish*, so concurrent admissions can share a
        long prefix before this prefill completes; each registration
        re-stamps the whole chain with one LRU tick (a parent is never
        older than its children)."""
        assert not cur.done, "prefill cursor already exhausted"
        s0 = len(cur.prompt)
        off = cur.off
        end = min(s0, (off // cur.chunk + 1) * cur.chunk)
        rid = cur.request_id
        with span(self.stats, "engine.prefill.dispatch", request_id=rid):
            toks = jnp.asarray(cur.prompt[None, off: end])
            if self.paged:
                sub_cache = {n: st.cache[n] for n in kvc.PAGED_POOL_KEYS}
                sub_cache.update(cur.row_cache)
                sub_dcache = {n: st.dcache[n] for n in kvc.DRAFT_POOL_KEYS}
                sub_dcache.update(cur.row_dcache)
            else:
                sub_cache, sub_dcache = cur.row_cache, cur.row_dcache
            with self._on_mesh():
                cache, dcache, logits_last, fused = self._prefill_chunk(
                    self.params, self.dparams, sub_cache, sub_dcache, toks,
                    cur.prev_feat, cur.extra)
            self.prefill_dispatches += 1

            cur.prev_feat = fused[:, -1]
            cur.logits_last = logits_last
            if self.paged:
                # the pools were written in place (batch-1 view); rebind
                # them into the batched state so interleaved decode steps
                # see the chunk, and keep only the per-row keys in the
                # cursor
                cur.row_cache = {n: v for n, v in cache.items()
                                 if n not in kvc.PAGED_POOL_KEYS}
                cur.row_dcache = {n: v for n, v in dcache.items()
                                  if n not in kvc.DRAFT_POOL_KEYS}
                pool = {n: cache[n] for n in kvc.PAGED_POOL_KEYS}
                dpool = {n: dcache[n] for n in kvc.DRAFT_POOL_KEYS}
                st = dc_replace(st, cache=dict(st.cache, **pool),
                                dcache=dict(st.dcache, **dpool))
            else:
                cur.row_cache, cur.row_dcache = cache, dcache
            feats = self._block_feats(cur, off, end, fused, 0)
        with span(self.stats, "engine.prefill.wait", request_id=rid):
            feats = jax.device_get(feats)
        # registration runs AFTER the row-cache rebind: a hash-equal
        # dedupe repoints the cursor's page table, and that edit must
        # land on the rebound row cache, not be clobbered by it
        with span(self.stats, "engine.prefill.register", request_id=rid):
            self._register_blocks(cur, off, feats)
        cur.off = end
        return st, end - off

    def _block_feats(self, cur: PrefillCursor, off: int, end: int,
                     fused, row: int) -> List:
        """The draft boot features of the prompt blocks the chunk
        ``[off, end)`` completes and the prefix cache registers (none
        without a cache): the block-boundary columns of row ``row`` of
        the chunk's [K, T, 3d] fused features, still on the device."""
        if not (self.paged and self._prefix is not None and cur.n_full):
            return []
        bs = self.spec.block_size
        lo, hi = off // bs, min(end // bs, cur.n_full)
        if hi <= lo:
            return []
        fused_row = fused[row]
        return [fused_row[(j + 1) * bs - 1 - off] for j in range(lo, hi)]

    def _register_blocks(self, cur: PrefillCursor, off: int,
                         feats: List[np.ndarray]) -> None:
        """Register the prompt blocks completed by the chunk starting at
        ``off`` into the prefix cache, re-stamping the whole chain with
        one LRU tick (a parent may never be older than its children, or
        eviction could drop a chain head and orphan the tail).
        ``feats`` are the host copies of ``_block_feats``: one boot
        feature per block, in block order.

        A block some concurrent admission already registered under the
        same chain key is *deduplicated* instead: this cursor's freshly
        computed page is collapsed onto the cached entry's page (see
        ``_dedupe_block``), so same-tick cold admissions of a shared
        prompt converge on ONE physical copy."""
        if not feats:
            return
        bs = self.spec.block_size
        tick = self._prefix.new_tick()
        for e in cur.chain_entries:
            e.tick = tick
        for j, feat in enumerate(feats, off // bs):
            e = self._prefix.insert(
                cur.chain_keys[j], j, int(cur.pt_host[j]),
                int(cur.dpt_host[j]), np.asarray(feat),
                self._page_alloc, self._draft_alloc, tick=tick,
                tokens=cur.prompt[j * bs:(j + 1) * bs],
                parent=(cur.chain_keys[j - 1] if j > 0
                        else kvc.PrefixCache._ROOT))
            if e is None:
                e = self._prefix.entry(cur.chain_keys[j])
                self._dedupe_block(cur, j, e)
            cur.chain_entries.append(e)

    def _dedupe_block(self, cur: PrefillCursor, j: int,
                      e: "kvc._PrefixEntry") -> None:
        """Collapse block ``j`` of a mid-prefill cursor onto an existing
        prefix-cache entry for the same chain key.  Hash-equal blocks
        hold bit-identical KV (same prompt prefix, deterministic
        compute, absolute chunk boundaries), so repointing is lossless:
        the slot takes a reference on the entry's page, releases its own
        duplicate back to the pool, and rewrites the host + device page
        tables.  This is how two cold admissions of the same prompt that
        race past each other's ``match()`` still end up sharing.

        Sharded pools only dedupe within a shard: collapsing onto a
        page another data shard owns would make this host reference
        pages it does not hold, so cross-shard duplicates keep their
        private copy (one physical copy per shard, by design)."""
        if int(cur.pt_host[j]) == e.page:
            return                      # already shared (admission match)
        if (self.data_shards > 1
                and self._page_alloc.page_shard(e.page)
                != self._page_alloc.slot_shard(cur.slot)):
            return                      # entry lives on another shard
        self._page_alloc.rebind_block(cur.slot, j, e.page)
        self._draft_alloc.rebind_block(cur.slot, j, e.draft_page)
        cur.pt_host[j] = e.page
        cur.dpt_host[j] = e.draft_page
        cur.row_cache = dict(cur.row_cache,
                             page_table=cur.row_cache["page_table"]
                             .at[0, j].set(e.page))
        cur.row_dcache = dict(cur.row_dcache,
                              page_table=cur.row_dcache["page_table"]
                              .at[0, j].set(e.draft_page))
        self._prefix_dedups += 1

    def prefill_step_fused(self, st: EngineState,
                           cursors: Sequence[PrefillCursor]
                           ) -> Tuple[EngineState, int]:
        """Advance EVERY open cursor by one chunk in a single fused
        dispatch (``_prefill_chunk_fused``) — the prefill counterpart of
        the fused decode step: the per-row chunk offsets and ragged
        token counts travel as operands, so N open admissions cost one
        kernel launch per tick instead of N.

        Each row runs the identical absolute chunk schedule the serial
        path would (``end = min(len, (off//chunk + 1)*chunk)``), pads
        are zero-packed on the right and masked out of every KV write,
        summary and length update, and no key-axis reassociation occurs
        — so the resulting caches and tokens are bit-identical to
        stepping the cursors one at a time.  Prefix-cache registration
        harvests block features per row in cursor order, so two cursors
        completing the same prompt block in one tick dedupe exactly as
        they would across serial steps.

        Per-request ``extra`` conditioning cannot be batched (each row
        would need its own encoder states) — callers route such cursors
        through ``prefill_step_into_slot``.  Returns
        (state, total tokens processed).  Consumes `st`."""
        cursors = [c for c in cursors if not c.done]
        assert cursors, "no open prefill cursor"
        assert all(c.extra is None for c in cursors), \
            "fused prefill cannot batch per-request `extra` conditioning"
        rids = ",".join(c.request_id for c in cursors)
        with span(self.stats, "engine.prefill.dispatch", request_id=rids):
            st, offs, ends, feats = self._prefill_dispatch(st, cursors)
        with span(self.stats, "engine.prefill.wait", request_id=rids):
            feats = jax.device_get(feats)
        # cursor order = admission (FIFO) order: cursor B completing a
        # block cursor A just registered this same tick collapses onto
        # A's page here
        with span(self.stats, "engine.prefill.register", request_id=rids):
            for cur, off, f in zip(cursors, offs, feats):
                self._register_blocks(cur, off, f)
        for cur, end in zip(cursors, ends):
            cur.off = end
        return st, sum(ends) - sum(offs)

    def _prefill_dispatch(self, st: EngineState,
                          cursors: Sequence[PrefillCursor]):
        """The dispatching half of ``prefill_step_fused``: one fused
        chunk for every cursor, each cursor's row keys rebound, and the
        pools rebound into ``st``.  Returns (state, chunk offsets, chunk
        ends, each cursor's ``_block_feats`` still on the device)."""
        k = len(cursors)
        offs = [c.off for c in cursors]
        ends = [min(len(c.prompt), (c.off // c.chunk + 1) * c.chunk)
                for c in cursors]
        nvalid = [e - o for o, e in zip(offs, ends)]
        tmax = max(nvalid)
        toks = np.zeros((k, tmax), np.int32)
        for i, c in enumerate(cursors):
            toks[i, : nvalid[i]] = c.prompt[offs[i]: ends[i]]
        t_valid = jnp.asarray(np.asarray(nvalid, np.int32))
        prev_feat = jnp.concatenate([c.prev_feat for c in cursors], axis=0)
        if self.paged:
            # one sub-state over the shared pools: every per-row cursor
            # key (page table, `length` = the row's pre-chunk token
            # count — the cursor invariant off == resident length —
            # plus any conditioning rows) concatenated along its batch
            # axis, exactly the serial sub_cache stacked K-high
            ax = kvc.CACHE_BATCH_AXIS
            sub_cache = {n: st.cache[n] for n in kvc.PAGED_POOL_KEYS}
            sub_cache.update(
                {n: jnp.concatenate([c.row_cache[n] for c in cursors],
                                    axis=ax.get(n, 0))
                 for n in cursors[0].row_cache})
            sub_dcache = {n: st.dcache[n] for n in kvc.DRAFT_POOL_KEYS}
            sub_dcache.update(
                {n: jnp.concatenate([c.row_dcache[n] for c in cursors],
                                    axis=0)
                 for n in cursors[0].row_dcache})
        else:
            sub_cache = [c.row_cache for c in cursors]
            sub_dcache = [c.row_dcache for c in cursors]
        with self._on_mesh():
            cache, dcache, logits, fused, feat_last = \
                self._prefill_chunk_fused(
                    self.params, self.dparams, sub_cache, sub_dcache,
                    jnp.asarray(toks), t_valid, prev_feat)
        self.prefill_dispatches += 1

        feats = []
        for i, cur in enumerate(cursors):
            cur.prev_feat = feat_last[i: i + 1]
            cur.logits_last = logits[i: i + 1]
            if self.paged:
                ax = kvc.CACHE_BATCH_AXIS
                cur.row_cache = {
                    n: jax.lax.slice_in_dim(cache[n], i, i + 1,
                                            axis=ax.get(n, 0))
                    for n in cache if n not in kvc.PAGED_POOL_KEYS}
                cur.row_dcache = {
                    n: jax.lax.slice_in_dim(dcache[n], i, i + 1, axis=0)
                    for n in dcache if n not in kvc.DRAFT_POOL_KEYS}
            else:
                cur.row_cache = cache[i]
                cur.row_dcache = dcache[i]
            feats.append(self._block_feats(cur, offs[i], ends[i], fused, i))
        if self.paged:
            pool = {n: cache[n] for n in kvc.PAGED_POOL_KEYS}
            dpool = {n: dcache[n] for n in kvc.DRAFT_POOL_KEYS}
            st = dc_replace(st, cache=dict(st.cache, **pool),
                            dcache=dict(st.dcache, **dpool))
        return st, offs, ends, feats

    def prefill_finalize_slot(self, st: EngineState, cur: PrefillCursor
                              ) -> Tuple[EngineState, int]:
        """Commit an exhausted cursor: build the slot's automaton state
        from the final chunk's logits (or, on a whole-prompt tail-entry
        hit, from the entry's stored first token) and scatter it into
        batch row ``cur.slot``.  A freshly prefilled prompt ending in a
        partial block also registers that block as a tail entry here
        (``_register_tail``) so identical future prompts skip prefill
        entirely.  Returns (state, first token).  Consumes `st` —
        callers must rebind."""
        assert cur.done, "prefill cursor still has chunks to run"
        with span(self.stats, "engine.prefill.finalize",
                  request_id=cur.request_id):
            if cur.boot_token is not None:
                # tail-entry boots are greedy-only (gated at begin), so the
                # stream key is all the sampling state the slot needs — and
                # it matches a full prefill of the same (prompt, seed) exactly
                sub = self._boot_state_from_token(
                    cur.row_cache, cur.row_dcache,
                    jnp.full((1,), cur.boot_token, jnp.int32),
                    cur.prev_feat, len(cur.prompt),
                    keys=self._seed_keys(cur.seed, 1)[1],
                    temps=jnp.full((1,), cur.temperature, jnp.float32))
            else:
                st = self._register_tail(st, cur)
                sub = self._boot_state(cur.row_cache, cur.row_dcache,
                                       cur.logits_last, cur.prev_feat,
                                       len(cur.prompt),
                                       temperature=cur.temperature,
                                       seed=cur.seed)
            self._pkv_active_rows[cur.slot] = False
            self._slot_temp[cur.slot] = cur.temperature
            self._slot_chain[cur.slot] = (cur.draft == "chain")
            st = self._write_slot(st, sub, jnp.int32(cur.slot))
            with span(self.stats, "engine.prefill.finalize.wait",
                      request_id=cur.request_id):
                first = int(jax.device_get(sub.pending[0, 0]))
        return st, first

    def prefill_into_slot(self, st: EngineState, slot: int,
                          prompt: np.ndarray, chunk: int = 256,
                          extra: Optional[Dict] = None,
                          max_new_tokens: Optional[int] = None,
                          temperature: Optional[float] = None,
                          seed: int = 0, draft: str = "tree",
                          request_id: str = ""
                          ) -> Tuple[EngineState, int]:
        """Admit a request in one blocking call: chunked batch-1 prefill,
        then scatter the sub-state into batch row `slot`.  Returns
        (state, first token).  Consumes `st` (buffers donated) — callers
        must rebind.  This is the whole-request wrapper over the
        resumable cursor (``prefill_begin_slot`` ->
        ``prefill_step_into_slot``* -> ``prefill_finalize_slot``), so it
        shares every invariant documented there — including the
        RuntimeError on page-pool exhaustion."""
        st, cur = self.prefill_begin_slot(st, slot, prompt, chunk=chunk,
                                          extra=extra,
                                          max_new_tokens=max_new_tokens,
                                          temperature=temperature,
                                          seed=seed, draft=draft,
                                          request_id=request_id)
        while not cur.done:
            st, _ = self.prefill_step_into_slot(st, cur)
        return self.prefill_finalize_slot(st, cur)

    # ------------------------------------------------------------------
    # copy-on-write: fork + pre-step exclusivity
    # ------------------------------------------------------------------
    def _read_slot(self, st: EngineState, slot: int) -> EngineState:
        """Extract batch row `slot` as a batch-1 sub-state (shared pool
        keys are omitted for paged caches — ``_write_slot`` passes them
        through)."""
        def row(a, axis):
            return jax.lax.dynamic_slice_in_dim(a, slot, 1, axis)
        paged = "page_table" in st.cache
        cache = {n: row(a, kvc.CACHE_BATCH_AXIS.get(n, 0))
                 for n, a in st.cache.items()
                 if not (paged and n in kvc.PAGED_POOL_KEYS)}
        dpaged = "page_table" in st.dcache
        dcache = {n: row(a, 0) for n, a in st.dcache.items()
                  if not (dpaged and n in kvc.DRAFT_POOL_KEYS)}
        kw = dict(cache=cache, dcache=dcache)
        for f in _PKV_FIELDS:
            a = getattr(st, f)
            kw[f] = row(a, 1) if a.ndim > 1 else a
        for f in _ROW_FIELDS:
            kw[f] = row(getattr(st, f), 0)
        return EngineState(**kw)

    def fork_slot(self, st: EngineState, src: int, dst: int) -> EngineState:
        """Copy-on-write fork: row `dst` becomes a live replica of row
        `src` sharing *all* of its physical pages (refcounts incremented,
        zero pool bytes copied).  Either branch may then diverge — the
        pre-step CoW (``prepare_cow``) hands a writer a private copy of
        any still-shared block before its first commit, so neither branch
        can ever perturb the other.  Consumes `st` — callers must
        rebind."""
        assert self.paged, "fork_slot requires the refcounted paged cache"
        assert src != dst
        self._page_alloc.free_slot(dst)         # stale pages, if any
        self._draft_alloc.free_slot(dst)
        self._page_alloc.fork(src, dst)
        self._draft_alloc.fork(src, dst)
        self._pkv_active_rows[dst] = self._pkv_active_rows[src]
        # the replica clones the source's PRNG stream (via _ROW_FIELDS),
        # temperature and draft shape: un-diverged branches replay the
        # identical token stream — callers wanting divergence re-admit
        # with a fresh seed
        self._slot_temp[dst] = self._slot_temp[src]
        self._slot_chain[dst] = self._slot_chain[src]
        self._forked_slots.update((src, dst))
        sub = self._read_slot(st, src)
        return self._write_slot(st, sub, jnp.int32(dst))

    def prepare_cow(self, st: EngineState, rows: np.ndarray) -> EngineState:
        """Pre-step copy-on-write: give every about-to-step row exclusive
        ownership of the physical blocks its writes may touch (trunk: the
        commit window ``[length, length + commit_write_extent)``; draft:
        the extend window past the draft length).  Shared blocks in the
        window are copied to private pages and the row's table is
        repointed.  Free no-op unless a live slot has fork-derived
        sharing — prefix-shared prompt blocks sit strictly below every
        write window and a tail-entry attach copies its block at
        admission, so only forked slots are scanned."""
        if not self.paged or not self._forked_slots.intersection(
                np.nonzero(rows)[0]):
            return st
        bs = self.spec.block_size
        plans = (
            (st.cache, self._page_alloc, np.asarray(st.cache["length"]),
             vf.commit_write_extent(self.pmax, self.tree.depth), 1),
            (st.dcache, self._draft_alloc, np.asarray(st.dcache["length"]),
             self.emax, 0),
        )
        # two-phase: plan every needed copy first (no allocator mutation),
        # budget-check, and only then execute — so pool exhaustion raises
        # with host allocator and device page tables still consistent
        planned = []
        for cdict, al, lengths, extent, pool_axis in plans:
            shared_blocks = []                # (slot, blk)
            for i in np.nonzero(rows)[0]:
                i = int(i)
                if i not in self._forked_slots:
                    continue
                if al.count(i) == 0 or not al.slot_holds_shared(i):
                    continue
                lo = int(lengths[i]) // bs
                hi = min(cdiv(int(lengths[i]) + extent, bs), al.count(i))
                for blk in range(lo, hi):
                    if al.refcount(al.page_at(i, blk)) > 1:
                        shared_blocks.append((i, blk))
            if len(shared_blocks) > al.free:
                self.reclaim_pages(len(shared_blocks) - al.free)
            if len(shared_blocks) > al.free:
                raise RuntimeError(
                    f"page pool exhausted during copy-on-write: need "
                    f"{len(shared_blocks)} private pages, {al.free} free "
                    f"of {al.capacity}")
            planned.append(shared_blocks)

        out = []
        for (cdict, al, lengths, extent, pool_axis), shared_blocks in zip(
                plans, planned):
            copies = [(i, blk) + al.cow_write(i, blk)
                      for i, blk in shared_blocks]  # (slot, blk, old, new)
            if copies:
                cdict = dict(cdict)
                sl, bl, olds, news = (jnp.asarray([c[j] for c in copies],
                                                  jnp.int32)
                                      for j in range(4))
                pool_keys = (kvc.PAGED_POOL_KEYS if pool_axis == 1
                             else kvc.DRAFT_POOL_KEYS)
                for n in pool_keys:
                    a = cdict[n]
                    cdict[n] = (a.at[:, news].set(a[:, olds])
                                if pool_axis == 1
                                else a.at[news].set(a[olds]))
                cdict["page_table"] = cdict["page_table"].at[sl, bl].set(news)
            out.append(cdict)
        return dc_replace(st, cache=out[0], dcache=out[1])

    # ------------------------------------------------------------------
    def mode_for(self, pending_len: int, seq_len: int,
                 pkv_active: bool) -> str:
        """One slot's mode automaton (Full -> Refresh -> Partial* -> ...)."""
        if not self.is_attn:
            return "state"
        if not self.partial_enabled:
            return "full"
        if seq_len <= self.spec.partial_budget_tokens:
            return "full"
        if not pkv_active:
            return "refresh"
        if (pending_len - 1 + self.tree.max_path
                + self.spec.refresh_margin // 4 > self.spec.buffer_size):
            return "refresh"
        return "partial"

    def select_mode(self, pending_len_max: int, seq_len_min: int) -> str:
        """Lock-step automaton over the whole batch (generate() path)."""
        return self.mode_for(pending_len_max, seq_len_min, self._pkv_active)

    def modes_for_rows(self, st: EngineState, rows: np.ndarray) -> np.ndarray:
        """Per-slot automaton as a mode *vector*: [B] int8 of
        MODE_FULL/MODE_REFRESH/MODE_PARTIAL (inactive rows read
        MODE_FULL; their entries are don't-cares — ``step_fused``
        normalises them).  This is the fused tick's one host-side
        decision; the vector then rides through the jitted step as an
        operand."""
        with span(self.stats, "engine.modes.wait"):
            pl, sl = jax.device_get((st.pending_len, st.seq_len))
        out = np.full((self.batch,), MODE_FULL, np.int8)
        for i in np.nonzero(rows)[0]:
            out[i] = MODE_IDS[self.mode_for(
                int(pl[i]), int(sl[i]), bool(self._pkv_active_rows[i]))]
        return out

    def select_mode_rows(self, st: EngineState,
                         rows: np.ndarray) -> Dict[str, np.ndarray]:
        """Per-slot automaton grouped by mode (the *grouped* per-mode
        scheduling path, kept for A/B against the fused tick).
        Returns {mode: [B] bool mask}."""
        modes = self.modes_for_rows(st, rows)
        out: Dict[str, np.ndarray] = {}
        for i in np.nonzero(rows)[0]:
            out.setdefault(MODE_NAMES[int(modes[i])],
                           np.zeros(self.batch, bool))[i] = True
        return out

    def step_fused(self, st: EngineState, rows: np.ndarray,
                   modes: np.ndarray) -> Tuple[EngineState, StepOutput]:
        """One fused multi-mode step: every row where `rows` is True
        steps in the mode `modes` assigns it — an arbitrary mix of
        FULL/REFRESH/PARTIAL slots costs exactly ONE jitted dispatch
        (``dispatches`` counts them).  Untouched rows are preserved
        bit-for-bit, and each stepped row's result is bit-identical to
        stepping it alone in its own mode (the losslessness anchor for
        continuous batching).  Consumes `st` (buffers donated in the
        merge) — callers must rebind."""
        assert self.is_attn, \
            "fused steps drive the attention automaton; state archs " \
            "use step(mode='state')"
        with span(self.stats, "engine.step.prepare"):
            rows = np.asarray(rows, bool)
            modes = np.asarray(modes, np.int8)
            active_modes = modes[rows]
            assert active_modes.size, "step_fused needs at least one live row"
            has_refresh = bool(np.any(active_modes == MODE_REFRESH))
            has_full = has_refresh or bool(np.any(active_modes == MODE_FULL))
            has_partial = bool(np.any(active_modes == MODE_PARTIAL))
            # sampling/chain flags from the host mirrors: like the mode
            # mix, they pick which masked branches exist — the per-row
            # behaviour rides on state operands (temps/keys) and the
            # is_chain vector.  Chain masking is the identity when the
            # tree IS a chain.
            has_sampled = bool(np.any(self._slot_temp[rows] > 0.0))
            has_chain = bool(self._tree_branching
                             and np.any(self._slot_chain[rows]))
            # inactive rows' compute is discarded by the in-jit row
            # merge; normalise their mode entries to one the variant
            # implements so the per-row selects never see an
            # unrepresented mode
            modes = np.where(rows, modes, active_modes[0]).astype(np.int8)
            st = self.prepare_cow(st, rows)
            if self._tier is not None:
                # seat hosted pages before any full-cache read (prefetch
                # hits land free; early refreshes pay a synchronous copy)
                st = self._tier_promote_rows(st, rows, modes)
            fn = self._fused_fn(has_full, has_partial, has_refresh,
                                has_sampled, has_chain)
        with span(self.stats, "engine.step.dispatch"):
            with self._on_mesh():
                st, (toks, counts, acc) = fn(self.params, self.dparams, st,
                                             jnp.asarray(rows),
                                             jnp.asarray(modes),
                                             jnp.asarray(self._slot_chain))
            self.dispatches += 1
        pin = self.zero_copy and has_refresh
        # everything the host reads of the step, in one fetch: tokens,
        # counts, accept lengths, the lengths the traffic bill reads and,
        # after a zero-copy refresh, the routed blocks to pin
        with span(self.stats, "engine.step.wait"):
            toks, counts, acc, seq_len, pbi_host = jax.device_get(
                (toks, counts, acc, st.seq_len,
                 st.pkv_blocks if pin else None))
        with span(self.stats, "engine.step.post"):
            self._pkv_active_rows |= rows & (modes == MODE_REFRESH)
            if pin:
                # pin the pages the refresh just routed — BEFORE the tier
                # epilogue, so demotion excludes them.  pin_slot_pages
                # takes the new references before dropping the previous
                # refresh's, so a page kept across refreshes never
                # transiently frees.
                al = self._page_alloc
                for i in np.nonzero(rows & (modes == MODE_REFRESH))[0]:
                    i = int(i)
                    blocks = np.unique(pbi_host[i][pbi_host[i] >= 0])
                    nb = al.count(i)
                    pages = [al.page_at(i, int(j)) for j in blocks
                             if j < nb]
                    if pages:
                        al.pin_slot_pages(i, pages)
            self._record_traffic_rows(modes, seq_len, rows)
            if self._tier is not None:
                # refresh epilogue: committed blocks go cold until the
                # next refresh — demote them; near-refresh partials
                # prefetch
                st = self._tier_epilogue(st, rows, modes)
            names = sorted({MODE_NAMES[int(m)] for m in active_modes})
            out = StepOutput(tokens=toks, counts=np.where(rows, counts, 0),
                             accept_len=np.where(rows, acc, 0),
                             mode=(names[0] if len(names) == 1
                                   else "fused"),
                             modes=modes)
        return st, out

    def step(self, st: EngineState, mode: str) -> Tuple[EngineState,
                                                        StepOutput]:
        """One lock-step draft -> verify(mode) -> accept -> commit round
        over the whole batch (``select_mode`` picks `mode`) — a thin
        wrapper over ``step_fused`` with a uniform mode vector, so
        lock-step outputs are the fused path's outputs by construction.
        Consumes `st` — callers must rebind."""
        if mode == "state":
            if self.is_attn:
                raise ValueError(mode)
            st = self.prepare_cow(st, np.ones((self.batch,), bool))
            ones = jnp.ones((self.batch,), bool)
            st, (toks, counts, acc) = self._step_state(
                self.params, self.dparams, st, ones)
            self.dispatches += 1
            return st, StepOutput(tokens=np.asarray(toks),
                                  counts=np.asarray(counts),
                                  accept_len=np.asarray(acc), mode=mode)
        if mode not in MODE_IDS:
            raise ValueError(mode)
        st, out = self.step_fused(
            st, np.ones((self.batch,), bool),
            np.full((self.batch,), MODE_IDS[mode], np.int8))
        if mode == "refresh":
            self._pkv_active = True
        return st, out

    def step_rows(self, st: EngineState, mode: str,
                  rows: np.ndarray) -> Tuple[EngineState, StepOutput]:
        """Step only the slots where `rows` is True in `mode` (the
        grouped per-mode path — one dispatch per distinct mode per tick,
        kept for A/B against ``step_fused``); every other slot's state is
        preserved bit-for-bit.  Consumes `st` (buffers donated in the
        merge) — callers must rebind."""
        if mode not in MODE_IDS:
            raise ValueError(mode)
        return self.step_fused(
            st, rows, np.full((self.batch,), MODE_IDS[mode], np.int8))

    def _record_traffic_rows(self, modes: np.ndarray, seq_len: np.ndarray,
                             rows: np.ndarray) -> None:
        """Per-row mode attribution: one traffic record per distinct
        mode actually stepped, each billed only for its own rows."""
        for mid in (MODE_FULL, MODE_REFRESH, MODE_PARTIAL):
            sub = rows & (modes == mid)
            if sub.any():
                self._record_traffic(MODE_NAMES[mid], seq_len, sub)

    def _record_traffic(self, mode: str, seq_len: np.ndarray,
                        rows: Optional[np.ndarray] = None):
        """seq_len: [B] host copy of the stepped state's lengths.
        rows: which batch rows actually stepped (masked continuous
        steps); None = the whole batch (lock-step path).

        Full-cache bytes are billed per row and *summed* — rows step at
        heterogeneous KV extents, so ``nrows x max(seq_len[rows])``
        (the old accounting) overstates the traffic whenever lengths
        diverge.  Refresh additionally bills its partial-cache rebuild:
        the retrieval-selected blocks (``partial_budget_tokens`` per
        row) are re-read on top of the full verify pass (the buffer is
        re-appended from pending state on-device, not re-read)."""
        cfg, spec = self.cfg, self.spec
        if not self.is_attn:
            return
        from repro.models.dense import attn_layer_count
        l_attn = attn_layer_count(cfg.layer_kinds())
        itemsize = 2 if cfg.dtype == "bfloat16" else 4
        if rows is None:
            nrows, seq_sum = self.batch, int(np.sum(seq_len))
        else:
            nrows = int(np.sum(rows))
            if nrows == 0:
                return
            seq_sum = int(np.sum(seq_len[rows]))
        hk, dh = cfg.num_kv_heads, cfg.head_dim_
        if mode == "partial":
            nbytes = partial_step_bytes(
                l_attn, nrows,
                spec.partial_budget_tokens + spec.buffer_size,
                hk, dh, itemsize)
        else:
            # batch=1 + per-row-summed context = the analytic sum
            nbytes = full_step_bytes(l_attn, 1, seq_sum, hk, dh, itemsize)
            if mode == "refresh":
                if self.zero_copy:
                    # routed rebuild: summaries scored + index writes +
                    # tail-buffer reset — the selected body never moves
                    nbytes += routed_refresh_bytes(
                        l_attn, nrows, self._nb_seq, self._ns_blocks,
                        spec.buffer_size, hk, dh, itemsize)
                else:
                    nbytes += partial_step_bytes(
                        l_attn, nrows, spec.partial_budget_tokens,
                        hk, dh, itemsize)
        self.traffic.record(mode, nbytes)

    # ------------------------------------------------------------------
    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 eos_id: int = -1, prefill_chunk: int = 256,
                 extra: Optional[Dict] = None):
        """Greedy SpecPV generation.  Returns (tokens [B, <=max_new],
        stats dict)."""
        st = self.prefill(prompt, chunk=prefill_chunk, extra=extra)
        b = self.batch
        out: List[List[int]] = [[int(np.asarray(st.pending[i, 0]))]
                                for i in range(b)]
        pending_max, seq_min = 1, int(np.min(np.asarray(st.seq_len)))
        accepts: List[int] = []
        modes: List[str] = []
        steps = 0
        while min(len(o) for o in out) < max_new_tokens:
            mode = self.select_mode(pending_max, seq_min)
            st, so = self.step(st, mode)
            steps += 1
            modes.append(mode)
            accepts.extend(so.accept_len.tolist())
            for i in range(b):
                cnt = int(so.counts[i])
                out[i].extend(int(x) for x in so.tokens[i, :cnt])
            pending_max = int(np.max(np.asarray(st.pending_len)))
            seq_min = int(np.min(np.asarray(st.seq_len)))
            if eos_id >= 0 and all(eos_id in o for o in out):
                break
        toks = np.full((b, max_new_tokens), -1, np.int64)
        for i in range(b):
            n = min(len(out[i]), max_new_tokens)
            toks[i, :n] = out[i][:n]
        # max_new_tokens=1 is satisfied by the prefill's seed token and
        # never enters the step loop: guard the empty-accepts mean (the
        # scheduler's _emit does the same) instead of emitting NaN + a
        # RuntimeWarning into stats
        stats = dict(steps=steps,
                     mean_accept=(float(np.mean(accepts))
                                  if accepts else 0.0),
                     modes={m: modes.count(m) for m in set(modes)},
                     tokens_per_step=float(np.mean(
                         [len(o) for o in out]) / max(steps, 1)))
        return toks, stats
