"""Cache residency tiers / traffic accounting.

The paper's offloading experiment (Fig. 4) keeps the full KV cache in host
memory and only the partial + draft caches on-device; partial verification
then avoids PCIe traffic.  On a TPU pod the analogue is *sharding*: the
full cache is sequence-sharded over the `model` axis while the partial
cache is small enough to live replicated next to the compute.  What we can
account for on any runtime is *bytes of cache touched per step mode*, which
is exactly the quantity that the PCIe link (GPU) or ICI (TPU) pays for.

``TrafficMeter`` tallies those bytes; ``benchmarks/bench_fig4_offload.py``
turns them into modelled step times for a given link bandwidth.

``TierManager`` is the working implementation of that residency split for
the *paged* engine (docs/paged_kv.md#residency-tiers): cold trunk-pool
pages — blocks a slot references but no partial step reads, i.e.
everything below the slot's committed length once the slot is past its
refresh — are demoted to host RAM as int8 (``kvcache/quant.py``), their
device pages recycled into the free list, and promoted back through an
asynchronous ``jax.device_put`` prefetch issued one mode-transition ahead
of the refresh that reads them (the SpecPV automaton makes that tick
predictable).  Promotion dequantizes straight into the fp pool in pool
dtype, so the verify path never changes: models keep reading the ordinary
pool (``models/dense.py``).  ``codec="fp8"`` swaps the int8 grid for an
e4m3 cast at the same byte footprint (per-token absmax/448 scale — see
``quantize_kv_fp8``); ``lossless=True`` offloads raw fp bytes instead —
twice the link traffic, bit-identical round-trip (the token-identity
anchor for the tiered serving tests).  The draft pool is never tiered:
the draft cache is read every step, so it is never cold.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.kvcache.quant import quantize_kv, quantize_kv_fp8, dequantize_kv


@dataclass
class TrafficMeter:
    bytes_by_mode: Dict[str, int] = field(default_factory=dict)
    steps_by_mode: Dict[str, int] = field(default_factory=dict)

    def record(self, mode: str, nbytes: int) -> None:
        self.bytes_by_mode[mode] = self.bytes_by_mode.get(mode, 0) + nbytes
        self.steps_by_mode[mode] = self.steps_by_mode.get(mode, 0) + 1

    def total(self) -> int:
        return sum(self.bytes_by_mode.values())

    def modelled_time_s(self, link_gb_s: float) -> float:
        """Time to move the recorded bytes over a link of ``link_gb_s``
        gigaBYTES per second (GB/s, not Gbit/s — PCIe 4.0 x16 is ~25 GB/s)."""
        return self.total() / (link_gb_s * 1e9)


def full_step_bytes(num_layers: int, batch: int, ctx_len: int, hk: int,
                    dh: int, itemsize: int) -> int:
    """Bytes of full cache read by one full/refresh verification step.

    ``batch`` and ``ctx_len`` multiply, so heterogeneous per-row extents
    must be billed as ``batch=1`` with ``ctx_len`` = the per-row *sum* —
    never ``nrows x max(len)`` (see ``SpecPVEngine._record_traffic``)."""
    return 2 * num_layers * batch * ctx_len * hk * dh * itemsize


def partial_step_bytes(num_layers: int, batch: int, partial_tokens: int,
                       hk: int, dh: int, itemsize: int) -> int:
    """Bytes of partial cache read per partial step — also the *gathered*
    refresh rebuild bill: a gathered refresh re-reads its
    retrieval-selected blocks (``partial_budget_tokens`` of them; the
    buffer is re-appended from pending state, not re-read) on top of the
    full verify read.  A zero-copy refresh bills
    ``routed_refresh_bytes`` instead — the partial body is never
    materialised, so no block bytes move at refresh time."""
    return 2 * num_layers * batch * partial_tokens * hk * dh * itemsize


def routed_refresh_bytes(num_layers: int, batch: int, num_blocks: int,
                         num_sel: int, buffer_tokens: int, hk: int,
                         dh: int, itemsize: int) -> int:
    """Zero-copy refresh rebuild bill (on top of the full verify read):
    the physical-page summaries scored for selection (kmax + kmin,
    ``num_blocks`` table entries each, fp32), the selected-block index
    writes (``num_sel`` int32 ids per layer/kv-head), and the dense tail
    buffer reset (``buffer_tokens`` K+V slots in pool dtype).  No block
    KV bytes move — the selected body stays in the pool and is routed by
    page table at partial-step time (``kernels.ops.
    routed_partial_attention``)."""
    summaries = 2 * num_layers * num_blocks * hk * dh * 4
    index_writes = num_layers * hk * num_sel * 4
    tail = 2 * num_layers * buffer_tokens * hk * dh * itemsize
    return batch * (summaries + index_writes + tail)


# ---------------------------------------------------------------------------
# tiered residency (host offload of cold pages)
# ---------------------------------------------------------------------------

class _HostSegment:
    """One demotion's worth of a slot's cold blocks, held host-side.

    ``k``/``v`` are int8 [L, n, block, Hk, Dh] with bf16 scales
    [L, n, block, Hk] (or the raw pool-layout [L, n, block, Hk*Dh]
    arrays and ``None`` scales when lossless); ``kmax``/``kmin`` are the
    fp32 physical-page summaries
    [L, n, Hk, Dh], saved so promotion restores retrieval scoring
    bit-for-bit."""

    __slots__ = ("blocks", "k", "v", "ks", "vs", "kmax", "kmin", "nbytes")

    def __init__(self, blocks, k, v, ks, vs, kmax, kmin):
        self.blocks = blocks            # List[int] logical block indices
        self.k, self.v = k, v
        self.ks, self.vs = ks, vs       # None when lossless
        self.kmax, self.kmin = kmax, kmin
        self.nbytes = sum(a.nbytes for a in (k, v, kmax, kmin))
        if ks is not None:
            self.nbytes += ks.nbytes + vs.nbytes


class TierManager:
    """Host pool + prefetch queue over one trunk ``PageAllocator``.

    The allocator owns the page-level bookkeeping (``demote``/``promote``
    keep ``_slot_pages`` consistent and recycle device pages through the
    free list); this class owns the *bytes*: quantize-on-demote,
    ``jax.device_put`` prefetch, dequantize-on-promote, and the
    demote/promote entries in a ``TrafficMeter`` (recorded as the bytes
    actually crossing the link — int8 + scales, i.e. ~half the fp bill,
    which is the point of quantized offload).

    Only *exclusively owned* pages demote (refcount 1, no prefix-cache
    pin): a shared page may be another slot's hot prefix, and the
    prefix cache must keep hits servable without a host round-trip.
    """

    def __init__(self, alloc, *, lossless: bool = False,
                 codec: str = "int8", traffic=None):
        assert codec in ("int8", "fp8"), f"unknown tier codec {codec!r}"
        self.alloc = alloc
        self.lossless = lossless
        self.codec = codec
        self.traffic = traffic
        self._host: Dict[int, List[_HostSegment]] = {}
        # slot -> list aligned with _host[slot]: device-side arrays from
        # an async device_put, or None when the segment was not prefetched
        self._pref: Dict[int, List[Optional[tuple]]] = {}
        self.demoted_pages = 0
        self.promoted_pages = 0
        self.prefetch_hits = 0
        self.sync_promotes = 0
        self.host_bytes = 0
        self.host_bytes_peak = 0
        # per-shard host-RAM accounting (sharded serving: one shard ==
        # one host, so these are per-host truths, not global averages)
        shards = getattr(alloc, "shards", 1)
        self.host_bytes_by = [0] * shards
        self.host_bytes_peak_by = [0] * shards

    def reset(self) -> None:
        self._host.clear()
        self._pref.clear()
        self.host_bytes = 0
        self.host_bytes_by = [0] * len(self.host_bytes_by)

    # ------------------------------------------------------------------
    def hosted(self, slot: int) -> int:
        """Hosted (promotion-owed) pages of `slot`."""
        return self.alloc.hosted_count(slot)

    def _bill_host(self, slot: int, nbytes: int) -> None:
        s = self.alloc.slot_shard(slot) if hasattr(self.alloc,
                                                   "slot_shard") else 0
        self.host_bytes += nbytes
        self.host_bytes_by[s] += nbytes
        self.host_bytes_peak = max(self.host_bytes_peak, self.host_bytes)
        self.host_bytes_peak_by[s] = max(self.host_bytes_peak_by[s],
                                         self.host_bytes_by[s])

    def stats(self) -> Dict[str, int]:
        out = dict(tier_hosted_pages=self.alloc.hosted_total,
                   tier_demoted_pages=self.demoted_pages,
                   tier_promoted_pages=self.promoted_pages,
                   tier_prefetch_hits=self.prefetch_hits,
                   tier_sync_promotes=self.sync_promotes,
                   tier_host_bytes=self.host_bytes,
                   tier_host_bytes_peak=self.host_bytes_peak)
        if len(self.host_bytes_by) > 1:
            out["tier_host_bytes_peak_per_host"] = max(
                self.host_bytes_peak_by)
            for s, b in enumerate(self.host_bytes_peak_by):
                out[f"tier_host_bytes_peak_shard_{s}"] = b
        return out

    # ------------------------------------------------------------------
    def demote_slot(self, cache: Dict, slot: int, length: int) -> Dict:
        """Offload `slot`'s cold blocks — complete blocks strictly below
        `length` (all future writes land at ``[length, ...)``, so these
        are read-only until the next full-cache pass) that the slot owns
        exclusively — and recycle their device pages.  Returns the cache
        dict with the slot's page-table entries repointed to the null
        page (the on-device statement of HOST residency).  No-op (same
        dict back) when nothing qualifies."""
        al = self.alloc
        bs = cache["k"].shape[2]
        blocks = [j for j in range(min(length // bs, al.count(slot)))
                  if al.demotable(slot, j)]
        if not blocks:
            return cache
        pages = jnp.asarray([al.page_at(slot, j) for j in blocks], jnp.int32)
        sub_k = cache["k"][:, pages]            # [L, n, block, Hk*Dh]
        sub_v = cache["v"][:, pages]
        if self.lossless:
            k, v = jax.device_get(sub_k), jax.device_get(sub_v)
            ks = vs = None
        else:
            # per-(token, head) scales: split the lane-dense heads
            heads = sub_k.shape[:3] + cache["kmax"].shape[2:]
            qfn = quantize_kv_fp8 if self.codec == "fp8" else quantize_kv
            qk, sk = qfn(sub_k.reshape(heads))
            qv, sv = qfn(sub_v.reshape(heads))
            k, ks = jax.device_get(qk), jax.device_get(sk)
            v, vs = jax.device_get(qv), jax.device_get(sv)
        seg = _HostSegment(blocks, k, v, ks, vs,
                           jax.device_get(cache["kmax"][:, pages]),
                           jax.device_get(cache["kmin"][:, pages]))
        self._host.setdefault(slot, []).append(seg)
        self._pref.setdefault(slot, []).append(None)
        for j in blocks:
            al.demote(slot, j)
        self.demoted_pages += len(blocks)
        self._bill_host(slot, seg.nbytes)
        if self.traffic is not None:
            self.traffic.record("demote", seg.nbytes)
        out = dict(cache)
        out["page_table"] = out["page_table"].at[
            slot, jnp.asarray(blocks, jnp.int32)].set(0)
        return out

    def prefetch_slot(self, slot: int) -> None:
        """Start the host->device transfer of `slot`'s hosted segments
        (``jax.device_put`` is asynchronous: the copy overlaps the
        partial steps still running before the refresh).  Idempotent —
        already-prefetched segments are left in flight."""
        segs = self._host.get(slot, [])
        pref = self._pref.get(slot, [])
        for i, seg in enumerate(segs):
            if pref[i] is None:
                pref[i] = tuple(jax.device_put(a) for a in
                                (seg.k, seg.v, seg.ks, seg.vs,
                                 seg.kmax, seg.kmin) if a is not None)

    def promote_slot(self, cache: Dict, slot: int, dtype=None) -> Dict:
        """Bring every hosted page of `slot` back on-device ahead of a
        full-cache read: allocate fresh pages, dequantize into the pool
        (pool dtype), restore the physical-page summaries, and repoint
        the page table.  Segments that were not prefetched fall back to
        a synchronous ``device_put`` (counted in ``sync_promotes`` — the
        early-refresh path).  Raises through the allocator when the pool
        cannot seat the promotion; callers reclaim/defer first."""
        segs = self._host.pop(slot, [])
        if not segs:
            self._pref.pop(slot, None)
            return cache
        pref = self._pref.pop(slot)
        pool_dtype = cache["k"].dtype if dtype is None else dtype
        out = dict(cache)
        for seg, dev in zip(segs, pref):
            if dev is None:
                self.sync_promotes += 1
                dev = tuple(jax.device_put(a) for a in
                            (seg.k, seg.v, seg.ks, seg.vs,
                             seg.kmax, seg.kmin) if a is not None)
            else:
                self.prefetch_hits += 1
            if self.lossless:
                k, v, kmax, kmin = dev
            else:
                qk, qv, sk, sv, kmax, kmin = dev[0], dev[1], dev[2], \
                    dev[3], dev[4], dev[5]
                k = dequantize_kv(qk, sk, dtype=pool_dtype)
                v = dequantize_kv(qv, sv, dtype=pool_dtype)
                k = k.reshape(k.shape[:3] + out["k"].shape[3:])
                v = v.reshape(v.shape[:3] + out["v"].shape[3:])
            pages = jnp.asarray([self.alloc.promote(slot, j)
                                 for j in seg.blocks], jnp.int32)
            out["k"] = out["k"].at[:, pages].set(k.astype(pool_dtype))
            out["v"] = out["v"].at[:, pages].set(v.astype(pool_dtype))
            out["kmax"] = out["kmax"].at[:, pages].set(kmax)
            out["kmin"] = out["kmin"].at[:, pages].set(kmin)
            out["page_table"] = out["page_table"].at[
                slot, jnp.asarray(seg.blocks, jnp.int32)].set(pages)
            self.promoted_pages += len(seg.blocks)
            self._bill_host(slot, -seg.nbytes)
            if self.traffic is not None:
                self.traffic.record("promote", seg.nbytes)
        return out

    def drop_slot(self, slot: int) -> None:
        """Discard `slot`'s host copies (eviction/reset: the allocator
        side is cleared by ``free_slot``)."""
        for seg in self._host.pop(slot, []):
            self._bill_host(slot, -seg.nbytes)
        self._pref.pop(slot, None)
