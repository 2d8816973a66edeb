"""Host-RAM KV tier: spilled KV blocks that survive HBM eviction.

The prefix cache (``ragged_manager.PrefixCache``) keeps hot shared prefixes
resident in the device KV pool, but capacity pressure evicts cache-only
blocks LRU-first -- and until now eviction meant the KV simply vanished and
the next request with that prefix paid full prefill.  :class:`HostKVTier`
is the layer below: eviction victims spill their block payloads (the exact
wire format ``InferenceEngineV2.export_kv_block`` produces -- int8 values +
per-(slot, head) fp32 scales when the pool is quantized, so spill/restore
is a memcpy, never a requantize) into host buffers keyed by the same
blake2b chain keys, and ``match_prefix`` restores them on a resident miss.
Host RAM is ~10x HBM on typical hosts, so the effective prefix-cache
working set grows by about that factor for the price of one H2D copy per
restored block.

Restore latency hides behind the ``DevicePrefetchingLoader`` idiom: when a
chain walk misses resident block *i*, the manager calls
:meth:`prefetch` with the REMAINING chain keys and the tier issues
``jax.device_put`` for the next ``prefetch_depth`` spilled blocks
immediately -- those transfers overlap the (jitted, donating) pool write of
block *i*, so by the time the walk reaches block *i+1* its payload is
already on device.

Long-context serving (``longctx.py``) adds a second consumer: a live
sequence's cold middle blocks spill here DURING decode and stream back per
layer -- :meth:`stream` fetches only one layer's payload leaves and
:meth:`stream_ahead` issues the next segment's H2D while the current one
computes, so the restore hides under partial-attention compute instead of
stalling the block walk.  Spilled blocks of live sequences are
:meth:`pin`-ned: LRU capacity eviction skips them (their KV exists nowhere
else -- evicting them would be data loss, not a cache miss).

Capacity is accounted in *wire* bytes (:func:`payload_wire_nbytes`): the
quantized payload plus its fp32 scales, never an fp32-equivalent, so the
host LRU bound stays honest under int8/fp8 pools.

Integrity: every spill stores a blake2b digest over the payload bytes and
every restore re-verifies it.  A mismatch (host memory corruption, a
buggy external pager mutating the buffers) drops the entry and reports a
plain cache miss -- the prompt recomputes, correctness never depends on the
tier.  ``tools/chaos.py`` drives this path by patching
:func:`_restore_seam`.
"""

import hashlib
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from ...telemetry.serving import (emit_host_tier_hit, emit_host_tier_restore,
                                  emit_host_tier_spill)
from ...telemetry.trace import get_tracer, span


def payload_digest(payloads: List[np.ndarray]) -> bytes:
    """Content digest of one block's spill payloads (dtype + shape + bytes
    per leaf, order-sensitive) -- the restore-time identity check."""
    h = hashlib.blake2b(digest_size=16)
    for p in payloads:
        arr = np.ascontiguousarray(p)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.digest()


def payload_nbytes(payloads: List[np.ndarray]) -> int:
    """Byte footprint of one block's (host-side) payload leaves -- the
    shared accounting unit for spill, migration and fabric framing."""
    return sum(int(np.asarray(p).nbytes) for p in payloads)


def payload_wire_nbytes(payloads) -> int:
    """WIRE bytes of one block's payloads: what actually crosses PCIe /
    the fabric and sits in host spill buffers.  ``BlockScaledTensor``
    leaves report their own ``wire_nbytes`` (1-byte values + fp32 scales);
    plain ndarray leaves count their real dtype bytes -- an int8/fp8 pool
    exports 1-byte arrays plus separate fp32 scale leaves, so the sum IS
    the quantized footprint, never an fp32-equivalent."""
    total = 0
    for p in payloads:
        wn = getattr(p, "wire_nbytes", None)
        total += int(wn) if wn is not None else int(np.asarray(p).nbytes)
    return total


def _restore_seam(key: bytes, payloads: List[np.ndarray]):
    """Identity pass-through on the restore path.  Exists so the chaos
    harness can corrupt spilled payloads in flight (``host_tier_corrupt``)
    without reaching into the tier's internals."""
    return payloads


class HostKVTier:
    """Bounded LRU store of spilled KV blocks in host memory.

    ``read_block(block) -> List[np.ndarray]`` and
    ``write_block(block, payloads)`` are the engine's block export/import
    hooks; the tier never touches pool internals.  Entries stay resident
    after a restore -- the device copy is a *cache* of the host copy, so a
    later eviction of the restored block refreshes rather than re-copies.
    """

    def __init__(self, config, read_block: Callable,
                 write_block: Callable):
        self.config = config
        self._read_block = read_block
        self._write_block = write_block
        # key -> (host payloads, digest, wire nbytes); LRU order, bounded
        self._entries: "OrderedDict[bytes, tuple]" = OrderedDict()
        # key -> device payloads issued ahead by prefetch(); bounded by
        # prefetch_depth, digest already verified at issue time
        self._inflight: "OrderedDict[bytes, list]" = OrderedDict()
        # (key, leaf-idx tuple) -> device leaves issued by stream_ahead()
        self._stream_inflight: "OrderedDict[tuple, list]" = OrderedDict()
        # keys whose digest a stream fetch already verified (a full check
        # per layer per segment would dominate the walk; content addresses
        # make one check per residence sufficient)
        self._stream_verified = set()
        # keys LRU capacity eviction must skip: spilled blocks of LIVE
        # sequences (longctx decode) -- their KV exists nowhere else
        self._pinned = set()
        self.bytes_used = 0
        self.spills = 0
        self.hits = 0
        self.misses = 0
        self.corrupt = 0
        self.evictions = 0
        self.pinned_overflow = 0
        self.stream_fetches = 0
        self.restore_seconds = 0.0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: bytes) -> bool:
        return key in self._entries

    @property
    def capacity_blocks(self) -> int:
        return int(self.config.capacity_blocks)

    @property
    def capacity_bytes(self) -> int:
        return int(getattr(self.config, "capacity_bytes", 0))

    # ------------------------------------------------------------- capacity
    def _drop_entry(self, key: bytes) -> None:
        payloads, digest, nbytes = self._entries.pop(key)
        self.bytes_used -= nbytes
        self._stream_verified.discard(key)
        for lk in [lk for lk in self._stream_inflight if lk[0] == key]:
            del self._stream_inflight[lk]

    def _evict_for(self, incoming_nbytes: int) -> None:
        """LRU-evict unpinned entries until one more block of
        ``incoming_nbytes`` fits both bounds.  When only pinned entries
        remain the tier runs over capacity rather than dropping live KV
        (counted in ``pinned_overflow`` -- the operator's signal that the
        byte budget is too small for the live working set)."""
        def over():
            if len(self._entries) >= self.capacity_blocks:
                return True
            cb = self.capacity_bytes
            return cb > 0 and self.bytes_used + incoming_nbytes > cb

        while over():
            victim = next((k for k in self._entries
                           if k not in self._pinned), None)
            if victim is None:
                self.pinned_overflow += 1
                break
            self._drop_entry(victim)
            self.evictions += 1

    # ------------------------------------------------------------------ pins
    def pin(self, key: bytes) -> None:
        """Exempt ``key`` from LRU capacity eviction (a live sequence's
        spilled block: dropping it would be data loss, not a cache miss)."""
        self._pinned.add(key)

    def unpin(self, key: bytes) -> None:
        self._pinned.discard(key)

    def drop(self, key: bytes) -> bool:
        """Forget ``key`` entirely (sequence flushed): entry, pin, and any
        in-flight transfers."""
        self._pinned.discard(key)
        self._inflight.pop(key, None)
        if key not in self._entries:
            return False
        self._drop_entry(key)
        return True

    # ------------------------------------------------------------------ spill
    def _insert(self, key: bytes, payloads: List[np.ndarray]) -> None:
        nbytes = payload_wire_nbytes(payloads)
        self._evict_for(nbytes)
        self._entries[key] = (payloads, payload_digest(payloads), nbytes)
        self.bytes_used += nbytes
        self.spills += 1
        emit_host_tier_spill(key)

    def spill(self, key: bytes, block: int) -> bool:
        """Copy ``block``'s KV to host under ``key`` (the prefix cache's
        eviction hook -- called while the block is still allocated and its
        KV resident).  A key already spilled only refreshes recency: chain
        keys are content addresses, the payload cannot have changed."""
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        with span("serve/kv_spill", trace_id="kvtier", key=key.hex()[:12],
                  block=int(block)):
            self._insert(key, self._read_block(block))
        return True

    def insert(self, key: bytes, payloads: List[np.ndarray]) -> bool:
        """Adopt an externally produced block payload (the decode side of a
        streamed sequence-parallel prefill: frames decoded off the fabric
        land here directly, no device round-trip).  Same accounting and
        eviction as :meth:`spill`."""
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        self._insert(key, [np.asarray(p) for p in payloads])
        return True

    # --------------------------------------------------------------- prefetch
    def prefetch(self, keys) -> int:
        """Issue-ahead H2D for up to ``prefetch_depth`` spilled ``keys``:
        verify each entry's digest on host, then start an async
        ``device_put`` whose transfer overlaps whatever pool writes the
        caller does next.  Returns how many transfers were issued."""
        issued = 0
        depth = max(1, int(self.config.prefetch_depth))
        for key in keys:
            if len(self._inflight) >= depth:
                break
            if key in self._inflight:
                continue
            entry = self._entries.get(key)
            if entry is None:
                break  # chain is broken here; later keys can't match anyway
            payloads, digest, _ = entry
            payloads = _restore_seam(key, payloads)
            if payloads is None or (self.config.verify_digests and
                                    payload_digest(payloads) != digest):
                self._drop_entry(key)
                self.corrupt += 1
                get_tracer().flight_dump(
                    "kv_corrupt", extra={"key": key.hex()[:12],
                                         "where": "prefetch"})
                break
            self._inflight[key] = [jax.device_put(p) for p in payloads]
            issued += 1
        return issued

    # ---------------------------------------------------------------- restore
    def restore(self, key: bytes, block: int) -> bool:
        """Write ``key``'s spilled KV into freshly allocated device block
        ``block``.  Returns False on miss or digest mismatch (caller treats
        both as a plain cache miss and frees the block).

        An in-flight prefetch is consulted FIRST: if capacity churn
        LRU-evicted the host entry after its ``device_put`` was issued, the
        transfer is still valid -- keys are content addresses and the
        digest was verified at issue time -- so issue-ahead survives
        eviction races instead of degrading to a miss."""
        device_payloads = self._inflight.pop(key, None)
        entry = self._entries.get(key)
        if device_payloads is None and entry is None:
            self.misses += 1
            return False
        t0 = time.perf_counter()
        prefetched = device_payloads is not None
        if prefetched:
            payloads = device_payloads  # digest verified at prefetch issue
        else:
            payloads, digest, _ = entry
            payloads = _restore_seam(key, payloads)
            if payloads is None or (self.config.verify_digests and
                                    payload_digest(payloads) != digest):
                self._drop_entry(key)
                self.corrupt += 1
                self.misses += 1
                get_tracer().flight_dump(
                    "kv_corrupt", extra={"key": key.hex()[:12],
                                         "where": "restore"})
                return False
        if entry is not None:
            self._entries.move_to_end(key)
        self._write_block(block, payloads)
        dt = time.perf_counter() - t0
        self.restore_seconds += dt
        self.hits += 1
        emit_host_tier_hit(key)
        emit_host_tier_restore(dt, prefetched)
        tracer = get_tracer()
        if tracer.enabled:
            tracer.record_span("kv_restore", "kvtier", dur_s=dt,
                               key=key.hex()[:12], block=int(block),
                               prefetched=bool(prefetched))
        return True

    # ------------------------------------------------------------- streaming
    # The long-context block walk never restores whole blocks into the
    # pool: it fetches ONE LAYER's payload leaves per partial-attention
    # pass, so a 256k-token context streams through a bounded device
    # footprint.  stream_ahead() is the issue-ahead half: segment s+1's
    # device_put overlaps segment s's compute.

    def stream(self, key: bytes, leaf_idxs) -> Optional[list]:
        """Device arrays of payload leaves ``leaf_idxs`` (``tree_leaves``
        order, as in the export format) for ``key``.  Consumes a matching
        :meth:`stream_ahead` transfer when one is in flight; returns None
        on a miss or a failed digest check."""
        li = tuple(int(i) for i in leaf_idxs)
        dev = self._stream_inflight.pop((key, li), None)
        if dev is not None:
            self.hits += 1
            emit_host_tier_hit(key)
            emit_host_tier_restore(0.0, True)
            return dev
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        payloads, digest, _ = entry
        payloads = _restore_seam(key, payloads)
        if payloads is None or (self.config.verify_digests
                                and key not in self._stream_verified
                                and payload_digest(payloads) != digest):
            self._drop_entry(key)
            self.corrupt += 1
            self.misses += 1
            get_tracer().flight_dump(
                "kv_corrupt", extra={"key": key.hex()[:12],
                                     "where": "stream"})
            return None
        self._stream_verified.add(key)
        self._entries.move_to_end(key)
        t0 = time.perf_counter()
        dev = [jax.device_put(payloads[i]) for i in li]
        dt = time.perf_counter() - t0
        self.restore_seconds += dt
        self.hits += 1
        self.stream_fetches += 1
        emit_host_tier_hit(key)
        emit_host_tier_restore(dt, False)
        return dev

    def stream_ahead(self, keys, leaf_idxs) -> int:
        """Issue-ahead H2D for the NEXT segments of the block walk, bounded
        by ``prefetch_depth`` outstanding transfers.  Returns how many were
        issued."""
        issued = 0
        depth = max(1, int(self.config.prefetch_depth))
        li = tuple(int(i) for i in leaf_idxs)
        for key in keys:
            if len(self._stream_inflight) >= depth:
                break
            lk = (key, li)
            if lk in self._stream_inflight:
                continue
            entry = self._entries.get(key)
            if entry is None:
                continue
            payloads, digest, _ = entry
            payloads = _restore_seam(key, payloads)
            if payloads is None or (self.config.verify_digests
                                    and key not in self._stream_verified
                                    and payload_digest(payloads) != digest):
                self._drop_entry(key)
                self.corrupt += 1
                get_tracer().flight_dump(
                    "kv_corrupt", extra={"key": key.hex()[:12],
                                         "where": "stream_ahead"})
                continue
            self._stream_verified.add(key)
            self._stream_inflight[lk] = [jax.device_put(payloads[i])
                                         for i in li]
            issued += 1
        return issued

    # ------------------------------------------------------------------ misc
    def stats(self) -> Dict[str, float]:
        return {"entries": len(self._entries), "spills": self.spills,
                "hits": self.hits, "misses": self.misses,
                "corrupt": self.corrupt, "evictions": self.evictions,
                "bytes_used": self.bytes_used, "pinned": len(self._pinned),
                "pinned_overflow": self.pinned_overflow,
                "stream_fetches": self.stream_fetches,
                "restore_seconds": self.restore_seconds}

    def audit(self) -> Dict[str, int]:
        """Cross-check tier accounting; raises ValueError on the first
        violation (chaos scenarios run this to prove churn leaks nothing).
        """
        total = sum(nb for _, _, nb in self._entries.values())
        if total != self.bytes_used:
            raise ValueError(
                f"tier byte accounting drifted: entries sum to {total}, "
                f"bytes_used says {self.bytes_used}")
        if self.capacity_bytes > 0 and not self._pinned \
                and self.bytes_used > self.capacity_bytes:
            raise ValueError(
                f"tier over byte capacity with nothing pinned: "
                f"{self.bytes_used} > {self.capacity_bytes}")
        stale = [lk for lk in self._stream_inflight
                 if lk[0] not in self._entries]
        if stale:
            raise ValueError(
                f"stream transfers in flight for dropped entries: {stale}")
        return {"entries": len(self._entries), "bytes_used": self.bytes_used,
                "pinned": len(self._pinned)}

    def clear(self) -> None:
        self._entries.clear()
        self._inflight.clear()
        self._stream_inflight.clear()
        self._stream_verified.clear()
        self._pinned.clear()
        self.bytes_used = 0
