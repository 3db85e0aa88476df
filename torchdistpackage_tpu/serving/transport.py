"""Fault-tolerant KV-migration transport — the wire under the router's
``export_slot`` → ``import_slot`` → ``migrate_blocks`` handoff.

The PR-13 router moves paged KV between replicas with one compiled
lane-vector copy and ASSUMES the copy is perfect — correct in-process,
fiction on a real DCN link, where chunks drop, bytes rot, and the peer
can vanish mid-transfer.  This module is the seam that makes the
assumption explicit and then removes it:

- :class:`MigrationTransport` — the interface the router speaks:
  ``begin`` opens a transfer handle for one exported request,
  ``fetch`` stages block payloads (prestaging transports pull bytes
  BEFORE the import lands, so a dead wire leaves the destination
  untouched), ``deliver`` writes staged blocks into the destination
  pool.  ``prestage`` tells the router which ordering the transport
  needs.
- :class:`LoopbackTransport` — the in-process null wire (default).
  ``deliver`` delegates straight to the router's cached per-pair
  ``migrate_blocks`` program (``Router._lane_copy``), so a loopback
  fleet is bit-for-bit the pre-transport router, compiled-signature
  accounting included.
- :class:`ChunkedWireTransport` — the real wire format, in-process: one
  chunk per migrated block (every pool leaf's block slice, int8
  ``(q8, scale)`` payload iff the comm model approved compression —
  the same ``_kv_quant`` arm ``migrate_blocks(compress=True)`` uses),
  a sender-side manifest of per-chunk SHA-256 + byte counts, receiver
  verification of every chunk, and the PR-4 ``with_retries``
  bounded-backoff loop re-requesting any chunk that drops, corrupts,
  or times out.  A :class:`~..resilience.ChaosMonkey` injects
  ``TRANSPORT_FAULT_KINDS`` per fetch attempt, so a non-repeating
  fault is healed by exactly one re-request and a repeating one
  exhausts the budget and surfaces as :class:`TransportDeadError`.

Failure taxonomy (what the router catches):

- :class:`TransportError` — ONE chunk attempt failed (drop / SHA
  mismatch / timeout).  Retryable: ``with_retries`` re-requests.
- :class:`TransportDeadError` — the transfer is over (retry budget
  exhausted).  The router falls back to re-prefill on a surviving
  replica (``migration_fallback`` event): correct-but-slower, never
  wrong.
- :class:`ReplicaDiedError` — the destination died mid-transfer.
  Terminal like a dead transport, but additionally carries
  ``.replica`` so the router takes it out of rotation.  Deliberately
  NOT a :class:`TransportError` subclass: retrying into a corpse
  wastes the whole backoff budget.

All payload staging is host-side numpy; ``deliver`` writes eagerly
(in-place for host-only stub pools, one ``.at[].set`` dispatch for
device pools) — no new traced signatures, every replica's
``decode_signatures`` stays 1 through wire migrations (asserted in the
chaos matrix).
"""

from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.events import default_event_log
from .paged_cache import is_quantized
from ..resilience.ckpt_guard import with_retries


class TransportError(RuntimeError):
    """One chunk attempt failed (dropped / corrupt / timed out) —
    retryable: the bounded-backoff loop re-requests the chunk."""


class TransportDeadError(RuntimeError):
    """The transfer failed terminally (retry budget exhausted).  The
    router must fall back to re-prefill on the target — NOT retry."""


class ReplicaDiedError(TransportDeadError):
    """The destination replica died mid-transfer.  Carries ``replica``
    so the router can take it out of rotation before falling back."""

    def __init__(self, replica: int, message: str) -> None:
        super().__init__(message)
        self.replica = int(replica)


def _leaf_items(cache: Dict[str, Any]) -> List[Tuple[str, Optional[int], Any]]:
    """Deterministic (name, sub-leaf index, array) walk of a paged pool
    pytree: plain leaves yield ``(name, None, arr)``, quantized
    ``(q8, scale)`` tuple pools yield one entry per member.  Sorted by
    name so sender and receiver agree on chunk byte layout."""
    out: List[Tuple[str, Optional[int], Any]] = []
    for name in sorted(cache):
        leaf = cache[name]
        if isinstance(leaf, tuple):
            out.extend((name, j, sub) for j, sub in enumerate(leaf))
        else:
            out.append((name, None, leaf))
    return out


class MigrationTransport:
    """Interface between :class:`~.router.Router` and the migration
    wire.  One transfer = ``begin`` (handle) → ``fetch`` (stage block
    payloads; prestaging impls raise here on a dead wire, BEFORE the
    destination admits anything) → ``deliver`` (write staged blocks
    into the destination pool at the import's block ids).

    ``prestage=False`` transports copy pool-to-pool at ``deliver`` time
    (the loopback path — nothing to stage); ``prestage=True`` transports
    pull bytes up front so every failure mode lands before the import.
    ``bind(router)`` is called once from the router constructor."""

    kind = "abstract"
    prestage = False

    def __init__(self) -> None:
        self._router: Optional[Any] = None
        self.stats: Dict[str, int] = {
            "sends": 0, "chunks": 0, "wire_bytes": 0, "retries": 0,
            "reshipped_blocks": 0, "dead_transfers": 0,
        }

    def bind(self, router: Any) -> "MigrationTransport":
        self._router = router
        return self

    def emit(self, kind: str, **fields: Any) -> None:
        """Land a transport event on the bound router's ledger (the
        default event log when unbound) — named ``emit`` so the repo
        lint's literal-kind scan covers transport call sites too."""
        ev = (self._router._ev if self._router is not None
              else default_event_log())
        ev.emit(kind, **fields)

    # one transfer ---------------------------------------------------------

    def begin(self, src_cache: Any, desc: Dict[str, Any], *, src: int,
              dst: int, compress: bool) -> Dict[str, Any]:
        raise NotImplementedError

    def fetch(self, handle: Dict[str, Any], block_ids: Sequence[int],
              reship: bool = False) -> None:
        raise NotImplementedError

    def deliver(self, handle: Dict[str, Any], dst_cache: Any,
                src_ids: Sequence[int], dst_ids: Sequence[int]) -> Any:
        raise NotImplementedError


class LoopbackTransport(MigrationTransport):
    """The in-process null wire: ``deliver`` runs the router's cached
    per-(pair, wire-format) ``migrate_blocks`` program directly — a
    loopback fleet is bit-for-bit the pre-transport router, including
    the compiled-signature accounting
    (``summary()['fleet']['migrations']['signatures']``)."""

    kind = "loopback"
    prestage = False

    def begin(self, src_cache: Any, desc: Dict[str, Any], *, src: int,
              dst: int, compress: bool) -> Dict[str, Any]:
        self.stats["sends"] += 1
        return {"src_cache": src_cache, "src": src, "dst": dst,
                "compress": bool(compress)}

    def fetch(self, handle: Dict[str, Any], block_ids: Sequence[int],
              reship: bool = False) -> None:
        return None  # nothing to stage: deliver copies pool-to-pool

    def deliver(self, handle: Dict[str, Any], dst_cache: Any,
                src_ids: Sequence[int], dst_ids: Sequence[int]) -> Any:
        assert self._router is not None, "LoopbackTransport is unbound"
        self.stats["chunks"] += len(src_ids)
        return self._router._lane_copy(
            handle["src"], handle["dst"], handle["src_cache"], dst_cache,
            src_ids, dst_ids, handle["compress"])


class ChunkedWireTransport(MigrationTransport):
    """Chunked, checksummed, retrying wire format for cross-replica KV.

    One chunk per migrated block: the concatenated bytes of every pool
    leaf's block slice, int8 ``(q8, scale)`` iff the transfer was opened
    with ``compress=True`` (the router passes the comm model's
    ``predict_compressed`` verdict — EQuARX-lineage int8 wire arm,
    exactly the payload ``migrate_blocks(compress=True)`` would write).
    The sender records a manifest entry (SHA-256 + byte count) per chunk
    when it FIRST reads the block; every arrival is verified against it,
    so a corrupt chunk is indistinguishable from a dropped one — both
    raise :class:`TransportError` and are re-requested by
    ``with_retries`` (bounded backoff, ``migration_retry`` event per
    re-request, ``retries`` budget per chunk).

    Fault injection: ``chaos.transport_faults_due(seq)`` is consulted on
    EVERY fetch attempt (``seq`` = this transfer's send sequence
    number); ``Fault.slot`` picks the victim chunk index.  A stall whose
    ``duration_s`` exceeds ``timeout_s`` is a timeout (modeled — the
    harness never sleeps the wall clock); ``replica_death_midmigration``
    raises :class:`ReplicaDiedError` once chunks have started flowing.

    ``base_delay_s``/``max_delay_s`` default to 0 so in-process retries
    are instant; a real deployment would set a genuine backoff.
    """

    kind = "chunked_wire"
    prestage = True

    def __init__(self, *, retries: int = 3, base_delay_s: float = 0.0,
                 max_delay_s: float = 0.0, timeout_s: float = 0.5,
                 chaos: Optional[Any] = None) -> None:
        super().__init__()
        self.retries = int(retries)
        self.base_delay_s = float(base_delay_s)
        self.max_delay_s = float(max_delay_s)
        self.timeout_s = float(timeout_s)
        self.chaos = chaos
        self._seq = 0

    # sender side ----------------------------------------------------------

    def begin(self, src_cache: Any, desc: Dict[str, Any], *, src: int,
              dst: int, compress: bool) -> Dict[str, Any]:
        seq = self._seq
        self._seq += 1
        self.stats["sends"] += 1
        # int8 stub / kv_quant tuple pools are already at wire precision
        compress = bool(compress) and not is_quantized(src_cache)
        return {"src_cache": src_cache, "src": src, "dst": dst,
                "compress": compress, "seq": seq, "rid": desc.get("orig_rid"),
                "staged": {}, "manifest": {}}

    def _read_block(self, handle: Dict[str, Any],
                    b: int) -> Tuple[Dict[Any, Any], bytes]:
        """Sender-side read of one block: per-leaf payload arrays (the
        staged form ``deliver`` writes) plus the canonical chunk bytes
        the manifest hashes."""
        payload: Dict[Any, Any] = {}
        parts: List[bytes] = []
        for name, j, leaf in _leaf_items(handle["src_cache"]):
            arr = np.asarray(leaf[:, b])
            if handle["compress"] and arr.dtype.kind == "f":
                from ..models.generate import _kv_quant

                q, scale = _kv_quant(arr)
                q = np.asarray(q)
                scale = np.asarray(scale, np.float32)
                payload[(name, j)] = (q, scale)
                parts.append(q.tobytes())
                parts.append(scale.tobytes())
            else:
                payload[(name, j)] = arr
                parts.append(arr.tobytes())
        return payload, b"".join(parts)

    # receiver side --------------------------------------------------------

    def fetch(self, handle: Dict[str, Any], block_ids: Sequence[int],
              reship: bool = False) -> None:
        """Stage ``block_ids`` (skipping blocks already staged —
        ``reship=True`` marks a post-import top-up re-requesting blocks
        the import expected to ``share`` but found evicted).  Each chunk
        is fetched under its own ``with_retries`` budget; exhaustion
        raises :class:`TransportDeadError`, a destination death raises
        :class:`ReplicaDiedError` immediately (no retry)."""
        ids = [int(b) for b in block_ids if int(b) not in handle["staged"]]
        if reship:
            self.stats["reshipped_blocks"] += len(ids)
        for idx, b in enumerate(ids):
            self._fetch_chunk(handle, b, idx, len(ids))

    def _fetch_chunk(self, handle: Dict[str, Any], b: int, idx: int,
                     total: int) -> None:
        def attempt() -> None:
            faults = (self.chaos.transport_faults_due(handle["seq"])
                      if self.chaos is not None else [])
            for f in faults:
                if f.kind != "replica_death_midmigration":
                    continue
                # the peer dies once chunks have started flowing: on the
                # second chunk of a multi-chunk send, immediately on a
                # single-chunk one
                if idx >= min(1, total - 1):
                    self.chaos.fire(f, seq=handle["seq"], chunk=idx,
                                    dst_replica=handle["dst"])
                    raise ReplicaDiedError(
                        handle["dst"],
                        f"replica {handle['dst']} died mid-migration "
                        f"(send {handle['seq']}, chunk {idx}/{total})")
            payload, raw = self._read_block(handle, b)
            man = handle["manifest"].setdefault(
                b, {"sha256": hashlib.sha256(raw).hexdigest(),
                    "bytes": len(raw)})
            for f in faults:
                victim = (f.slot or 0) % max(1, total)
                if victim != idx:
                    continue
                if f.kind == "chunk_drop":
                    self.chaos.fire(f, seq=handle["seq"], chunk=idx,
                                    block=b)
                    raise TransportError(
                        f"chunk {idx} (block {b}) dropped on send "
                        f"{handle['seq']}")
                if f.kind == "chunk_corrupt":
                    self.chaos.fire(f, seq=handle["seq"], chunk=idx,
                                    block=b)
                    raw = bytes([raw[0] ^ 0xFF]) + raw[1:]
                if f.kind == "transport_stall":
                    self.chaos.fire(f, seq=handle["seq"], chunk=idx,
                                    block=b, duration_s=f.duration_s)
                    if f.duration_s > self.timeout_s:
                        raise TransportError(
                            f"chunk {idx} (block {b}) timed out: stalled "
                            f"{f.duration_s}s > timeout {self.timeout_s}s")
            if (hashlib.sha256(raw).hexdigest() != man["sha256"]
                    or len(raw) != man["bytes"]):
                raise TransportError(
                    f"chunk {idx} (block {b}) failed SHA-256 manifest "
                    f"check on send {handle['seq']}")
            handle["staged"][b] = payload
            self.stats["chunks"] += 1
            self.stats["wire_bytes"] += man["bytes"]

        def on_retry(attempt_n: int, delay: float, err: BaseException) -> None:
            self.stats["retries"] += 1
            self.emit(
                "migration_retry", seq=handle["seq"], block=int(b),
                chunk=idx, attempt=attempt_n, retries=self.retries,
                delay_s=round(delay, 6), error=repr(err),
                src_replica=handle["src"], dst_replica=handle["dst"])

        try:
            with_retries(
                attempt, retries=self.retries,
                base_delay_s=self.base_delay_s,
                max_delay_s=self.max_delay_s, jitter=0.0,
                retry_on=(TransportError,), on_retry=on_retry)
        except TransportError as e:
            self.stats["dead_transfers"] += 1
            raise TransportDeadError(
                f"transfer {handle['seq']} dead: chunk {idx} (block {b}) "
                f"failed {self.retries + 1} attempts: {e}") from e

    def deliver(self, handle: Dict[str, Any], dst_cache: Any,
                src_ids: Sequence[int], dst_ids: Sequence[int]) -> Any:
        """Write staged blocks into the destination pool at the import's
        block ids.  Host-only (numpy) pools are written in place — the
        same contract as :func:`~.sim.host_migrate_blocks`; device pools
        take one eager ``.at[].set`` per leaf (data movement, not a new
        traced program)."""
        pairs = [(int(s), int(d)) for s, d in zip(src_ids, dst_ids)]
        missing = [s for s, _ in pairs if s not in handle["staged"]]
        if missing:
            raise TransportDeadError(
                f"deliver before fetch: blocks {missing} never staged on "
                f"send {handle['seq']}")
        out: Dict[str, Any] = {}
        for name in dst_cache:
            leaf = dst_cache[name]
            if isinstance(leaf, tuple):
                out[name] = tuple(
                    self._write_leaf(sub, (name, j), pairs, handle)
                    for j, sub in enumerate(leaf))
            else:
                out[name] = self._write_leaf(leaf, (name, None), pairs,
                                             handle)
        return out

    def _write_leaf(self, leaf: Any, key: Tuple[str, Optional[int]],
                    pairs: List[Tuple[int, int]], handle: Dict[str, Any]) -> Any:
        vals = []
        for s, _d in pairs:
            v = handle["staged"][s][key]
            if isinstance(v, tuple):  # int8 wire payload: dequantize
                q, scale = v
                v = (q.astype(np.float32) * scale[..., None])
            vals.append(np.asarray(v))
        stacked = np.stack(vals, axis=1)
        idxs = [d for _s, d in pairs]
        if isinstance(leaf, np.ndarray):  # host-only pool: write in place
            leaf[:, idxs] = stacked.astype(leaf.dtype)
            return leaf
        import jax.numpy as jnp

        return leaf.at[:, idxs].set(jnp.asarray(stacked, leaf.dtype))
