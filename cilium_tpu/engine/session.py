"""Incremental verdict session: CaptureReplay's dedup machinery,
re-built for ONLINE streams.

Offline replay (engine.verdict.CaptureReplay) beats the host↔device
transport by staging a capture's string tables on device once and
streaming 2–4 bytes per flow (unique-row ids). An online stream has no
"whole capture" to stage — chunks keep arriving with fresh string
tables — but live traffic has the same statistical shape: strings and
15-tuples repeat heavily. This class makes the dedup INCREMENTAL:

* per-field session string tables grow as new strings appear; only the
  NEW strings are DFA-scanned on device (a delta scan +
  ``dynamic_update_slice`` into the staged match-word table) — the
  reference's per-string regex LRU (``pkg/fqdn/re``), as a growing
  device-resident table;
* a session unique-row table grows the same way; each chunk ships as
  int32 row ids (4 B/flow) + whatever delta rows/strings are new;
* steady state (no new strings/rows) a chunk's H2D is JUST the id
  stream — 244 B/flow (raw featurized blob) → 4 B/flow.

Capacity is bounded: when the row table or a string table would
exceed its cap, the session RESETS (drops all tables and re-interns
from scratch) — the same "dedup must pay for itself" trade
``CaptureReplay.stage_unique`` makes with its ratio guard, expressed
as an eviction policy an unbounded stream needs.

Verdicts are bit-identical to ``VerdictEngine.verdict_l7_records``
(pinned by tests/test_incremental_session.py's differential).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cilium_tpu.engine.dfa_kernel import dfa_scan_banked
from cilium_tpu.engine.memo import (
    VerdictMemo,
    auth_signature,
    hash_rows,
    memo_pack,
)
from cilium_tpu.engine.verdict import (
    _ROW_COLS,
    _gen_intern_rows,
    _gen_l7g_cols,
    verdict_step_capture,
)
from cilium_tpu.core.flow import TrafficDirection

#: session caps: beyond these the dedup tables stop paying for
#: themselves (high-cardinality traffic) and the session re-interns
MAX_ROWS = 1 << 18
MAX_STRINGS = 1 << 16

_FIELDS = ("path", "method", "host", "headers", "qname")
#: row-column index of the L7 type (the family key of the
#: bank-reference invalidation narrowing)
_L7_COL = _ROW_COLS.index("l7_types")
_DPORT_COL = _ROW_COLS.index("dports")
_PREFIX = {"path": "path", "method": "method", "host": "host",
           "headers": "hdr", "qname": "dns", "l7g": "l7g"}


def _pow2(n: int, floor: int = 256) -> int:
    return max(floor, 1 << max(0, n - 1).bit_length())


@functools.partial(jax.jit, donate_argnums=(4,))
def _delta_scan_update(trans, byteclass, start, accept, table,
                       data, lens, valid, offset):
    """Scan a (padded) delta of new strings through one field's banked
    DFA and splice the match words into the session table at
    ``offset``. Donating ``table`` lets XLA update in place — the
    table is device-resident state, not a per-call transfer."""
    words = dfa_scan_banked(trans, byteclass, start, accept, data, lens)
    flat = words.reshape(data.shape[0], -1)
    flat = jnp.where(valid[:, None], flat, 0)
    return jax.lax.dynamic_update_slice(
        table, flat.astype(table.dtype), (offset, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _delta_rows_update(table, rows, offset):
    return jax.lax.dynamic_update_slice(table, rows, (offset, 0))


class _StringTable:
    """One field's session string table: host dict + device match
    words, delta-scanned on growth."""

    def __init__(self, engine, field: str, width: int):
        self.engine = engine
        self.field = field
        self.width = width
        self.ids: Dict[bytes, int] = {b"": 0}
        self.n = 1
        self.capacity = 0
        self.words: Optional[jax.Array] = None  # [cap, NW] on device
        self._nw: Optional[int] = None
        #: new (id, bytes) strings awaiting a device delta-scan
        self._pending: list = [(0, b"")]

    def intern(self, s: bytes) -> int:
        i = self.ids.get(s)
        if i is None:
            i = self.ids[s] = self.n
            self.n += 1
            self._pending.append((i, s))
        return i

    def flush(self) -> None:
        """Push pending strings' match words to the device table."""
        if not self._pending:
            return
        eng = self.engine
        prefix = _PREFIX[self.field]
        a = eng._arrays
        if f"{prefix}_trans" not in a:
            # the engine staged no automaton for this field (an l7g
            # table under a policy with no frontend rules): interning
            # continues host-side — ids stay stable across swaps —
            # and the pending delta scans when a policy that needs
            # the words arrives
            return
        if self._nw is None:
            # words-per-bank from the accept table: [NB, S, W] u32 →
            # flattened row is NB*W u32 lanes
            acc = a[f"{prefix}_accept"]
            self._nw = int(acc.shape[0]) * int(acc.shape[2])
        base = self._pending[0][0]
        D = _pow2(len(self._pending), floor=256)
        # capacity must cover base+D, not just n: dynamic_update_slice
        # CLAMPS an overrunning start index, which would silently slide
        # the (zero-padded) delta window over earlier rows' words
        cap_needed = _pow2(max(self.n, base + D))
        if cap_needed > self.capacity or self.words is None:
            old, old_cap = self.words, self.capacity
            self.capacity = cap_needed
            grown = jnp.zeros((self.capacity, self._nw),
                              dtype=jnp.uint32)
            if old is not None:
                grown = _delta_rows_update(
                    grown, old.astype(jnp.uint32), 0)
            self.words = grown
        # contiguous ids by construction (appended in intern order)
        raw = [s for _, s in self._pending]
        data = np.zeros((D, self.width), dtype=np.uint8)
        lens = np.zeros(D, dtype=np.int32)
        valid = np.zeros(D, dtype=bool)
        for j, s in enumerate(raw):
            b = s[:self.width]
            data[j, :len(b)] = np.frombuffer(b, dtype=np.uint8)
            lens[j] = len(b)
            # strings longer than the session width behave like the
            # raw path's fixed_len clip: invalid → zero words
            valid[j] = len(s) <= self.width
        self.words = _delta_scan_update(
            a[f"{prefix}_trans"], a[f"{prefix}_byteclass"],
            a[f"{prefix}_start"], a[f"{prefix}_accept"],
            self.words,
            jax.device_put(data, eng.device),
            jax.device_put(lens, eng.device),
            jax.device_put(valid, eng.device),
            base)
        self._pending = []


class IncrementalSession:
    """Online analog of CaptureReplay for one VerdictEngine.

    ``verdict_chunk(rec, l7, offsets, blob, gen, ...)`` returns
    ``(n, device verdict array)`` — dispatch only; the caller reads
    back (and can pipeline readbacks across chunks)."""

    def __init__(self, engine, widths: Optional[Dict[str, int]] = None,
                 max_rows: int = MAX_ROWS,
                 max_strings: int = MAX_STRINGS,
                 memo: bool = True, loader=None):
        from cilium_tpu.core.config import EngineConfig
        from cilium_tpu.engine.memo import policy_generation

        self.engine = engine
        #: optional Loader backref: makes the session swap-safe under
        #: churn — committed revisions are consumed as PolicyDeltas
        #: (bank-scoped: only rows touching a changed identity/bank
        #: recompute; a no-change commit drops nothing)
        self.loader = loader
        self._gen_epoch = policy_generation()
        #: device-resident verdict memo over the session row table
        #: (engine/memo.py): steady state, a chunk whose rows are all
        #: known costs one id H2D + one gather — the verdict step runs
        #: only for DELTA rows. Disable to force every chunk through
        #: the full step.
        self.memo_enabled = memo
        self.memo = VerdictMemo(device=engine.device) if memo else None
        cfg = EngineConfig()
        caps = {"path": max(cfg.http_path_buckets),
                "method": cfg.http_method_len,
                "host": cfg.http_host_len,
                "headers": 1024, "qname": cfg.dns_name_len,
                "l7g": cfg.l7g_len}
        self.widths = {f: min(int((widths or {}).get(f, caps[f])),
                              caps[f])
                       for f in _FIELDS + ("l7g",)}
        self.max_rows = max_rows
        self.max_strings = max_strings
        self.fmax = int(engine.policy.kafka_interns.get("gen_fmax", 4))
        # gen block: [proto id, frontend family, l7g string id,
        # pair ids...] (see CaptureFeaturizer.gen_rows)
        self.row_width = len(_ROW_COLS) + 3 + self.fmax
        self._step = jax.jit(verdict_step_capture)
        self.resets = 0
        self._init_state()

    def _init_state(self) -> None:
        self.tables = {f: _StringTable(self.engine, f, self.widths[f])
                       for f in _FIELDS}
        # the l7g (serialized frontend record) table interns host-side
        # unconditionally — string ids are policy-independent, so row
        # encodings survive swaps between fe and non-fe policies —
        # but only flushes/scans when the engine staged l7g arrays
        self.tables["l7g"] = _StringTable(self.engine, "l7g",
                                          self.widths["l7g"])
        self.kafka_memo: Dict[Tuple[str, bytes], int] = {}
        #: row-hash → [(row bytes, id), ...] chains (exact, see
        #: _row_idx)
        self.row_ids: Dict[int, list] = {}
        self.n_rows = 0
        self.row_capacity = 0
        self.rows_dev: Optional[jax.Array] = None
        self._pending_rows: list = []
        #: host mirror of each session row's (enforcement identity,
        #: l7 type, dport) — bounded by max_rows like the row table
        #: itself: the bank-reference invalidation mask is computed
        #: from it without a device readback
        self._row_eps: list = []
        #: session row ids a bank-scoped commit touched, awaiting a
        #: scatter refill in _memo_serve
        self._memo_dirty: Optional[np.ndarray] = None

    def reset(self, reason: str = "session-reset") -> None:
        self.resets += 1
        if self.memo is not None:
            # session row ids restart from 0 — memoized outputs keyed
            # by the old id space must go with them
            self.memo.invalidate(reason)
        self._init_state()

    # -- swap safety ------------------------------------------------------
    def _ensure_current(self) -> None:
        """Consume committed revisions' PolicyDeltas (mirrors
        ``CaptureReplay._ensure_current``): a no-change commit keeps
        every table and the memo; a bank-scoped commit rescans the
        session string tables through the new arrays (session strings
        are raw bytes — policy-independent) and queues only rows whose
        enforcement identity changed for a memo refill; anything else
        resets the session."""
        from cilium_tpu.engine.memo import (
            POLICY_GENERATION,
            policy_generation,
        )

        gen_now = policy_generation()
        if gen_now == self._gen_epoch:
            return
        delta = POLICY_GENERATION.deltas_since(self._gen_epoch)
        self._gen_epoch = gen_now
        new_engine = self.engine
        if self.loader is not None:
            cand = self.loader.engine
            if type(cand).__name__ == "VerdictEngine":
                new_engine = cand
        if delta.is_noop:
            self._rebind(new_engine)
            if self.memo is not None:
                self.memo.adopt()
            return
        partial = (not delta.full
                   and new_engine is not self.engine
                   and (new_engine.policy.kafka_interns
                        == self.engine.policy.kafka_interns))
        if not partial:
            self._rebind(new_engine)
            self.reset(reason="policy-swap")
            return
        self._rebind(new_engine)
        # rescan EVERY session string through the new policy's DFAs:
        # the match-word tables are policy-scoped even though the
        # strings themselves are not. O(session strings), bounded.
        for t in self.tables.values():
            t._pending = sorted(
                ((i, s) for s, i in t.ids.items()), key=lambda p: p[0])
            t.words = None
            t.capacity = 0
            t._nw = None
        if self.memo is not None and self.memo.filled:
            if delta.changed_identities:
                from cilium_tpu.engine.memo import affected_row_ids

                # bank-reference narrowing: only rows whose own L7
                # family AND entry port read a swapped bank refill —
                # an HTTP-path bank swap on one port keeps the same
                # identity's DNS/kafka rows AND its other ports'
                # HTTP rows serving (PolicyDelta.affects)
                pairs = self._row_eps[:self.memo.filled]
                affected = affected_row_ids(
                    delta,
                    np.fromiter((p[0] for p in pairs),
                                dtype=np.int64, count=len(pairs)),
                    np.fromiter((p[1] for p in pairs),
                                dtype=np.int64, count=len(pairs)),
                    dports=np.fromiter((p[2] for p in pairs),
                                       dtype=np.int64,
                                       count=len(pairs)))
                if len(affected):
                    self.memo.partial_invalidate(len(affected),
                                                 delta.reason)
                    prev = self._memo_dirty
                    self._memo_dirty = (affected if prev is None
                                        else np.union1d(prev, affected))
            self.memo.adopt()
        elif self.memo is not None:
            self.memo.adopt()

    def _rebind(self, engine) -> None:
        if engine is self.engine:
            return
        self.engine = engine
        for t in self.tables.values():
            t.engine = engine

    # -- per-chunk host featurize -----------------------------------------
    def _string_lut(self, field: str, idx: np.ndarray, offsets,
                    blob) -> np.ndarray:
        """Chunk string-table ids → session string ids (session table
        row == match-word row), interning new strings."""
        tbl = self.tables[field]
        uniq = np.unique(idx)
        lut = np.zeros(int(idx.max()) + 1 if len(idx) else 1,
                       dtype=np.int32)
        for u in uniq:
            s = blob[int(offsets[u]):int(offsets[u + 1])].tobytes()
            lut[u] = tbl.intern(s)
        return lut[idx]

    def _kafka_lut(self, key: str, idx: np.ndarray, offsets,
                   blob) -> np.ndarray:
        intern = self.engine.policy.kafka_interns.get(key, {})
        uniq, inv = np.unique(idx, return_inverse=True)
        out = np.empty(len(uniq), dtype=np.int32)
        for j, u in enumerate(uniq):
            s = blob[int(offsets[u]):int(offsets[u + 1])].tobytes()
            memo_key = (key, s)
            v = self.kafka_memo.get(memo_key)
            if v is None:
                v = self.kafka_memo[memo_key] = intern.get(
                    s.decode("utf-8", "replace"), -2)
            out[j] = v
        return out[inv]

    def _encode_rows(self, rec, l7, offsets, blob, gen) -> np.ndarray:
        B = len(rec)
        out = np.full((B, self.row_width), -2, dtype=np.int32)
        col = {c: i for i, c in enumerate(_ROW_COLS)}
        ingress = rec["direction"] == int(TrafficDirection.INGRESS)
        out[:, col["ep_ids"]] = np.where(
            ingress, rec["dst_identity"], rec["src_identity"])
        out[:, col["peer_ids"]] = np.where(
            ingress, rec["src_identity"], rec["dst_identity"])
        out[:, col["dports"]] = rec["dport"]
        out[:, col["protos"]] = rec["proto"]
        out[:, col["directions"]] = rec["direction"]
        out[:, col["l7_types"]] = rec["l7_type"]
        out[:, col["kafka_api_key"]] = l7["kafka_api_key"]
        out[:, col["kafka_api_version"]] = l7["kafka_api_version"]
        out[:, col["kafka_client"]] = self._kafka_lut(
            "client_id", l7["kafka_client"], offsets, blob)
        out[:, col["kafka_topic"]] = self._kafka_lut(
            "topic", l7["kafka_topic"], offsets, blob)
        for f in _FIELDS:
            out[:, col[f"{f}_row"]] = self._string_lut(
                f, l7[f], offsets, blob)
        ncols = len(_ROW_COLS)
        if gen is not None:
            gen_block = _gen_intern_rows(
                gen, offsets, blob, self.engine.policy.kafka_interns)
            fam, uniq_ser, l7g_row = _gen_l7g_cols(gen, offsets, blob)
            # serialized frontend records intern into the session l7g
            # table (delta-scanned like any string); non-frontend
            # records keep id 0 (the empty string)
            tbl = self.tables["l7g"]
            ser_ids = np.zeros(len(uniq_ser), dtype=np.int32)
            for j, s in enumerate(uniq_ser[1:], start=1):
                ser_ids[j] = tbl.intern(s)
            out[:, ncols] = gen_block[:, 0]
            out[:, ncols + 1] = fam
            out[:, ncols + 2] = ser_ids[l7g_row]
            out[:, ncols + 3:] = gen_block[:, 1:]
            # frontend records normalize the l7-type lane to their
            # family — same invariant as encode_flows; keys the fe
            # lane on device and the (ep, l7type, dport) memo mirror
            out[:, col["l7_types"]] = np.where(
                fam > 0, fam, out[:, col["l7_types"]])
        else:
            # no generic section: proto/pair slots stay -2 ("absent"),
            # matching encode_flows' defaults for non-generic flows;
            # the family/l7g columns read "no frontend record"
            out[:, ncols + 1] = 0
            out[:, ncols + 2] = 0
        return out

    @staticmethod
    def _hash_rows(rows: np.ndarray) -> np.ndarray:
        """The shared dedup row hash (``engine.memo.hash_rows`` — one
        implementation for the offline CaptureReplay dedup and this
        online session, so the two layers can't drift). Dedup by 1-D
        hash is ~10× cheaper than ``np.unique(rows, axis=0)``'s
        lexicographic row sort (29 ms → ~3 ms per 8k×21 chunk, the
        serving path's host hot spot); collisions are handled exactly,
        never assumed away."""
        return hash_rows(rows)

    def _row_idx(self, rows: np.ndarray) -> np.ndarray:
        """Chunk rows → session row ids, interning new unique rows.

        Exactness: hashes pick CANDIDATE matches only. Within the
        chunk, every row is verified against its hash-group
        representative; across the session, the id map chains on hash
        with stored row bytes compared before reuse. Any mismatch
        falls back to the exact row-sort path for this chunk."""
        h = self._hash_rows(rows)
        uh, first, inv = np.unique(h, return_index=True,
                                   return_inverse=True)
        # within-chunk verification: all rows must equal their hash
        # representative, or two distinct rows collided
        if not np.array_equal(rows, rows[first][inv]):
            return self._row_idx_exact(rows)
        lut = np.empty(len(uh), dtype=np.int32)
        for j in range(len(uh)):
            row = rows[first[j]]
            key = int(uh[j])
            chain = self.row_ids.get(key)
            rid = None
            if chain is not None:
                for stored_bytes, stored_id in chain:
                    if stored_bytes == row.tobytes():
                        rid = stored_id
                        break
            if rid is None:
                rid = self.n_rows
                self.n_rows += 1
                self._pending_rows.append(row.copy())
                self._row_eps.append((int(row[0]),
                                      int(row[_L7_COL]),
                                      int(row[_DPORT_COL])))
                if chain is None:
                    self.row_ids[key] = [(row.tobytes(), rid)]
                else:
                    chain.append((row.tobytes(), rid))
            lut[j] = rid
        return lut[inv].astype(np.int32)

    def _row_idx_exact(self, rows: np.ndarray) -> np.ndarray:
        """Exact fallback for an in-chunk hash collision (row sort)."""
        uniq, inv = np.unique(rows, axis=0, return_inverse=True)
        lut = np.empty(len(uniq), dtype=np.int32)
        for j in range(len(uniq)):
            row = uniq[j]
            key = int(self._hash_rows(row[None, :])[0])
            chain = self.row_ids.setdefault(key, [])
            rid = None
            for stored_bytes, stored_id in chain:
                if stored_bytes == row.tobytes():
                    rid = stored_id
                    break
            if rid is None:
                rid = self.n_rows
                self.n_rows += 1
                self._pending_rows.append(row.copy())
                self._row_eps.append((int(row[0]),
                                      int(row[_L7_COL]),
                                      int(row[_DPORT_COL])))
                chain.append((row.tobytes(), rid))
            lut[j] = rid
        return lut[inv].astype(np.int32)

    def _flush_rows(self) -> None:
        if not self._pending_rows:
            return
        base = self.n_rows - len(self._pending_rows)
        D = _pow2(len(self._pending_rows), floor=256)
        # cover base+D (same clamping hazard as _StringTable.flush)
        cap_needed = _pow2(max(self.n_rows, base + D))
        if cap_needed > self.row_capacity or self.rows_dev is None:
            old = self.rows_dev
            self.row_capacity = cap_needed
            grown = jnp.zeros((self.row_capacity, self.row_width),
                              dtype=jnp.int32)
            if old is not None:
                grown = _delta_rows_update(grown, old, 0)
            self.rows_dev = grown
        delta = np.zeros((D, self.row_width), dtype=np.int32)
        delta[:len(self._pending_rows)] = np.stack(self._pending_rows)
        self.rows_dev = _delta_rows_update(
            self.rows_dev, jax.device_put(delta, self.engine.device),
            base)
        self._pending_rows = []

    # -- the chunk entry point --------------------------------------------
    def encode_ids(self, rec, l7, offsets, blob, gen=None):
        """HOST half of a chunk: swap-safety check, capacity guard,
        featurize + intern → ``(idx, novel)`` where ``idx`` is the
        chunk's session row ids (int32, unpadded) and ``novel`` the
        number of rows this chunk interned for the first time. No
        device work happens here — the verdict ring packs many
        streams' encoded ids into ONE :meth:`serve_ids` dispatch.
        Rows already interned (``n - novel``) never ship their
        featurized bytes again: only the 4-byte id crosses, the
        memo-bypass selective-copy property the ring counts."""
        n = len(rec)
        if n == 0:
            return np.zeros(0, dtype=np.int32), 0
        self._ensure_current()
        if (self.n_rows >= self.max_rows
                or any(t.n >= self.max_strings
                       for t in self.tables.values())):
            self.reset()
        rows = self._encode_rows(rec, l7, offsets, blob, gen)
        before = self.n_rows
        idx = self._row_idx(rows)
        return idx, self.n_rows - before

    def serve_ids(self, idx: np.ndarray, authed_pairs=None,
                  provenance: bool = False):
        """DEVICE half: flush pending string/row deltas and serve one
        id vector — ONE fused dispatch (delta verdict step + memo
        fill) plus one on-device gather, however many streams'
        chunks were packed into ``idx``. Returns the device verdict
        array aligned to ``idx`` (padding sliced by the caller); with
        ``provenance=True`` returns a
        :class:`~cilium_tpu.engine.attribution.ServedPack` carrying
        the attribution lane, per-row cited generations, and the
        memo-hit/computed split alongside the verdicts (same
        dispatch — the extra lanes ride the gather the memo already
        does)."""
        for t in self.tables.values():
            t.flush()
        self._flush_rows()
        n = len(idx)
        B_pad = _pow2(n, floor=32)
        if B_pad > n:
            # pad ids point at row 0 — a REAL session row, but padded
            # verdicts are sliced off before anything reads them
            idx = np.concatenate(
                [idx, np.zeros(B_pad - n, dtype=np.int32)])
        from cilium_tpu.engine.verdict import DISPATCH_POINT, _faults

        _faults.maybe_fail(DISPATCH_POINT)
        table_words = {f: self.tables[f].words for f in _FIELDS}
        if "l7g_trans" in self.engine._arrays:
            table_words["l7g"] = self.tables["l7g"].words
        if self.memo is not None:
            return self._memo_serve(idx, table_words, authed_pairs,
                                    provenance=provenance)
        batch = {"rows": self.rows_dev,
                 "idx": jax.device_put(idx, self.engine.device)}
        self.engine._stage_auth(batch, authed_pairs)
        out = self._step(self.engine._arrays, table_words, batch)
        if not provenance:
            return out["verdict"]
        return self._pack_provenance(out, idx, memo_hit=None)

    def _pack_provenance(self, out, idx, memo_hit=None):
        """Build the ServedPack for one served id vector. ``out`` is
        the step/gather output dict; ``memo_hit`` the per-row
        hit mask (None = everything computed this dispatch)."""
        from cilium_tpu.engine.attribution import (
            ServedPack,
            kernel_label,
        )
        from cilium_tpu.engine.memo import policy_generation

        gen_now = policy_generation()
        n = len(idx)
        if memo_hit is None:
            memo_hit = np.zeros(n, dtype=bool)
        if self.memo is not None and self.memo.gens is not None:
            gens = self.memo.cited_gens(idx)
        else:
            gens = np.full(n, gen_now, dtype=np.int64)
        return ServedPack(
            verdict=out["verdict"],
            l7_match=out.get("l7_match"),
            match_spec=out["match_spec"],
            gens=gens, memo_hit=memo_hit, generation=gen_now,
            kernel=kernel_label(self.engine))

    def verdict_chunk(self, rec, l7, offsets, blob, gen=None,
                      authed_pairs=None):
        """Featurize + intern one chunk, push deltas, dispatch the
        gather+verdict step. Returns (n, device verdict array).
        Composition of :meth:`encode_ids` + :meth:`serve_ids` — the
        single-stream shape of what the verdict ring does for many
        streams per dispatch."""
        from cilium_tpu.runtime.tracing import (
            PHASE_DEVICE,
            PHASE_HOST,
            TRACER,
        )

        n = len(rec)
        if n == 0:
            return 0, None
        with TRACER.span("session.featurize", phase=PHASE_HOST,
                         records=n):
            idx, _ = self.encode_ids(rec, l7, offsets, blob, gen)
        with TRACER.span("session.dispatch", phase=PHASE_DEVICE,
                         records=n):
            # delta flushes are device transfers — device-dispatch,
            # like the step they feed
            return n, self.serve_ids(idx, authed_pairs=authed_pairs)

    def _memo_serve(self, idx: np.ndarray, table_words,
                    authed_pairs, provenance: bool = False):
        """Serve one (padded) id chunk from the verdict memo. Outputs
        for DELTA rows — session rows newer than the memo's fill mark
        — are computed first through the shared capture step (so
        memoized and recomputed verdicts are bit-equal by
        construction) and spliced into the device memo table; the
        chunk itself is then one gather. An auth-view change or policy
        generation bump drops the memo and the next chunk refills from
        row 0."""
        sig = auth_signature(authed_pairs)
        m = self.memo
        m.valid_for(sig)  # drops the memo on generation/auth change
        base0 = m.filled  # rows below this mark are memo HITS
        if m.filled < self.n_rows:
            base = m.filled
            n_new = self.n_rows - base
            D = _pow2(n_new, floor=32)
            # pad ids clamp to real rows; their (garbage) memo slots
            # sit beyond the fill mark and are rewritten by the next
            # delta before any id can reference them
            fill_idx = np.minimum(
                np.arange(base, base + D, dtype=np.int32),
                self.n_rows - 1)
            batch = {"rows": self.rows_dev,
                     "idx": jax.device_put(fill_idx,
                                           self.engine.device)}
            self.engine._stage_auth(batch, authed_pairs)
            out = self._step(self.engine._arrays, table_words, batch)
            m.fill(memo_pack(out), base, n_new, sig)
        dirty = self._memo_dirty
        if dirty is not None and len(dirty) and m.table is not None:
            # bank-scoped refill: rewrite ONLY the rows a committed
            # revision touched; everything else keeps serving
            D = _pow2(len(dirty), floor=32)
            ridx = (np.concatenate(
                [dirty, np.full(D - len(dirty), dirty[0],
                                dtype=dirty.dtype)])
                if D > len(dirty) else dirty)
            batch = {"rows": self.rows_dev,
                     "idx": jax.device_put(ridx, self.engine.device)}
            self.engine._stage_auth(batch, authed_pairs)
            out = self._step(self.engine._arrays, table_words, batch)
            m.refill_scatter(ridx, memo_pack(out), len(dirty))
        refilled = dirty if dirty is not None else None
        self._memo_dirty = None
        # gather() stages idx itself (memo.py) — a device_put here
        # would be a second, redundant transfer of the id block
        gathered = m.gather(idx)
        if not provenance:
            return gathered["verdict"]
        # memo-hit = the row was resident BEFORE this dispatch and was
        # not rewritten by the bank-scoped refill above — everything
        # else was computed under the current generation
        hit = idx < base0
        if refilled is not None and len(refilled):
            hit &= ~np.isin(idx, refilled)
        return self._pack_provenance(gathered, idx, memo_hit=hit)
