"""CP (context parallel): shard the **payload byte columns**, not the
automaton state.

MULTICHIP_PERF_r05's TP lane is the indictment this module answers:
sharding the DFA *state* axis (parallel/tp.py) costs one ``psum`` per
scanned byte — the PR-6 collective ledger records exactly L collectives
per compiled block, and the lane spends 99.99% of its time in them.
Hyperflex (PAPERS.md) and the state-space-duality framing say the scan
is a *blockwise-parallel* workload: a DFA byte step is a function
``f_c: S→S`` and composition is associative, so a payload's net effect
factors into per-block composed transition vectors that combine with
ONE small exchange — not a collective per byte.

The CP layout (SURVEY §2.6 CP row):

* the full (small) transition table is **resident on every device** —
  the tensors that grow with pattern complexity stay put;
* the payload **byte columns are sharded** over the ``seq`` axis: each
  device scans its contiguous block with
  :func:`cilium_tpu.engine.longscan.block_transitions` (blockwise SP
  inside the shard) and composes a block transition vector ``[B, S]``;
* a **single carry-exchange collective per compiled block** threads
  the automaton state across devices: the per-device composed vectors
  ride one ring pass (``all_gather`` of the ``[NB, B, S]`` carries —
  XLA lowers it as the ring permute circulating each shard's carry one
  hop per step, fused into one collective op), after which every
  device composes the n functions locally and reads the final states.
  The ledger therefore records **1 collective per block** where TP
  records L.

The verdict-step face (:func:`make_cp_verdict_step`) reads the
megakernel's extra group-accept planes (``rp_path_gaccept``) off the
final carried state, so the factored resolve still runs in the SAME
single dispatch — CP changes where bytes live, never the verdict.

When this pays: long payloads (the 1KiB header bucket and beyond) on a
real mesh — per-device work is ``L/n × S`` gathers against the
sequential scan's ``L × 1``, so the lane wins when payloads are long
and the per-bank state count is modest (payload automata: tens of
states). On the emulated CPU mesh the honest number is the
constant-silicon overhead vs the same blockwise math on one device
(``bench_multichip.py`` cp lane).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cilium_tpu.engine.longscan import _compose, block_transitions
from cilium_tpu.parallel import collectives
from jax import shard_map

#: a field only CP-shards when each device gets at least this many
#: byte columns — below it the exchange would outweigh the scan and
#: the field scans replicated (zero collectives) instead
MIN_SHARD_COLS = 8

#: the five scanned string fields: (bank-tensor prefix, batch field)
_SCAN_FIELDS = (("path", "path"), ("method", "method"),
                ("host", "host"), ("hdr", "headers"), ("dns", "qname"))


def _compose_finals(trans, byteclass, start, data_shard, lengths,
                    seq_axis: str, n_dev: int, block: int, site: str,
                    ) -> jax.Array:
    """shard_map-body core: this device's byte-column block → final
    DFA states ``[NB, B]`` for every bank, via blockwise composition
    and ONE carry-exchange collective.

    ``trans [NB, S, K]`` / ``byteclass [NB, 256]`` / ``start [NB]``
    are replicated; ``data_shard [B, Lg/n]`` is this device's
    contiguous column block of the globally ``[B, Lg]`` payload."""
    NB, S, _K = trans.shape
    B, shard_len = data_shard.shape
    idx = lax.axis_index(seq_axis)
    offset = (idx * shard_len).astype(jnp.int32)
    # blockwise SP inside the shard (longscan identity): pad to the
    # inner block, compose blocks with a log-depth associative scan
    pad = (-shard_len) % block
    d = jnp.pad(data_shard, ((0, 0), (0, pad))) if pad else data_shard
    nb = d.shape[1] // block
    blocks = d.reshape(B, nb, block)
    pos = offset + jnp.arange(nb * block, dtype=jnp.int32).reshape(
        nb, block)
    valid = pos[None, :, :] < lengths[:, None, None]    # [B, nb, blk]

    def one_bank(tr, bc):
        g = block_transitions(tr, bc, blocks, valid)     # [B, nb, S]
        net = lax.associative_scan(lambda a, b: _compose(b, a), g,
                                   axis=1)
        return net[:, -1, :]                             # [B, S]

    mine = jax.vmap(one_bank)(trans, byteclass)          # [NB, B, S]
    # THE carry exchange — the lane's ONLY collective, once per
    # compiled block (TP pays one psum per scanned byte here)
    allg = collectives.all_gather(mine, seq_axis, site=site)
    # local left-to-right composition of the n carried functions
    carry = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                             (NB, B, S))
    for j in range(n_dev):
        carry = _compose(allg[j], carry)
    return jnp.take_along_axis(
        carry,
        jnp.broadcast_to(start.astype(jnp.int32)[:, None, None],
                         (NB, B, 1)),
        axis=2)[..., 0]                                  # [NB, B]


def _words_of(accept: jax.Array, finals: jax.Array) -> jax.Array:
    """accept [NB, S, W], finals [NB, B] → words [B, NB, W]."""
    w = jax.vmap(lambda a, fs: a[fs])(accept, finals)
    return jnp.transpose(w, (1, 0, 2))


@functools.lru_cache(maxsize=None)
def _cp_banked_step(mesh: Mesh, seq_axis: str, block: int,
                    want_extra: bool):
    """Cached shard_map wrapper per (mesh, axis, block) — the PR-4
    lru-factory discipline: rebuilding the wrapper per call is a
    jit-cache miss and a full re-trace (ctlint recompile-hazard)."""
    n_dev = mesh.shape[seq_axis]

    def scan(trans, byteclass, start, accept, extra, data, lengths):
        finals = _compose_finals(trans, byteclass, start, data,
                                 lengths, seq_axis, n_dev, block,
                                 "cp.carry_exchange")
        words = _words_of(accept, finals)
        if extra is None:
            return words
        return words, _words_of(extra, finals)

    if want_extra:
        def wrapped(trans, byteclass, start, accept, extra, data,
                    lengths):
            return scan(trans, byteclass, start, accept, extra, data,
                        lengths)
        in_specs = (P(), P(), P(), P(), P(), P(None, seq_axis), P())
        out_specs = (P(), P())
    else:
        def wrapped(trans, byteclass, start, accept, data, lengths):
            return scan(trans, byteclass, start, accept, None, data,
                        lengths)
        in_specs = (P(), P(), P(), P(), P(None, seq_axis), P())
        out_specs = P()
    return jax.jit(shard_map(
        wrapped, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False))


def dfa_scan_banked_cp(
    mesh: Mesh,
    trans: jax.Array,       # [NB, S, K] int32 — replicated
    byteclass: jax.Array,   # [NB, 256] int32
    start: jax.Array,       # [NB] int32
    accept: jax.Array,      # [NB, S, W] uint32
    data: jax.Array,        # [B, L] uint8 — L sharded over seq_axis
    lengths: jax.Array,     # [B] int32
    seq_axis: str = "seq",
    block: int = 256,
    extra_accept: Optional[jax.Array] = None,
):
    """Payload-sharded banked scan → accept words ``[B, NB, W]``
    uint32, bit-identical to ``dfa_kernel.dfa_scan_banked`` (same
    contract incl. the ``extra_accept`` → ``(words, extra_words)``
    tuple the megakernel's group planes use). ``L`` pads up to a
    multiple of the seq-axis size; padded bytes sit past every
    ``lengths`` bound and are composition no-ops."""
    n_dev = mesh.shape[seq_axis]
    _B, L = data.shape
    pad = (-L) % n_dev
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    fn = _cp_banked_step(mesh, seq_axis, int(block),
                         extra_accept is not None)
    start = jnp.asarray(start, jnp.int32)
    if extra_accept is None:
        return fn(trans, byteclass, start, accept, data, lengths)
    return fn(trans, byteclass, start, accept, extra_accept, data,
              lengths)


# ----------------------------------------------------- verdict-step face --

def cp_sharded_keys(batch: Dict, mesh: Mesh,
                    seq_axis: str = "seq") -> Tuple[str, ...]:
    """Which ``*_data`` byte buckets CP-shard on this mesh: the column
    count must divide the axis and leave ≥ :data:`MIN_SHARD_COLS`
    per device (method's 16 bytes stay replicated on an 8-way mesh —
    a 2-column shard would be all exchange, no scan)."""
    n = mesh.shape[seq_axis]
    out = []
    for _prefix, field in _SCAN_FIELDS:
        key = f"{field}_data"
        if key not in batch:
            continue
        L = batch[key].shape[1]
        if L % n == 0 and L // n >= MIN_SHARD_COLS:
            out.append(key)
    return tuple(sorted(out))


def cp_shard_batch(batch: Dict, mesh: Mesh, seq_axis: str = "seq",
                   ) -> Dict:
    """Stage a flat/packed batch for the CP step ONCE: sharded byte
    buckets get ``P(None, seq_axis)``, everything else replicates —
    explicit NamedSharding device_puts, no per-call re-shard."""
    sharded = set(cp_sharded_keys(batch, mesh, seq_axis))
    out = {}
    for k, v in batch.items():
        spec = P(None, seq_axis) if k in sharded else P()
        out[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


@functools.lru_cache(maxsize=None)
def _cp_verdict_factory(mesh: Mesh, seq_axis: str, block: int,
                        batch_keys: Tuple[str, ...],
                        sharded: Tuple[str, ...]):
    """One compiled program per (mesh, axis, block, batch layout):
    mapstate gather + five byte-scans (CP-sharded where the bucket
    divides) + factored resolve, all inside ONE shard_map dispatch."""
    from cilium_tpu.core.flow import TrafficDirection
    from cilium_tpu.engine.dfa_kernel import dfa_scan_banked
    from cilium_tpu.engine.mapstate_kernel import mapstate_lookup
    from cilium_tpu.engine.megakernel import fused_verdict_core
    from cilium_tpu.engine.verdict import _verdict_core, unpack_batch

    n_dev = mesh.shape[seq_axis]
    sharded_set = frozenset(sharded)

    def body(arrays, batch):
        b = unpack_batch(batch) if "scalars" in batch else dict(batch)
        ms = mapstate_lookup(
            arrays["ms_key_w0"], arrays["ms_key_w1"],
            arrays["ms_key_w2"], arrays["ms_deny"],
            arrays["ms_ruleset"], arrays["ms_enf_ids"],
            arrays["ms_enf_flags"],
            b["ep_ids"], b["peer_ids"], b["dports"], b["protos"],
            b["directions"],
            auth=arrays.get("ms_auth"),
            port_plens=arrays.get("ms_plens"),
            tmpl_ids=arrays.get("ms_tmpl_ids"))
        plan_on = "rp_g_method" in arrays  # static under jit
        words = []
        gwords = None
        for prefix, field in _SCAN_FIELDS:
            data = b[f"{field}_data"]
            lengths = b[f"{field}_len"]
            valid = b[f"{field}_valid"]
            want_groups = plan_on and prefix == "path"
            extra = arrays["rp_path_gaccept"] if want_groups else None
            if f"{field}_data" in sharded_set:
                # data here is this device's column block
                finals = _compose_finals(
                    arrays[f"{prefix}_trans"],
                    arrays[f"{prefix}_byteclass"],
                    arrays[f"{prefix}_start"], data, lengths,
                    seq_axis, n_dev, block, f"cp.carry.{prefix}")
                w3 = _words_of(arrays[f"{prefix}_accept"], finals)
                g3 = _words_of(extra, finals) if want_groups else None
            else:
                out = dfa_scan_banked(
                    arrays[f"{prefix}_trans"],
                    arrays[f"{prefix}_byteclass"],
                    arrays[f"{prefix}_start"],
                    arrays[f"{prefix}_accept"],
                    data, lengths, extra_accept=extra)
                w3, g3 = out if want_groups else (out, None)
            if g3 is not None:
                gw = jax.lax.reduce(g3, jnp.uint32(0),
                                    jax.lax.bitwise_or, (1,))
                gwords = jnp.where(valid[:, None], gw, 0)
            flat = w3.reshape(w3.shape[0], -1)
            words.append(jnp.where(valid[:, None], flat, 0))
        if "l7g_trans" in arrays:   # static per staged policy
            # protocol-frontend scan: small replicated bank stack,
            # full batch per device (serialized records are short —
            # CP column-sharding them would be all exchange, no scan)
            w3 = dfa_scan_banked(
                arrays["l7g_trans"], arrays["l7g_byteclass"],
                arrays["l7g_start"], arrays["l7g_accept"],
                b["l7g_data"], b["l7g_len"])
            flat = w3.reshape(w3.shape[0], -1)
            words.append(jnp.where(b["l7g_valid"][:, None], flat, 0))
        words = tuple(words)
        ingress = b["directions"] == int(TrafficDirection.INGRESS)
        src = jnp.where(ingress, b["peer_ids"], b["ep_ids"])
        dst = jnp.where(ingress, b["ep_ids"], b["peer_ids"])
        kafka_cols = (b["kafka_api_key"], b["kafka_api_version"],
                      b["kafka_client"], b["kafka_topic"])
        gen_cols = (b["gen_proto"], b["gen_pairs"])
        if not plan_on:
            return _verdict_core(arrays, ms, b["l7_types"], words,
                                 kafka_cols, (src, dst), b,
                                 gen_cols=gen_cols)
        return fused_verdict_core(arrays, ms, b["l7_types"], words,
                                  gwords, kafka_cols, (src, dst), b,
                                  gen_cols=gen_cols)

    batch_specs = {k: (P(None, seq_axis) if k in sharded_set else P())
                   for k in batch_keys}
    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(), batch_specs), out_specs=P(),
        check_vma=False))


def make_cp_verdict_step(mesh: Mesh, batch: Dict,
                         seq_axis: str = "seq", block: int = 256):
    """The CP-sharded verdict step for ``batch``'s layout: full
    nine-lane output, bit-equal to the single-device fused step, one
    dispatch. Stage inputs with :func:`cp_shard_batch` (batch) and
    replicated ``device_put`` (policy arrays)."""
    keys = tuple(sorted(batch.keys()))
    sharded = cp_sharded_keys(batch, mesh, seq_axis)
    return _cp_verdict_factory(mesh, seq_axis, int(block), keys,
                               sharded)
