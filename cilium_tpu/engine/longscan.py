"""Long-payload automaton scanning: SP + CP (ring) parallelism.

The reference handles long payloads by *streaming* (proxylib ``OnData``
returns MORE with bounded buffers — SURVEY.md §5.7); a TPU wants the
whole payload resident and the scan *parallelized*. The key identity:
a DFA's per-byte step is a function ``f_c: S→S``, and function
composition is **associative** — so a payload's net effect can be
computed blockwise:

* **SP (sequence parallel, single device)** — split the payload into
  blocks; compute each block's composed transition vector ``g[S]`` with
  a sequential ``lax.scan`` *inside* the block but vectorized *across*
  blocks; combine blocks with ``lax.associative_scan`` (log depth).
  Parallelism L/block × S instead of a length-L sequential chain.
* **CP (context parallel, multi-device)** — shard the payload length
  across a mesh axis; each device composes its shard locally, then a
  **ring ``ppermute`` pass** circulates the small ``[S]`` carry
  (ring-attention-shaped: heavy local compute + neighbor exchange of a
  small state), giving each device the composition of everything to its
  left; one more local apply yields the final state.

Composition cost is an S-wide gather per step, so this pays off when
S is modest (payload automata: tens of states) and L is large (the
regime the reference's streaming parsers target).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _compose(f: jax.Array, g: jax.Array) -> jax.Array:
    """(f ∘ g)[s] = f[g[s]] — apply g first, then f.

    Supports leading batch dims on both (broadcast like jnp ops):
    f, g: [..., S] int32.
    """
    return jnp.take_along_axis(f, g, axis=-1)


def block_transitions(
    trans: jax.Array,       # [S, K] int32
    byteclass: jax.Array,   # [256] int32
    data: jax.Array,        # [..., L] uint8 — L is the block length
    valid: Optional[jax.Array] = None,  # [..., L] bool, False = skip byte
) -> jax.Array:
    """Composed transition vector for each block: out[..., S] with
    out[..., s] = state reached from s after consuming the block."""
    S = trans.shape[0]
    cls = byteclass[data.astype(jnp.int32)]            # [..., L]
    L = data.shape[-1]

    def step(g, t):
        # next g[s] = T[g[s], c_t]  (apply byte t after the prefix)
        c_t = cls[..., t]                               # [...]
        rows = jnp.take_along_axis(
            trans[g], c_t[..., None, None],
            axis=-1)[..., 0]                            # [..., S]
        if valid is not None:
            rows = jnp.where(valid[..., t, None], rows, g)
        return rows, None

    init = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                            data.shape[:-1] + (S,))
    out, _ = lax.scan(step, init, jnp.arange(L, dtype=jnp.int32))
    return out


def payload_scan_sp(
    trans: jax.Array,       # [S, K]
    byteclass: jax.Array,   # [256]
    start: jax.Array,       # scalar int32
    data: jax.Array,        # [B, L] uint8
    lengths: jax.Array,     # [B] int32
    block: int = 256,
) -> jax.Array:
    """Final DFA states [B] for long payloads, blockwise-parallel."""
    B, L = data.shape
    pad = (-L) % block
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    nblocks = data.shape[1] // block
    blocks = data.reshape(B, nblocks, block)
    pos = (jnp.arange(nblocks * block)
           .reshape(nblocks, block))                    # [nb, block]
    valid = pos[None, :, :] < lengths[:, None, None]    # [B, nb, block]

    g = block_transitions(trans, byteclass, blocks, valid)  # [B, nb, S]
    # left-to-right composition: net = g_nb ∘ ... ∘ g_1.
    # associative_scan composes adjacent pairs; with fn(a, b) where a is
    # the earlier block, the combined effect is b ∘ a (a applied first).
    net = lax.associative_scan(
        lambda a, b: _compose(b, a), g, axis=1)         # prefix compositions
    final_fn = net[:, -1, :]                            # [B, S]
    return jnp.take_along_axis(
        final_fn, jnp.broadcast_to(start, (B,))[:, None].astype(jnp.int32),
        axis=1)[:, 0]


@functools.lru_cache(maxsize=None)
def _cp_step(mesh: Mesh, seq_axis: str, block: int):
    """Cached shard_map wrapper per (mesh, axis, block): the wrapper
    used to be rebuilt inside :func:`payload_scan_cp`, so every call
    was a fresh closure — a jit-cache miss and a full re-trace per
    payload batch (ctlint recompile-hazard). Batch size and shard
    length are read off the shard inside, so the same compiled step
    serves every payload shape that hits it."""
    n_dev = mesh.shape[seq_axis]

    def local(trans, byteclass, start, data_shard, lengths):
        B, shard_len = data_shard.shape
        # my position on the ring
        idx = lax.axis_index(seq_axis)
        offset = idx * shard_len
        # local composed function over my shard (blockwise SP inside)
        pad = (-shard_len) % block
        d = jnp.pad(data_shard, ((0, 0), (0, pad))) if pad else data_shard
        nb = d.shape[1] // block
        blocks = d.reshape(B, nb, block)
        pos = offset + jnp.arange(nb * block).reshape(nb, block)
        valid = pos[None, :, :] < lengths[:, None, None]
        g = block_transitions(trans, byteclass, blocks, valid)
        net = lax.associative_scan(lambda a, b: _compose(b, a), g, axis=1)
        mine = net[:, -1, :]                            # [B, S]

        # ring exclusive-prefix composition: after n_dev-1 steps,
        # ``carry`` = composition of all shards strictly to my left.
        S = trans.shape[0]
        identity = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

        from cilium_tpu.parallel import collectives

        def ring_step(i, state):
            carry, send = state
            recv = collectives.ppermute(send, seq_axis, perm,
                                        site="cp.ring_carry")
            # recv = cumulative of the sender (my left neighbor, covering
            # shards [sender-k .. sender]); fold into my carry only while
            # it still describes shards left of me: step i delivers the
            # shard i+1 to my left.
            take = (idx - 1 - i) >= 0
            carry = jnp.where(take, _compose(carry, recv), carry)
            return carry, recv

        carry = identity
        send = mine
        # the ring body traces once, executes n_dev-1 times per block
        # (a 1-device mesh runs it zero times — factor 0 records 0)
        with collectives.LEDGER.scaled(n_dev - 1):
            carry, _ = lax.fori_loop(
                0, n_dev - 1, lambda i, st: ring_step(i, st),
                (carry, send))
        # NOTE: this fori ring passes each device's LOCAL function one
        # hop per step, so after k steps I have received the local
        # function of the device k hops left and composed it in order.
        final_fn = _compose(mine, carry)                # [B, S]
        states = jnp.take_along_axis(
            final_fn,
            jnp.broadcast_to(start, (B,))[:, None].astype(jnp.int32),
            axis=1)[:, 0]
        # device idx holds the composition of shards [0..idx]; only the
        # last device has the whole payload — gather and keep its answer
        all_states = collectives.all_gather(
            states, seq_axis, site="cp.final_gather")   # [n_dev, B]
        return all_states[n_dev - 1]

    from jax import shard_map

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(), P(None, seq_axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def payload_scan_cp(
    mesh: Mesh,
    trans,                  # [S, K]
    byteclass,              # [256]
    start,                  # scalar int32
    data,                   # [B, L] — L sharded over seq_axis
    lengths,                # [B]
    seq_axis: str = "seq",
    block: int = 256,
):
    """Context-parallel payload scan: L sharded across ``seq_axis``;
    per-device blockwise composition + ring ppermute of the carry."""
    n_dev = mesh.shape[seq_axis]
    _B, L = data.shape
    assert L % n_dev == 0, "payload length must divide the seq axis"
    fn = _cp_step(mesh, seq_axis, block)
    return fn(trans, byteclass, jnp.asarray(start, jnp.int32), data,
              lengths)
