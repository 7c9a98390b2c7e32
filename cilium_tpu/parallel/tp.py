"""TP (tensor parallel): shard the automaton **state axis** across chips.

SURVEY.md §2.6: the reference partitions its verdict table per-endpoint
(per-endpoint BPF policy maps); the TP analog here shards the DFA
transition-table *state* dimension over a mesh axis, with a ``psum``
combining the per-shard partial contributions — the classic
contracting-dimension-sharded matmul.

The step uses the one-hot matmul formulation of the DFA transition:
with the current state one-hot
``oh[B, S]`` and transition table ``T[S, K]``, the next-state row is
``oh @ T``. Sharding ``S`` gives each device a slice ``T[S/n, K]`` and
the *partial* one-hot for its state range (all-zero rows when the
current state lives on another shard); the local matmul produces a
partial ``[B, K]`` contribution and ``lax.psum`` restores the exact row
(each one-hot row has exactly one nonzero, so the sum has exactly one
contributing term). State ids ride through float32, exact only below 2^24 — enforced with a hard
check (``MAX_TP_STATES``), not a silent wrap. Accept-word extraction is
sharded the same way, byte-plane by byte-plane.

When this pays: rule banks whose subset-construction DFA is too big for
one chip's HBM (``S × K`` transition + ``S × W`` accept tensors) — the
state axis is the only axis that grows with pattern complexity rather
than pattern count, so it is the axis TP must cut.

This is the **fallback** lane, never a throughput play: the scan-step
``psum`` executes once per scanned byte (on record in the PR-6
collective ledger; MULTICHIP_PERF_r05 measured the lane 99.99%
collective-bound). The throughput lane for scan sharding is the
payload-sharded blockwise CP scan (``parallel/cp.py`` — ONE carry
exchange per compiled block); reach for TP only when a single bank's
states genuinely exceed one chip.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from cilium_tpu.parallel import collectives
from jax import shard_map

#: one-hot matmul carries state ids in f32 — exact only below 2^24
MAX_TP_STATES = 1 << 24


def _check_state_count(S: int) -> None:
    if S >= MAX_TP_STATES:
        raise ValueError(
            f"TP one-hot matmul step is exact only for state ids < "
            f"2^24; got {S} states. Split the bank (smaller bank_size / "
            f"max_dfa_states) before sharding.")


def pad_states(trans: np.ndarray, accept: np.ndarray,
               n_shards: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pad the state axis to a multiple of ``n_shards``.

    Padded states self-loop into the dead state (0) and accept nothing;
    reachable dynamics never enter them. Accepts single-bank
    ``trans [S, K] / accept [S, W]`` or banked ``[NB, S, K] / [NB, S, W]``.
    """
    s_axis = trans.ndim - 2
    S = trans.shape[s_axis]
    pad = (-S) % n_shards
    if pad == 0:
        return trans, accept
    widths_t = [(0, 0)] * trans.ndim
    widths_t[s_axis] = (0, pad)
    widths_a = [(0, 0)] * accept.ndim
    widths_a[s_axis] = (0, pad)
    return (np.pad(trans, widths_t), np.pad(accept, widths_a))


def _local_scan(trans_l, byteclass, start, accept_l, data, lengths,
                state_axis: str):
    """shard_map body: trans_l/accept_l hold this device's state slice."""
    S_loc, K = trans_l.shape
    idx = lax.axis_index(state_axis)
    offset = (idx * S_loc).astype(jnp.int32)
    cls = byteclass[data.astype(jnp.int32)]          # [B, L]
    B, L = data.shape
    trans_f = trans_l.astype(jnp.float32)

    def step(states, inputs):
        c_t, t = inputs
        # partial one-hot: rows are zero when the state is off-shard
        oh = jax.nn.one_hot(states - offset, S_loc,
                            dtype=jnp.float32)       # [B, S_loc]
        part = jnp.matmul(oh, trans_f,
                          precision=lax.Precision.HIGHEST)  # [B, K]
        # exact: 1 nonzero term. Ledger-routed: THE collective-per-
        # scanned-byte that makes TP a fallback lane, now on record
        rows = collectives.psum(part, state_axis, site="tp.scan_step")
        nxt = jnp.take_along_axis(
            rows, c_t[:, None].astype(jnp.int32), axis=1
        )[:, 0].astype(jnp.int32)
        return jnp.where(t < lengths, nxt, states), None

    init = jnp.full((B,), start, dtype=jnp.int32)
    ts = jnp.arange(L, dtype=jnp.int32)
    # the scan body traces ONCE but executes L times per block — the
    # scaled() context makes the ledger's count per block honest
    with collectives.LEDGER.scaled(int(L)):
        finals, _ = lax.scan(step, init, (cls.T, ts))    # [B]

    # accept words, state-sharded: psum of byte-plane matmuls
    oh_f = jax.nn.one_hot(finals - offset, S_loc, dtype=jnp.float32)
    W = accept_l.shape[1]
    out = jnp.zeros((B, W), dtype=jnp.uint32)
    for shift in (0, 8, 16, 24):
        plane = ((accept_l >> shift) & jnp.uint32(0xFF)).astype(jnp.float32)
        part = jnp.matmul(oh_f, plane, precision=lax.Precision.HIGHEST)
        vals = collectives.psum(part, state_axis,
                                site="tp.accept_plane").astype(jnp.uint32)
        out = out | (vals << shift)
    return finals, out


@functools.lru_cache(maxsize=None)
def _tp_step(mesh: Mesh, state_axis: str):
    """Cached shard_map wrapper per (mesh, axis). Building the wrapper
    inside :func:`dfa_scan_tp` made every call a fresh closure — a
    jit-cache miss and a full re-trace per batch (found by ctlint
    recompile-hazard); byteclass/start ride as replicated args so the
    wrapped callable itself is invariant."""

    def wrapped(trans, byteclass, start, accept, data, lengths):
        return _local_scan(trans, byteclass, start, accept, data,
                           lengths, state_axis)

    return shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(state_axis, None), P(), P(),
                  P(state_axis, None), P(None, None), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )


def dfa_scan_tp(
    mesh: Mesh,
    trans: jax.Array,       # [S, K] int32 — S divisible by mesh[state_axis]
    byteclass: jax.Array,   # [256] int32
    start,                  # scalar int32
    accept: jax.Array,      # [S, W] uint32
    data: jax.Array,        # [B, L] uint8
    lengths: jax.Array,     # [B] int32
    state_axis: str = "state",
) -> Tuple[jax.Array, jax.Array]:
    """State-axis-sharded DFA scan → (finals [B], accept words [B, W])."""
    _check_state_count(trans.shape[0])
    fn = _tp_step(mesh, state_axis)
    return fn(trans, byteclass, jnp.asarray(start, jnp.int32), accept,
              data, lengths)


@functools.lru_cache(maxsize=None)
def _tp_banked_step(mesh: Mesh, state_axis: str):
    """Cached banked-TP wrapper per (mesh, axis) — same per-call
    re-trace fix as :func:`_tp_step`, with byteclass as a replicated
    arg instead of a closure."""

    def local(trans_l, byteclass, starts, accept_l, data, lengths):
        def one_bank(t, a, s, bc):
            _, words = _local_scan(t, bc, s, a, data, lengths,
                                   state_axis)
            return words
        words = jax.vmap(one_bank)(trans_l, accept_l, starts,
                                   byteclass)        # [NB, B, W]
        return jnp.transpose(words, (1, 0, 2))       # [B, NB, W]

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(None, state_axis, None), P(),
                  P(), P(None, state_axis, None), P(None, None), P()),
        out_specs=P(),
        check_vma=False,
    )


def dfa_scan_banked_tp(
    mesh: Mesh,
    trans: jax.Array,       # [NB, S, K] int32
    byteclass: jax.Array,   # [NB, 256] int32
    start: jax.Array,       # [NB] int32
    accept: jax.Array,      # [NB, S, W] uint32
    data: jax.Array,        # [B, L]
    lengths: jax.Array,     # [B]
    state_axis: str = "state",
) -> jax.Array:
    """All banks, state-axis TP → accept words ``[B, NB, W]`` uint32
    (same contract as ``dfa_kernel.dfa_scan_banked``)."""
    _check_state_count(trans.shape[1])
    fn = _tp_banked_step(mesh, state_axis)
    return fn(trans, byteclass, start, accept, data, lengths)
