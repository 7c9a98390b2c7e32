"""Pallas TPU kernel for the banked bitset-NFA byte-scan.

The MXU-resident face of ``engine/nfa_kernel.py``: the per-byte
position advance

    D' = ((Followᵀ · D) > 0) ⊙ (ClassAccept · one_hot(class))

is two matmuls per byte — the block-structured follow advance and the
class-acceptance plane select — with the position bitset ``D`` living
as a ``[P ≤ 128, TILE]`` tile in VMEM for the whole byte loop of its
grid cell. Rules-as-lanes: every rule's positions ride the same tile,
so one MXU pass advances the whole bank. Like ``engine/pallas_dfa.py``
this is data-oblivious (fixed shapes, no data-dependent gathers) and
exact: all operands are 0/1, products accumulate counts ≤ 128 in f32
(``preferred_element_type`` pinned), thresholding recovers the OR.

Padding bytes use a *hold class* (index ``KP-1``): the host-side
byte→class lookup writes the hold class wherever t ≥ length, and the
kernel carries the bitset through unchanged on those lanes — no
length input and no masked loads in the hot loop. Zero-length strings
come out as the (frozen) start set; the caller's accept extraction
overrides them with the empty-string accept words, exactly like the
XLA formulation.

Constraints: positions per bank ≤ 128 (one MXU tile —
``nfa_kernel.MAX_POSITIONS``). Grid: (bank, batch-tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from cilium_tpu.engine.nfa_kernel import MAX_POSITIONS

TILE = 1024     # flows per grid cell (lane axis: 8×128 tiles)


def _nfa_kernel(cls_ref, follow_t_ref, acc_ref, start_ref, out_ref):
    """One (bank, batch-tile) cell: scan L bytes, emit final bitsets.

    cls_ref      [1, L, TILE]   int32  byte classes (KP-1 = hold/pad)
    follow_t_ref [1, PP, PP]    bf16   transposed ε-closed follow
    acc_ref      [1, KP, PP]    bf16   class-acceptance plane (class-major
                                       so the lane axis stays 128-wide)
    start_ref    [1, PP, 128]   f32    start bitset in column 0
    out_ref      [1, 1, PP, TILE] f32  final position bitsets (0/1)
    """
    _, L, TILE_ = cls_ref.shape
    _, KP, PP = acc_ref.shape
    follow_t = follow_t_ref[0]                               # [PP, PP]
    acc = acc_ref[0]                                         # [KP, PP]
    start = start_ref[0, :, 0:1]                             # [PP, 1]
    iota_k = lax.broadcasted_iota(jnp.int32, (KP, TILE_), 0)

    def masks(t):
        c = cls_ref[0, t]                                    # [TILE]
        oh_c = (iota_k == c[None, :]).astype(jnp.bfloat16)   # [KP, TILE]
        # contract the class axis directly — no in-kernel transpose
        am = lax.dot_general(acc, oh_c, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        hold = oh_c[KP - 1].astype(jnp.float32)              # [TILE]
        return am, hold

    am0, hold0 = masks(0)
    v0 = jnp.broadcast_to(start, (PP, TILE_)).astype(jnp.float32)
    v = jnp.where(hold0[None, :] > 0, v0, v0 * am0)

    def step(t, v):
        am, hold = masks(t)
        pre = jnp.dot(follow_t, v.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)    # [PP, TILE]
        nxt = (pre > 0).astype(jnp.float32) * am
        return jnp.where(hold[None, :] > 0, v, nxt)

    v = lax.fori_loop(1, L, step, v)
    out_ref[0, 0] = v


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def nfa_finals_pallas(
    follow: jax.Array,      # [NB, P, P] f32, P ≤ 128
    acc_cls: jax.Array,     # [NB, P, K] f32
    byteclass: jax.Array,   # [NB, 256] int32
    start: jax.Array,       # [NB, P] f32
    data: jax.Array,        # [B, L] uint8/int32
    lengths: jax.Array,     # [B] int32
    interpret: bool = False,
    tile: int = TILE,
) -> jax.Array:
    """Final position bitsets for every (bank, flow) → [NB, B, P] f32.

    Zero-length flows come out as the frozen start set; callers mask
    them with the empty-string accept words (``nfa_kernel._accept_of``
    does exactly that)."""
    NB, P, K = acc_cls.shape
    if P > MAX_POSITIONS:
        raise ValueError(
            f"pallas NFA kernel needs ≤{MAX_POSITIONS} positions/bank, "
            f"got {P} (compile with a smaller bank_size)")
    B, L = data.shape
    PP = MAX_POSITIONS
    KP = max(8, -(-(K + 1) // 8) * 8)
    HOLD = KP - 1
    NT = max(1, -(-B // tile))
    BP = NT * tile

    follow_p = jnp.zeros((NB, PP, PP), jnp.float32) \
        .at[:, :P, :P].set(follow)
    follow_t = jnp.transpose(follow_p, (0, 2, 1)).astype(jnp.bfloat16)
    acc_p = jnp.zeros((NB, KP, PP), jnp.bfloat16) \
        .at[:, :K, :P].set(
            jnp.transpose(acc_cls, (0, 2, 1)).astype(jnp.bfloat16))
    start_p = jnp.zeros((NB, PP, 128), jnp.float32) \
        .at[:, :P, 0].set(start)

    # byte → class outside the kernel (256-entry table, bounded
    # entropy); padding positions get the hold class
    cls = jax.vmap(lambda bc: bc[data.astype(jnp.int32)])(byteclass)
    pad_pos = jnp.arange(L, dtype=jnp.int32)[None, :] >= lengths[:, None]
    cls = jnp.where(pad_pos[None, :, :], HOLD, cls)          # [NB, B, L]
    cls = jnp.transpose(cls, (0, 2, 1))                      # [NB, L, B]
    cls = jnp.pad(cls, ((0, 0), (0, 0), (0, BP - B)),
                  constant_values=HOLD)

    finals = pl.pallas_call(
        _nfa_kernel,
        grid=(NB, NT),
        in_specs=[
            pl.BlockSpec((1, L, tile), lambda b, t: (b, 0, t)),
            pl.BlockSpec((1, PP, PP), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, KP, PP), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, PP, 128), lambda b, t: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, PP, tile),
                               lambda b, t: (b, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((NB, NT, PP, BP // NT),
                                       jnp.float32),
        interpret=interpret,
    )(cls, follow_t, acc_p, start_p)
    finals = jnp.transpose(finals, (0, 1, 3, 2)).reshape(NB, BP, PP)
    return finals[:, :B, :P]
