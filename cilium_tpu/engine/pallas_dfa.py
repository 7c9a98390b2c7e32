"""Pallas TPU kernel for the banked DFA byte-scan.

Why a hand-written kernel: the MXU matmul step's cost is shape-only —
it gives the RE2-style linear-time, *input-independent* timing
guarantee the reference relies on (SURVEY.md §2.2), which matters for
deployments where verdict latency must not leak rule or payload
structure. It is NOT the default throughput path: it pays K×S MACs
per lookup where "gather" pays one lookup, so "gather" is the default
and this kernel is opt-in via CILIUM_TPU_DFA_IMPL=pallas.

Layout: flows ride the lane axis (TILE=1024 lanes), the state axis
rides sublanes, and each step is

    rows = transᵀ @ onehot(state)        # [KP,SP] @ [SP,TILE] on MXU
    next = Σ_k rows ⊙ onehot(class)      # VPU column select
    s_oh = (iota_S == next)              # back to one-hot

One-hot columns have a single nonzero and all table values are state
ids < 128, so bf16 operands with f32 accumulation are exact.

Padding-byte handling uses an *identity class*: the table gets one extra
class column with trans[s, K] = s, and the host-side byte→class lookup
writes class K wherever t ≥ length — the scan then carries the state
through padding with no mask input and no `where` in the hot loop.

Constraints: per-bank state count S ≤ 128 (one MXU tile; compile with a
smaller ``bank_size`` to stay under — the banked entry point falls back
to the XLA gather path otherwise). The byte→class lookup stays an XLA
gather outside the kernel: its table is 256 entries (bounded entropy),
so it has no adversarial regime.

Grid: (bank, batch-tile); the transition tile stays resident in VMEM for
the whole L-step byte loop of its grid cell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 1024         # flows per grid cell (lane axis: 8×128 tiles)
MAX_STATES = 128    # one MXU tile; also keeps bf16 state ids exact


def _scan_kernel(start_ref, cls_ref, trans_ref, out_ref):
    """One (bank, batch-tile) cell: scan L bytes, emit final states.

    start_ref [NB]          int32  bank start states (scalar prefetch)
    cls_ref   [1, L, TILE]  int32  byte classes (class KP-pad = identity)
    trans_ref [1, KP, SP]   bf16   transposed transition table
    out_ref   [1, 1, 8, 128] int32 final states
    """
    _, L, _ = cls_ref.shape
    _, KP, SP = trans_ref.shape
    trans_t = trans_ref[0]                                   # [KP, SP]
    start = start_ref[pl.program_id(0)]
    iota_k = lax.broadcasted_iota(jnp.int32, (KP, TILE), 0)
    iota_s = lax.broadcasted_iota(jnp.int32, (SP, TILE), 0)
    s_oh = (iota_s == start).astype(jnp.bfloat16)            # [SP, TILE]

    def step(t, s_oh):
        c = cls_ref[0, t]                                    # [TILE]
        oh_c = (iota_k == c[None, :]).astype(jnp.float32)    # [KP, TILE]
        rows = jnp.dot(trans_t, s_oh,
                       preferred_element_type=jnp.float32)   # [KP, TILE]
        nxt = jnp.sum(rows * oh_c, axis=0).astype(jnp.int32)
        return (iota_s == nxt[None, :]).astype(jnp.bfloat16)

    s_oh = lax.fori_loop(0, L, step, s_oh)
    final = jnp.sum(s_oh.astype(jnp.float32) * iota_s.astype(jnp.float32),
                    axis=0).astype(jnp.int32)                # [TILE]
    out_ref[0, 0] = final.reshape(8, 128)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dfa_finals_pallas(
    trans: jax.Array,       # [NB, S, K] int32, S ≤ 128
    byteclass: jax.Array,   # [NB, 256] int32
    start: jax.Array,       # [NB] int32
    data: jax.Array,        # [B, L] uint8/int32
    lengths: jax.Array,     # [B] int32
    interpret: bool = False,
) -> jax.Array:
    """Final DFA states for every (bank, flow) → [NB, B] int32."""
    NB, S, K = trans.shape
    if S > MAX_STATES:
        raise ValueError(
            f"pallas DFA kernel needs ≤{MAX_STATES} states/bank, got {S} "
            f"(compile with a smaller bank_size)")
    B, L = data.shape
    SP = MAX_STATES
    KEEP = K                                   # identity-class index
    KP = max(8, -(-(K + 1) // 8) * 8)
    NT = max(1, -(-B // TILE))
    BP = NT * TILE

    trans_p = jnp.zeros((NB, SP, KP), jnp.int32).at[:, :S, :K].set(trans)
    ident = jnp.broadcast_to(jnp.arange(SP, dtype=jnp.int32)[None, :],
                             (NB, SP))
    trans_p = trans_p.at[:, :, KEEP].set(ident)
    trans_t = jnp.transpose(trans_p, (0, 2, 1)).astype(jnp.bfloat16)

    # byte → class outside the kernel (256-entry table, bounded entropy);
    # padding positions get the identity class
    cls = jax.vmap(lambda bc: bc[data.astype(jnp.int32)])(byteclass)
    pad_pos = jnp.arange(L, dtype=jnp.int32)[None, :] >= lengths[:, None]
    cls = jnp.where(pad_pos[None, :, :], KEEP, cls)          # [NB, B, L]
    cls = jnp.transpose(cls, (0, 2, 1))                      # [NB, L, B]
    cls = jnp.pad(cls, ((0, 0), (0, 0), (0, BP - B)),
                  constant_values=KEEP)

    finals = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(NB, NT),
            in_specs=[
                pl.BlockSpec((1, L, TILE), lambda b, t, _s: (b, 0, t)),
                pl.BlockSpec((1, KP, SP), lambda b, t, _s: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 8, 128),
                                   lambda b, t, _s: (b, t, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((NB, NT, 8, 128), jnp.int32),
        interpret=interpret,
    )(start.astype(jnp.int32), cls, trans_t)
    return finals.reshape(NB, BP)[:, :B]


def pallas_supported(trans_shape) -> bool:
    """True when the banked table fits the kernel's state budget."""
    return trans_shape[1] <= MAX_STATES
