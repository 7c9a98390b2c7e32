"""Pallas TPU kernel for the banked DFA byte-scan: one MXU step a byte.

Why a hand-written kernel: the dense arm's scalar gather
(``dfa_kernel.dfa_scan``) pays a per-index cost on the TPU that no bank
shape amortizes (~8.8 ns a lookup on a v5e, whatever the state count),
while this kernel's step is a dense contraction whose cost is
shape-only (~2.0 ns a lookup at 2,048 states, TILE 1024; PERF.md §6).
That buys two things:

* throughput on wide banks: under the default pick
  ``dfa_kernel.pick_arm`` sends here, on the TPU, every bank stack of
  more than 128 padded states over a batch that fills a tile — the
  capture string-table scan of the 1k-rule path stack (7 banks × 2,048
  states);
* the RE2-style linear-time, *input-independent* timing guarantee the
  reference relies on (SURVEY.md §2.2), for deployments where verdict
  latency must not leak rule or payload structure: the explicit
  ``CILIUM_TPU_DFA_IMPL=pallas`` pick runs every bank here. Nothing in
  the step gathers: the byte → class lookup is a contraction too.

Layout: flows ride the lane axis (TILE lanes), the state and class
axes ride sublanes, and each step is

    rows = planes @ onehot(state)        # [2·KP,SP] @ [SP,TILE] on MXU
    pick = cmap @ onehot(byte)           # [KP,256] @ [256,TILE] on MXU
    hi, lo = Σ_k rows ⊙ pick             # VPU column select, per plane
    next = hi·64 + lo  (held where t ≥ length)

**Exact hi/lo planes.** Each transition id ``t`` is split into two
planes, ``t >> 6`` and ``t & 63``, stacked as one ``[2·KP, SP]`` bf16
matrix. Both are integers ≤ 255 for every ``S ≤ 16,384``, so bf16
holds them exactly; each one-hot column has a single nonzero, so the
f32 accumulation of one product is exact too, and ``pick`` is exactly
0 or 1. The arithmetic is the gather's, not a lower precision of it.

The planes and the class map are derived inside the traced program
from the staged ``trans`` and ``byteclass`` tables (no extra staged
array). Grid: (bank, batch-tile); the bank's planes and class map stay
resident in VMEM for the whole L-step byte loop of its grid cell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: flows per grid cell (lane axis: 8×128 tiles): the fastest of 256,
#: 512 and 1024 at the path piece's shape on a v5e (PERF.md §6)
TILE = 1024
#: padded states a bank may have: the policy compiler's own per-bank
#: budget (``compile_patterns(max_states=8192)``). VMEM holds it at
#: TILE, the planes stay exact, and the kernel still beats the gather
#: there on a v5e (6.1 against 8.8 ns a lookup, PERF.md §6)
MAX_STATES = 8192
#: byte classes the auto rule sends here: the stacked hi/lo planes
#: fill at most one 128-row MXU pass (timed at 28 classes only)
MAX_CLASSES = 64
LO_BITS = 6         # transition id = hi << LO_BITS | lo


def padded_states(s: int) -> int:
    """Sublane extent of a bank's one-hot: ``s`` rounded up to 128."""
    return max(128, -(-s // 128) * 128)


def _scan_kernel(start_ref, data_ref, len_ref, cmap_ref, planes_ref,
                 out_ref):
    """One (bank, batch-tile) cell: scan L bytes, emit final states.

    start_ref  [NB]           int32  bank start states (scalar prefetch)
    data_ref   [L, T]         int32  bytes, shared by every bank
    len_ref    [1, T]         int32  string lengths
    cmap_ref   [1, KP, 256]   bf16   byte → class one-hot of the bank
    planes_ref [1, 2·KP, SP]  bf16   hi planes over lo planes, transposed
    out_ref    [1, 1, T/128, 128] int32 final states
    """
    L, T = data_ref.shape
    _, KP2, SP = planes_ref.shape
    KP = KP2 // 2
    planes = planes_ref[0]                                   # [2KP, SP]
    cmap = cmap_ref[0]                                       # [KP, 256]
    lens = len_ref[...]                                      # [1, T]
    iota_s = lax.broadcasted_iota(jnp.int32, (SP, T), 0)
    iota_v = lax.broadcasted_iota(jnp.int32, (256, T), 0)
    state = jnp.full((1, T), start_ref[pl.program_id(0)], jnp.int32)

    def step(t, state):
        s_oh = (iota_s == state).astype(jnp.bfloat16)        # [SP, T]
        rows = jnp.dot(planes, s_oh,
                       preferred_element_type=jnp.float32)   # [2KP, T]
        v_oh = (iota_v == data_ref[pl.ds(t, 1), :]).astype(jnp.bfloat16)
        pick = jnp.dot(cmap, v_oh,
                       preferred_element_type=jnp.float32)   # [KP, T] 0/1
        hi = jnp.sum(rows[:KP] * pick, axis=0, keepdims=True)
        lo = jnp.sum(rows[KP:] * pick, axis=0, keepdims=True)
        nxt = (hi * (1 << LO_BITS) + lo).astype(jnp.int32)   # [1, T]
        return jnp.where(t < lens, nxt, state)

    state = lax.fori_loop(0, L, step, state)
    out_ref[0, 0] = state.reshape(T // 128, 128)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dfa_finals_pallas(
    trans: jax.Array,       # [NB, S, K] int32, S ≤ MAX_STATES
    byteclass: jax.Array,   # [NB, 256] int32
    start: jax.Array,       # [NB] int32
    data: jax.Array,        # [B, L] uint8/int32
    lengths: jax.Array,     # [B] int32
    interpret: bool = False,
) -> jax.Array:
    """Final DFA states for every (bank, flow) → [NB, B] int32."""
    NB, S, K = trans.shape
    if not pallas_supported(trans.shape):
        raise ValueError(
            f"pallas DFA kernel needs ≤{MAX_STATES} states/bank, got {S} "
            f"(compile with a smaller bank_size)")
    B, L = data.shape
    SP = padded_states(S)
    KP = max(8, -(-K // 8) * 8)
    NT = max(1, -(-B // TILE))
    BP = NT * TILE

    trans_p = jnp.zeros((NB, SP, KP), jnp.int32).at[:, :S, :K].set(trans)
    trans_t = jnp.transpose(trans_p, (0, 2, 1))              # [NB, KP, SP]
    planes = jnp.concatenate(
        [trans_t >> LO_BITS, trans_t & ((1 << LO_BITS) - 1)],
        axis=1).astype(jnp.bfloat16)                         # [NB, 2KP, SP]
    cmap = (byteclass[:, None, :] == jnp.arange(KP)[None, :, None]
            ).astype(jnp.bfloat16)                           # [NB, KP, 256]
    data_t = jnp.pad(jnp.transpose(data.astype(jnp.int32)),
                     ((0, 0), (0, BP - B)))                  # [L, BP]
    lens = jnp.pad(lengths.astype(jnp.int32), (0, BP - B))[None, :]

    finals = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(NB, NT),
            in_specs=[
                pl.BlockSpec((L, TILE), lambda b, t, _s: (0, t)),
                pl.BlockSpec((1, TILE), lambda b, t, _s: (0, t)),
                pl.BlockSpec((1, KP, 256), lambda b, t, _s: (b, 0, 0)),
                pl.BlockSpec((1, 2 * KP, SP), lambda b, t, _s: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, TILE // 128, 128),
                                   lambda b, t, _s: (b, t, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((NB, NT, TILE // 128, 128),
                                       jnp.int32),
        interpret=interpret,
    )(start.astype(jnp.int32), data_t, lens, cmap, planes)
    return finals.reshape(NB, BP)[:, :B]


def pallas_supported(trans_shape) -> bool:
    """True when the banked table fits the kernel's state budget."""
    return padded_states(trans_shape[1]) <= MAX_STATES
