"""Batched DFA byte-scan — the dense-gather L7 automaton kernel.

The TPU replacement for the reference's per-request regex scans
(SURVEY.md §3.4: "per-request × per-rule scan is exactly what the batched
automaton pass replaces"). Design notes:

* The scan is a ``lax.scan`` over byte positions with a ``[batch]``
  state carry; each step is one gather from the flattened transition
  table — sequential in L (string length) but embarrassingly parallel in
  the batch and bank dimensions, which is where the throughput comes
  from (flows ≫ bytes).
* Transition tables are byte-class compressed ``[S, K]`` int32; padding
  bytes are masked with ``where`` so bucketed/padded strings need no
  sentinel symbol.
* Banks are vmapped: ``[n_banks, S, K]`` tables, one shared input batch.
  Banks are also the EP (expert-parallel) shard unit
  (``cilium_tpu.parallel``).
* This is the ``dfa-dense`` arm of the megakernel's per-bank-shape
  autotuner (``engine/megakernel.py``); the ``nfa-bitset``
  rules-as-lanes arm lives in ``engine/nfa_kernel.py``.

Implementation choice is a TRACE-STATIC argument: callers resolve it
once on the host (``resolve_impl()`` reads the env; the engine does it
at staging) and thread it through — nothing here reads the
environment or probes the backend under trace.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def resolve_impl(env=None) -> str:
    """HOST-side step-implementation resolution — call once at
    engine/bank staging and thread the result as a static argument
    (never under trace: flipping the env between traces would
    otherwise be an invisible recompile lever).

    The arms (none has been timed on a locally attached chip yet —
    PERF.md):

    * "gather" — one transition-table lookup per (flow, byte, bank).
      Algorithmically minimal work — the default everywhere.
    * "pallas" — engine/pallas_dfa.py MXU matmul step: data-oblivious
      (RE2-style input-independent timing) but pays K×S MACs per
      lookup; needs ≤128 states/bank. Kept as an option for
      constant-time-guarantee deployments.
    * "onehot" — the matmul formulation in plain XLA (any state
      count); portable reference implementation.

    Pallas kernels compile for the TPU only: on any other backend the
    "pallas" pick resolves to "gather" (tests that want the Pallas
    interpreter call the kernel with ``interpret=True`` themselves).
    """
    import os

    env = os.environ if env is None else env
    pick = env.get("CILIUM_TPU_DFA_IMPL", "")
    if pick == "pallas" and jax.default_backend() != "tpu":
        return "gather"
    if pick in ("gather", "onehot", "pallas"):
        return pick
    return "gather"


def dfa_scan(
    trans: jax.Array,       # [S, K] int32
    byteclass: jax.Array,   # [256] int32
    start: jax.Array,       # scalar int32
    data: jax.Array,        # [B, L] uint8/int32 padded byte strings
    lengths: jax.Array,     # [B] int32
    impl: Optional[str] = None,
) -> jax.Array:
    """Run the DFA over each row of ``data``; returns final states [B].

    ``impl``: "gather" (one gather per step; the default) or "onehot"
    (two f32 matmuls per step — exact for state ids < 2^24,
    MXU-friendly). A trace-static choice; None means "gather".
    """
    impl = impl or "gather"
    if impl == "pallas":
        impl = "gather"  # single-bank path: pallas handled in banked entry
    if impl not in ("gather", "onehot"):
        raise ValueError(f"unknown dfa impl {impl!r}")
    B, L = data.shape
    S, K = trans.shape
    cls = byteclass[data.astype(jnp.int32)]  # [B, L]

    if impl == "gather":
        trans_flat = trans.reshape(-1)      # [S*K]

        def step(states, inputs):
            c_t, t = inputs
            nxt = trans_flat[states * K + c_t]
            states = jnp.where(t < lengths, nxt, states)
            return states, None
    else:
        trans_f32 = trans.astype(jnp.float32)

        def step(states, inputs):
            c_t, t = inputs
            oh_s = jax.nn.one_hot(states, S, dtype=jnp.float32)   # [B,S]
            # HIGHEST: TPU matmuls default to bf16 accumulation, which
            # rounds state ids > 256 — transitions must be exact f32
            rows = jnp.matmul(oh_s, trans_f32,
                              precision=lax.Precision.HIGHEST)    # [B,K]
            oh_c = jax.nn.one_hot(c_t, K, dtype=jnp.float32)      # [B,K]
            nxt = jnp.sum(rows * oh_c, axis=1).astype(jnp.int32)
            states = jnp.where(t < lengths, nxt, states)
            return states, None

    init = jnp.full((B,), start, dtype=jnp.int32)
    ts = jnp.arange(L, dtype=jnp.int32)
    final, _ = lax.scan(step, init, (cls.T, ts))
    return final


def _accept_rows(accept: jax.Array, finals: jax.Array,
                 impl: str) -> jax.Array:
    """accept [S, W] uint32, finals [B] → [B, W] uint32."""
    if impl == "gather":
        return accept[finals]
    # one-hot matmul, exact via byte-planes (each plane value ≤ 255 is
    # exact even in bf16, and each one-hot row has a single nonzero
    # product — but use HIGHEST anyway for uniform guarantees)
    S, W = accept.shape
    oh = jax.nn.one_hot(finals, S, dtype=jnp.float32)         # [B, S]
    out = jnp.zeros((finals.shape[0], W), dtype=jnp.uint32)
    for shift in (0, 8, 16, 24):
        plane = ((accept >> shift) & jnp.uint32(0xFF)).astype(jnp.float32)
        vals = jnp.matmul(oh, plane,
                          precision=lax.Precision.HIGHEST
                          ).astype(jnp.uint32)                 # [B, W]
        out = out | (vals << shift)
    return out


def dfa_finals_banked(
    trans: jax.Array,       # [NB, S, K] int32
    byteclass: jax.Array,   # [NB, 256] int32
    start: jax.Array,       # [NB] int32
    data: jax.Array,        # [B, L]
    lengths: jax.Array,     # [B]
    impl: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """Final DFA states for every (bank, flow) → [NB, B] int32; the
    accept-table reads layer on top (``dfa_scan_banked``)."""
    impl = impl or "gather"
    if impl == "pallas":
        from cilium_tpu.engine import pallas_dfa

        # pallas is an explicit opt-in for its input-independent
        # timing guarantee: a bank it cannot hold is an error, never a
        # silent swap to the data-dependent gather
        # ctlint: disable=recompile-hazard  # bank-shape check is a trace-time static, by design
        if not pallas_dfa.pallas_supported(trans.shape):
            raise ValueError(
                f"CILIUM_TPU_DFA_IMPL=pallas requested but a bank has "
                f"{trans.shape[1]} states (limit "
                f"{pallas_dfa.MAX_STATES}); compile with a smaller "
                f"bank_size or drop the pallas pick")
        return pallas_dfa.dfa_finals_pallas(
            trans, byteclass, start, data, lengths,
            interpret=interpret)
    return jax.vmap(
        lambda tr, bc, st: dfa_scan(tr, bc, st, data, lengths, impl=impl)
    )(trans, byteclass, start)              # [NB, B]


def dfa_scan_banked(
    trans: jax.Array,       # [NB, S, K] int32
    byteclass: jax.Array,   # [NB, 256] int32
    start: jax.Array,       # [NB] int32
    accept: jax.Array,      # [NB, S, W] uint32
    data: jax.Array,        # [B, L]
    lengths: jax.Array,     # [B]
    impl: Optional[str] = None,
    interpret: bool = False,
    extra_accept: Optional[jax.Array] = None,
):
    """All banks over one batch → accept words ``[B, NB, W]`` uint32.

    ``impl``/``interpret`` are trace-static (resolve on the host via
    :func:`resolve_impl`; None = "gather"; ``interpret`` runs a Pallas
    pick in the interpreter, for CPU tests). ``extra_accept``
    ([NB, S, Wg]) reads a second accept plane off the same final
    states — the megakernel's group-accept tables (one extra gather,
    no second scan) — and makes the return a ``(words, extra_words)``
    tuple."""
    impl = impl or "gather"
    finals = dfa_finals_banked(trans, byteclass, start, data, lengths,
                               impl=impl, interpret=interpret)
    word_impl = "gather" if impl == "pallas" else impl

    def extract(acc):
        words = jax.vmap(
            lambda a, fs: _accept_rows(a, fs, word_impl)
        )(acc, finals)                      # [NB, B, W]
        return jnp.transpose(words, (1, 0, 2))  # [B, NB, W]

    words = extract(accept)
    if extra_accept is None:
        return words
    return words, extract(extra_accept)


def match_bits(words: jax.Array) -> jax.Array:
    """Flatten ``[B, NB, W]`` accept words to ``[B, NB*W]`` — the global
    lane space used by rule bitmap masks (dfa.BankedDFA.stacked lane_of)."""
    B = words.shape[0]
    return words.reshape(B, -1)


def any_lane_match(words: jax.Array, mask: jax.Array) -> jax.Array:
    """``words [B, NW]`` uint32 vs ``mask [NW]`` (or broadcastable):
    True where any masked lane bit is set."""
    return jnp.any((words & mask) != 0, axis=-1)
