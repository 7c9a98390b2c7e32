"""Batched DFA byte-scan — the dense-gather L7 automaton kernel, and the
one rule that picks every scan arm.

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

**Which arm scans a bank stack** is decided by :func:`pick_arm` alone,
from what the code observes: the backend, the stack's padded states
and byte classes, the rows, and whether the stack's bitset-NFA tensors
could be built. Engine staging asks it once per field (NFA or dense,
``engine/megakernel.plan_for_engine``); the trace asks it per dense
stack from static shapes (:func:`scan_arm`: the MXU kernel of
``engine/pallas_dfa.py`` or this module's gather). The bitset-NFA arm
lives in ``engine/nfa_kernel.py``. Every arm gives the same words.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


def pick_arm(backend: str, states: int, classes: int, rows: int,
             nfa_fits: Callable[[], bool] = lambda: False) -> str:
    """The scan arm of a ``[NB, states, classes]`` bank stack over
    ``rows`` rows on ``backend`` — the one rule:

    * "nfa" — the bitset-NFA arm (``engine/nfa_kernel.py``, its Pallas
      kernel on the TPU): on the TPU, for a stack of more than 128
      padded DFA states whose NFA tensors could be built
      (``nfa_fits()``: positions within ``nfa_kernel.MAX_POSITIONS``
      and no quarantined bank). ``nfa_fits`` is asked only in that
      regime, since the build costs host time; the default says no,
      as at trace time, where the stack is a dense one. Not re-timed
      against the MXU kernel since that took banks past 128 states.
    * "pallas" — the MXU kernel (``engine/pallas_dfa.py``): on the TPU,
      more than 128 padded states (up to its ``MAX_STATES``), at most
      ``MAX_CLASSES`` byte classes, over a batch that fills at least
      one kernel tile. 2.0 ns a lookup on a v5e at the replay cell's
      path piece (7 banks × 2,048 states × 28 classes, 8,192 rows ×
      32 bytes), 0.6 ns at 128 states, 6.1 ns at 8,192 (PERF.md §6).
    * "gather" — everywhere else: one transition-table lookup per
      (flow, byte, bank), 8.8 ns a lookup on a v5e at every state
      count. The smaller banks and sub-tile batches (the
      method/host/hdr/dns stacks, the served path's 256-row chunks)
      keep it.

    Pallas kernels compile for the TPU only, so off it every stack
    takes the gather (tests that want the Pallas interpreter call the
    kernels with ``interpret=True`` themselves)."""
    if backend != "tpu":
        return "gather"
    from cilium_tpu.engine import pallas_dfa

    sp = pallas_dfa.padded_states(states)
    # ctlint: disable=recompile-hazard  # the arm is a trace-time static of the bank shape, by design: one compile per shape either way
    if sp <= 128:
        return "gather"
    if nfa_fits():
        return "nfa"
    # ctlint: disable=recompile-hazard  # the arm is a trace-time static of the bank shape, by design: one compile per shape either way
    if (sp <= pallas_dfa.MAX_STATES
            and classes <= pallas_dfa.MAX_CLASSES
            and rows >= pallas_dfa.TILE):
        return "pallas"
    return "gather"


def scan_arm(impl: Optional[str], trans_shape, rows: int) -> str:
    """The arm :func:`dfa_finals_banked` runs for a dense ``[NB, S, K]``
    bank stack over ``rows`` rows: :func:`pick_arm` on the default
    backend, or ``impl`` where a test names the arm. Shapes are
    static, so this is a host-side decision even under trace."""
    if impl is not None:
        return impl
    _nb, states, classes = trans_shape
    return pick_arm(jax.default_backend(), states, classes, rows)


def dfa_scan(
    trans: jax.Array,       # [S, K] int32
    byteclass: jax.Array,   # [256] int32
    start: jax.Array,       # scalar int32
    data: jax.Array,        # [B, L] uint8/int32 padded byte strings
    lengths: jax.Array,     # [B] int32
) -> jax.Array:
    """Run the DFA over each row of ``data`` with one gather a step;
    returns final states [B]."""
    B, L = data.shape
    S, K = trans.shape
    cls = byteclass[data.astype(jnp.int32)]  # [B, L]
    trans_flat = trans.reshape(-1)      # [S*K]

    def step(states, inputs):
        c_t, t = inputs
        nxt = trans_flat[states * K + c_t]
        states = jnp.where(t < lengths, nxt, states)
        return states, None

    init = jnp.full((B,), start, dtype=jnp.int32)
    ts = jnp.arange(L, dtype=jnp.int32)
    final, _ = lax.scan(step, init, (cls.T, ts))
    return final


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
    accept-table reads layer on top (``dfa_scan_banked``). The arm is
    :func:`scan_arm`'s: the rule's, or ``impl`` ("gather"/"pallas")
    where a test names it."""
    arm = scan_arm(impl, trans.shape, data.shape[0])
    if arm == "pallas":
        from cilium_tpu.engine import pallas_dfa

        return pallas_dfa.dfa_finals_pallas(
            trans, byteclass, start, data, lengths,
            interpret=interpret)
    return jax.vmap(
        lambda tr, bc, st: dfa_scan(tr, bc, st, data, lengths)
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

    ``impl``/``interpret`` are trace-static: ``impl`` None takes the
    rule's arm (:func:`scan_arm`), and ``interpret`` runs a Pallas
    arm in the interpreter, for CPU tests. ``extra_accept``
    ([NB, S, Wg]) reads a second accept plane off the same final
    states — the megakernel's group-accept tables (one extra gather,
    no second scan) — and makes the return a ``(words, extra_words)``
    tuple."""
    finals = dfa_finals_banked(trans, byteclass, start, data, lengths,
                               impl=impl, interpret=interpret)

    def extract(acc):
        words = jax.vmap(lambda a, fs: a[fs])(acc, finals)  # [NB, B, W]
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
