"""Bitset-NFA byte-scan — the "rules-as-lanes" automaton arm.

The Hyperflex-style (PAPERS.md) alternative to the dense-gather DFA of
``engine/dfa_kernel.py``: instead of subset-constructing a union DFA
and gathering one next-state id per byte, the scan carries a **bitset
over the bank's NFA positions** (a position = one byte-consuming edge
of the Thompson NFA — the Glushkov position automaton derived through
the existing ``policy/compiler/nfa.py`` construction) and advances ALL
positions of ALL rules in the bank at once:

    D' = ((D · Follow) > 0) ⊙ ClassAccept[byte]

``Follow`` is the ε-closed position-to-position successor matrix —
**block-structured by rule** (positions of different patterns never
follow each other; the only cross-block rows are the shared start), so
the matmul is the block-diagonal one-hot advance of every rule lane in
one MXU pass. Acceptance is a second matmul: rule r matched iff D
intersects r's accept positions.

Why it earns a place next to the dense DFA:

* **No subset construction** — the position count is the pattern
  length sum, immune to the DFA state explosion that alternation-heavy
  banks hit (the ``max_dfa_states`` overflow/halving path). A bank
  whose DFA blows past the 128-state Pallas budget can still fit 128
  positions.
* **Data-oblivious** — two fixed-shape matmuls per byte, the RE2-style
  input-independent timing guarantee, on the MXU instead of the VPU.
* On CPU backends the matmul costs more than the gather, so the arm
  serves only on the TPU, where ``dfa_kernel.pick_arm`` gives it the
  banks whose DFA passed 128 states while their positions fit one
  tile.

Exactness: all matrices are 0/1; products accumulate counts ≤ P ≤ 128,
exact in f32 (``preferred_element_type`` pinned); thresholding ``> 0``
recovers the boolean OR. Verified bit-equal to ``dfa_scan_banked``
over the golden corpus and hypothesis-random banks
(tests/test_megakernel.py).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cilium_tpu.policy.compiler import regex_parser as rp
from cilium_tpu.policy.compiler.dfa import _byte_classes
from cilium_tpu.policy.compiler.nfa import build_nfa, eps_closure

#: position budget per bank: one MXU tile — the Pallas kernel's hard
#: cap, and the arm's eligibility bound on every backend (past it
#: the follow matmul outgrows its tile anyway)
MAX_POSITIONS = 128


@dataclasses.dataclass
class NFABank:
    """One bank's position-automaton tensors (host numpy)."""

    follow: np.ndarray      # [P, P] f32 0/1 ε-closed successor matrix
    acc_cls: np.ndarray     # [P, K] f32 0/1 class acceptance per position
    byteclass: np.ndarray   # [256] int32 byte → class
    start: np.ndarray       # [P] f32 0/1 positions live before byte 0
    accept: np.ndarray      # [P, W] uint32 rule bitmaps per position
    empty: np.ndarray       # [W] uint32 rules matching the empty string
    n_patterns: int

    @property
    def n_positions(self) -> int:
        return self.follow.shape[0]


def compile_nfa_bank(patterns: Sequence[str],
                     max_quantifier: int = 64,
                     case_insensitive: bool = False,
                     lanes: Optional[Sequence[int]] = None) -> NFABank:
    """Compile one bank of patterns into position-automaton tensors.

    ``lanes`` maps pattern i to its accept-bit lane (default i) so a
    registry-assembled bank keeps its served lane layout. An empty
    pattern list yields the 0-position dead bank (matches nothing) —
    the bitset-NFA face of a quarantined fail-closed bank."""
    lanes = list(lanes) if lanes is not None else list(range(len(patterns)))
    n_lanes = (max(lanes) + 1) if lanes else 1
    n_words = max(1, (max(n_lanes, 1) + 31) // 32)
    if not patterns:
        return NFABank(
            follow=np.zeros((0, 0), np.float32),
            acc_cls=np.zeros((0, 1), np.float32),
            byteclass=np.zeros(256, np.int32),
            start=np.zeros((0,), np.float32),
            accept=np.zeros((0, n_words), np.uint32),
            empty=np.zeros((n_words,), np.uint32),
            n_patterns=0)
    asts = [rp.parse(p, max_quantifier=max_quantifier,
                     case_insensitive=case_insensitive)
            for p in patterns]
    nfa = build_nfa(asts)
    byteclass, n_classes = _byte_classes(nfa)
    rep = [0] * n_classes
    for b in range(255, -1, -1):
        rep[int(byteclass[b])] = b
    # positions = byte-consuming edges, in deterministic state order
    edges = [(s, m, t) for s in range(nfa.n_states)
             for (m, t) in nfa.edges[s]]
    P = len(edges)
    acc_cls = np.zeros((P, max(1, n_classes)), np.float32)
    for i, (_, m, _) in enumerate(edges):
        for c in range(n_classes):
            if (m >> rep[c]) & 1:
                acc_cls[i, c] = 1.0
    closures = [eps_closure(nfa, [t]) for (_, _, t) in edges]
    start_cl = eps_closure(nfa, [nfa.start])
    follow = np.zeros((P, P), np.float32)
    for i in range(P):
        cl = closures[i]
        for j, (sj, _, _) in enumerate(edges):
            if sj in cl:
                follow[i, j] = 1.0
    start = np.array([1.0 if e[0] in start_cl else 0.0
                      for e in edges], np.float32)
    accept = np.zeros((P, n_words), np.uint32)
    empty = np.zeros((n_words,), np.uint32)

    def set_bit(words, idx):
        lane = lanes[idx]
        words[lane // 32] |= np.uint32(1 << (lane % 32))

    for i in range(P):
        for s in closures[i]:
            if nfa.accepts[s] >= 0:
                set_bit(accept[i], nfa.accepts[s])
    for s in start_cl:
        if nfa.accepts[s] >= 0:
            set_bit(empty, nfa.accepts[s])
    return NFABank(follow=follow, acc_cls=acc_cls, byteclass=byteclass,
                   start=start, accept=accept, empty=empty,
                   n_patterns=len(patterns))


def nfa_supported(banks: Sequence[NFABank]) -> bool:
    """True when every bank fits the position budget."""
    return all(b.n_positions <= MAX_POSITIONS for b in banks)


def stack_nfa_banks(banks: Sequence[NFABank],
                    extra_accept: Optional[Sequence[np.ndarray]] = None
                    ) -> Dict[str, np.ndarray]:
    """Pad + stack banks for the engine (mirror of
    ``BankedDFA.stacked``). ``extra_accept`` (optional, per bank
    ``[P, Wg]``) rides along as the group-accept plane of the factored
    resolve (``engine/megakernel.py``)."""
    NB = len(banks)
    Pm = max([b.n_positions for b in banks] + [1])
    Km = max([b.acc_cls.shape[1] for b in banks] + [1])
    Wm = max([b.accept.shape[1] for b in banks] + [1])
    out = {
        "nfa_follow": np.zeros((NB, Pm, Pm), np.float32),
        "nfa_acc_cls": np.zeros((NB, Pm, Km), np.float32),
        "nfa_byteclass": np.zeros((NB, 256), np.int32),
        "nfa_start": np.zeros((NB, Pm), np.float32),
        "nfa_accept": np.zeros((NB, Pm, Wm), np.uint32),
        "nfa_empty": np.zeros((NB, Wm), np.uint32),
    }
    for i, b in enumerate(banks):
        P, K, W = b.n_positions, b.acc_cls.shape[1], b.accept.shape[1]
        out["nfa_follow"][i, :P, :P] = b.follow
        out["nfa_acc_cls"][i, :P, :K] = b.acc_cls
        out["nfa_byteclass"][i] = b.byteclass
        out["nfa_start"][i, :P] = b.start
        out["nfa_accept"][i, :P, :W] = b.accept
        out["nfa_empty"][i, :W] = b.empty
    if extra_accept is not None:
        Wg = max([g.shape[1] for g in extra_accept] + [1])
        gacc = np.zeros((NB, Pm, Wg), np.uint32)
        for i, g in enumerate(extra_accept):
            gacc[i, :g.shape[0], :g.shape[1]] = g
        out["nfa_gaccept"] = gacc
    return out


def _or_reduce(masked: jax.Array, axis: int) -> jax.Array:
    return jax.lax.reduce(masked, jnp.uint32(0), jax.lax.bitwise_or,
                          (axis,))


def _accept_of(final: jax.Array, accept: jax.Array,
               empty: jax.Array, lengths: jax.Array) -> jax.Array:
    """Live-position bitset [B, P] → accept words [B, W]."""
    hit = final > 0
    words = _or_reduce(
        jnp.where(hit[:, :, None], accept[None, :, :], jnp.uint32(0)), 1)
    return jnp.where((lengths == 0)[:, None], empty[None, :], words)


def nfa_finals(follow: jax.Array, acc_cls: jax.Array,
               byteclass: jax.Array, start: jax.Array,
               data: jax.Array, lengths: jax.Array) -> jax.Array:
    """One bank's scan → final position bitset [B, P] (f32 0/1).

    The hot loop is two ops per byte: the follow matmul (MXU; counts
    are exact in f32) and the class-acceptance mask (a [B] gather into
    the [K, P] acceptance plane — on TPU the Pallas kernel
    (``engine/pallas_nfa.py``) replaces the gather with a one-hot
    matmul so the whole step is MXU-resident)."""
    B, L = data.shape
    cls = byteclass[data.astype(jnp.int32)]               # [B, L]
    acc_t = acc_cls.T                                     # [K, P]
    am0 = acc_t[cls[:, 0]] if L else jnp.zeros_like(start)[None]
    v0 = jnp.where((lengths > 0)[:, None],
                   start[None, :] * am0,
                   jnp.zeros((B, follow.shape[0]), jnp.float32))

    def step(v, inp):
        c_t, t = inp
        pre = jnp.matmul(v, follow,
                         preferred_element_type=jnp.float32)
        nxt = (pre > 0).astype(jnp.float32) * acc_t[c_t]
        return jnp.where((t < lengths)[:, None], nxt, v), None

    ts = jnp.arange(1, L, dtype=jnp.int32)
    final, _ = jax.lax.scan(step, v0, (cls.T[1:], ts))
    return final


def nfa_scan_banked(
    stacked: Dict[str, jax.Array],
    data: jax.Array,        # [B, L] uint8/int32
    lengths: jax.Array,     # [B]
    extra_accept: bool = False,
    interpret: bool = False,
):
    """All banks over one batch → accept words ``[B, NB, W]`` uint32
    (+ group words ``[B, NB, Wg]`` when ``extra_accept`` and the stack
    carries a ``nfa_gaccept`` plane). Same contract as
    ``dfa_scan_banked`` — the two arms are interchangeable per bank
    shape, which is what ``dfa_kernel.pick_arm`` relies on. The
    positions advance in the Pallas kernel on the TPU (the backend
    reading the rule makes) or, with ``interpret``, in its
    interpreter; elsewhere in plain XLA."""
    if interpret or jax.default_backend() == "tpu":
        from cilium_tpu.engine.pallas_nfa import nfa_finals_pallas

        finals = nfa_finals_pallas(
            stacked["nfa_follow"], stacked["nfa_acc_cls"],
            stacked["nfa_byteclass"], stacked["nfa_start"],
            data, lengths, interpret=interpret)      # [NB, B, P]
    else:
        finals = jax.vmap(
            lambda f, a, bc, s: nfa_finals(f, a, bc, s, data, lengths)
        )(stacked["nfa_follow"], stacked["nfa_acc_cls"],
          stacked["nfa_byteclass"], stacked["nfa_start"])
    words = jax.vmap(
        lambda fin, acc, emp: _accept_of(fin, acc, emp, lengths)
    )(finals, stacked["nfa_accept"], stacked["nfa_empty"])
    words = jnp.transpose(words, (1, 0, 2))          # [B, NB, W]
    if not extra_accept:
        return words
    gacc = stacked["nfa_gaccept"]
    gwords = jax.vmap(
        lambda fin, acc: _accept_of(
            fin, acc, jnp.zeros((acc.shape[1],), jnp.uint32), lengths)
    )(finals, gacc)
    return words, jnp.transpose(gwords, (1, 0, 2))


def banks_from_dfa(banked, cfg, case_insensitive: bool = False
                   ) -> Optional[List[NFABank]]:
    """Rebuild each compiled DFA bank's pattern group as an NFA bank,
    preserving lane assignment (``pattern_bank``/``pattern_lane``).
    Returns None when any bank busts the position budget. Banks no
    current pattern references (stale quarantine covers) cannot be
    reconstructed faithfully — callers gate the arm on a
    quarantine-free build (``CompiledPolicy.bank_quarantined``)."""
    per_bank: Dict[int, List[Tuple[int, str]]] = {}
    for i, pat in enumerate(banked.patterns):
        per_bank.setdefault(int(banked.pattern_bank[i]), []).append(
            (int(banked.pattern_lane[i]), pat))
    # cheap pre-flight: positions ≥ literal occurrences, so a bank
    # whose pattern text alone dwarfs the budget can be rejected
    # before paying parse + closure work
    for members in per_bank.values():
        if sum(len(p) for _, p in members) > 16 * MAX_POSITIONS:
            return None
    banks: List[NFABank] = []
    for b in range(banked.n_banks):
        members = sorted(per_bank.get(b, ()))
        bank = compile_nfa_bank(
            [p for _, p in members],
            max_quantifier=cfg.max_quantifier,
            case_insensitive=case_insensitive,
            lanes=[lane for lane, _ in members])
        if bank.n_positions > MAX_POSITIONS:
            return None
        banks.append(bank)
    return banks
