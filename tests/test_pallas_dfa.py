"""Pallas DFA kernel ≡ XLA gather scan (interpret mode on CPU, asked
for explicitly: the engine never infers it from the backend).

The kernel's contract (engine/pallas_dfa.py): identical final states /
accept words to the gather path for any bank with ≤128 states.
"""

import numpy as np
import pytest

from cilium_tpu.engine import pallas_dfa
from cilium_tpu.engine.dfa_kernel import dfa_scan_banked
from cilium_tpu.policy.compiler.dfa import compile_patterns


def _random_banked(rng, nb, s, k, b, l):
    trans = rng.integers(0, s, (nb, s, k)).astype(np.int32)
    byteclass = rng.integers(0, k, (nb, 256)).astype(np.int32)
    start = rng.integers(0, s, (nb,)).astype(np.int32)
    accept = rng.integers(0, 2, (nb, s, 1)).astype(np.uint32)
    data = rng.integers(0, 256, (b, l)).astype(np.uint8)
    lengths = rng.integers(0, l + 1, (b,)).astype(np.int32)
    return trans, byteclass, start, accept, data, lengths


@pytest.mark.parametrize("nb,s,k,b,l", [
    (1, 2, 1, 7, 4),          # degenerate empty-matcher shape
    (3, 17, 5, 50, 12),
    (2, 128, 31, 40, 9),      # full state budget
])
def test_pallas_finals_match_gather(nb, s, k, b, l):
    rng = np.random.default_rng(nb * 1000 + s)
    trans, byteclass, start, accept, data, lengths = _random_banked(
        rng, nb, s, k, b, l)
    want = dfa_scan_banked(trans, byteclass, start, accept, data, lengths,
                           impl="gather")
    got = dfa_scan_banked(trans, byteclass, start, accept, data, lengths,
                          impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_on_compiled_patterns():
    pats = [r"/api/v[0-9]+/users", r"/health", r"GET|POST",
            r"[a-z]+\.example\.com", r"/static/.*\.js"]
    banked = compile_patterns(pats, bank_size=2, max_states=128)
    arrs = banked.stacked()
    strings = [b"/api/v1/users", b"/health", b"GET", b"POST",
               b"foo.example.com", b"/static/app.js", b"/nope",
               b"x" * 40, b""]
    L = 48
    data = np.zeros((len(strings), L), dtype=np.uint8)
    lengths = np.zeros(len(strings), dtype=np.int32)
    for i, s in enumerate(strings):
        data[i, :len(s)] = np.frombuffer(s, dtype=np.uint8)
        lengths[i] = len(s)
    want = dfa_scan_banked(arrs["trans"], arrs["byteclass"], arrs["start"],
                           arrs["accept"], data, lengths, impl="gather")
    got = dfa_scan_banked(arrs["trans"], arrs["byteclass"], arrs["start"],
                          arrs["accept"], data, lengths, impl="pallas",
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_rejects_oversized_bank():
    with pytest.raises(ValueError):
        pallas_dfa.dfa_finals_pallas(
            np.zeros((1, 200, 4), np.int32), np.zeros((1, 256), np.int32),
            np.zeros((1,), np.int32), np.zeros((4, 8), np.uint8),
            np.zeros((4,), np.int32), interpret=True)


def test_pallas_refuses_large_banks_in_banked_entry():
    # the banked entry raises when S > 128: an explicit pallas pick is
    # never silently swapped for the data-dependent gather
    rng = np.random.default_rng(7)
    trans, byteclass, start, accept, data, lengths = _random_banked(
        rng, 2, 200, 6, 16, 8)
    with pytest.raises(ValueError, match="pallas"):
        dfa_scan_banked(trans, byteclass, start, accept, data, lengths,
                        impl="pallas", interpret=True)
