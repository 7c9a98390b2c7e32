"""Pallas DFA kernel ≡ XLA gather scan (interpret mode on CPU, asked
for explicitly: off the TPU the rule never picks the kernel).

The kernel's contract (engine/pallas_dfa.py): identical final states /
accept words to the gather path for any bank of at most
``MAX_STATES`` padded states; state ids past 64 and past 256 exercise
both hi/lo planes. Plus the one rule that picks every scan arm
(``dfa_kernel.pick_arm``).
"""

import numpy as np
import pytest

from cilium_tpu.engine import pallas_dfa
from cilium_tpu.engine.dfa_kernel import (
    dfa_finals_banked,
    dfa_scan_banked,
    pick_arm,
    scan_arm,
)
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
    (2, 128, 31, 40, 9),      # one 128-state sublane tile
    (2, 129, 7, 40, 10),      # one state past it: SP = 256
    (2, 1024, 28, 40, 12),    # ids past 256: both planes carry bits
    (1, 2048, 28, 24, 12),    # the 1k-rule path bank's padded size
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


def test_pallas_on_compiled_patterns_past_one_tile():
    """A compiled bank of more than 128 states (so SP = 256 and ids
    pass 64) through the kernel, strings that walk deep into it."""
    pats = [r".*/api/v[0-9]+/(users|groups|items)/[a-f0-9]{8}",
            r".*/static/[a-z]+/[a-z0-9]{4}\.(js|css|png)",
            r".*token=[a-f0-9]{12}"]
    banked = compile_patterns(pats, bank_size=4, max_states=4096)
    arrs = banked.stacked()
    assert arrs["trans"].shape[1] > 128
    strings = [b"/x/api/v12/users/deadbeef", b"/api/v1/items/0123abcd",
               b"/api/v3/groups/0123abc", b"/static/app/ab12.css",
               b"/static/x/y.png", b"/a?token=0123456789ab", b"",
               b"/static/app/ab12.js?token=0123456789a",
               b"/api/v9/users/deadbeefdeadbeef"]
    L = 40
    data = np.zeros((len(strings), L), dtype=np.uint8)
    lengths = np.zeros(len(strings), dtype=np.int32)
    for i, s in enumerate(strings):
        data[i, :len(s)] = np.frombuffer(s, dtype=np.uint8)
        lengths[i] = len(s)
    args = (arrs["trans"], arrs["byteclass"], arrs["start"])
    want = dfa_finals_banked(*args, data, lengths, impl="gather")
    got = dfa_finals_banked(*args, data, lengths, impl="pallas",
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(want).max()) > 64


def test_pallas_rejects_oversized_bank():
    over = pallas_dfa.MAX_STATES + 1
    with pytest.raises(ValueError):
        pallas_dfa.dfa_finals_pallas(
            np.zeros((1, over, 4), np.int32), np.zeros((1, 256), np.int32),
            np.zeros((1,), np.int32), np.zeros((4, 8), np.uint8),
            np.zeros((4,), np.int32), interpret=True)


def test_pallas_refuses_large_banks_in_banked_entry():
    # the banked entry raises past MAX_STATES: a named pallas arm is
    # never silently swapped for the data-dependent gather
    over = pallas_dfa.MAX_STATES + 1
    trans = np.zeros((2, over, 2), np.int32)
    with pytest.raises(ValueError, match="pallas"):
        dfa_scan_banked(trans, np.zeros((2, 256), np.int32),
                        np.zeros((2,), np.int32),
                        np.zeros((2, over, 1), np.uint32),
                        np.zeros((16, 8), np.uint8),
                        np.zeros((16,), np.int32),
                        impl="pallas", interpret=True)


#: (backend, states, classes, rows, nfa, arm): ``nfa`` None asks the
#: rule as the trace does, for a dense stack; True/False is whether the
#: stack's NFA tensors could be built, as staging asks it (False: a
#: bank past the position budget, or a quarantined build)
PICKS = [
    ("cpu", 2048, 28, 8192, None, "gather"),  # off the TPU: always gather
    ("gpu", 2048, 28, 8192, None, "gather"),
    ("tpu", 2048, 28, 8192, None, "pallas"),  # the 1k-rule path piece
    ("tpu", 129, 28, 1024, None, "pallas"),   # one tile, just past 128
    ("tpu", 128, 28, 8192, None, "gather"),   # ≤128 states keep the gather
    ("tpu", 78, 20, 8192, None, "gather"),
    ("tpu", 2048, 28, 256, None, "gather"),   # a sub-tile batch
    ("tpu", 2048, 28, 1023, None, "gather"),
    ("tpu", 8192, 28, 8192, None, "pallas"),  # the state ceiling
    ("tpu", 8193, 28, 8192, None, "gather"),  # past it
    ("tpu", 2048, 65, 8192, None, "gather"),  # past one MXU pass of planes
    ("tpu", 2048, 28, 8192, True, "nfa"),     # past 128 states, NFA fits
    ("tpu", 129, 6, 0, True, "nfa"),          # staging asks with no rows
    ("tpu", 2048, 28, 8192, False, "pallas"),  # NFA ineligible: dense
    ("tpu", 2048, 28, 256, False, "gather"),
    ("tpu", 128, 28, 8192, True, "gather"),   # ≤128 states: NFA not asked
    ("cpu", 2048, 28, 8192, True, "gather"),  # off the TPU: not asked
    ("gpu", 512, 6, 0, True, "gather"),
]


@pytest.mark.parametrize(
    "backend,states,classes,rows,nfa,arm", PICKS,
    ids=["-".join(str(v) for v in p if v is not None) for p in PICKS])
def test_pick_arm_by_bank_shape(backend, states, classes, rows, nfa,
                                arm):
    if nfa is None:
        assert pick_arm(backend, states, classes, rows) == arm
        return
    asked = []

    def nfa_fits():
        asked.append(1)
        return nfa

    assert pick_arm(backend, states, classes, rows, nfa_fits) == arm
    # the NFA build costs host time: it is asked for only in the arm's
    # regime, the TPU past 128 padded states
    assert bool(asked) == (backend == "tpu" and states > 128)


def test_scan_arm_asks_the_rule_on_the_default_backend(monkeypatch):
    import jax

    shape = (7, 2048, 28)
    assert scan_arm(None, shape, 8192) == "gather"   # the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert scan_arm(None, shape, 8192) == "pallas"
    assert scan_arm(None, shape, 256) == "gather"
    # a test may still name the arm
    assert scan_arm("gather", shape, 8192) == "gather"


def test_auto_pick_runs_the_kernel_on_a_tpu_backend(monkeypatch):
    """The default pick wires the shape rule into the banked entry: with
    the backend reported as "tpu", a 200-state bank stack over one full
    tile runs the Pallas kernel (here in the interpreter) and gives the
    gather's accept words."""
    import jax

    from cilium_tpu.engine import dfa_kernel

    rng = np.random.default_rng(11)
    trans, byteclass, start, accept, data, lengths = _random_banked(
        rng, 2, 200, 9, pallas_dfa.TILE, 6)
    want = dfa_scan_banked(trans, byteclass, start, accept, data, lengths,
                           impl="gather")
    calls = []
    real = pallas_dfa.dfa_finals_pallas
    monkeypatch.setattr(pallas_dfa, "dfa_finals_pallas",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = dfa_kernel.dfa_scan_banked(trans, byteclass, start, accept, data,
                                     lengths, impl=None, interpret=True)
    assert calls
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
