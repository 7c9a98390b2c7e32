"""Long-payload SP/CP scans ≡ the sequential DFA scan."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cilium_tpu.engine.dfa_kernel import dfa_scan
from cilium_tpu.engine.longscan import payload_scan_cp, payload_scan_sp
from cilium_tpu.policy.compiler.dfa import compile_patterns
from cilium_tpu.parallel.mesh import make_mesh

PATTERNS = [".*attack-signature.*", ".*(GET|POST) /evil.*", ".*xx[0-9]{3}yy.*"]


def _setup(L=2048, B=16, seed=0):
    banked = compile_patterns(PATTERNS, bank_size=8)
    assert banked.n_banks == 1
    bank = banked.banks[0]
    rng = np.random.default_rng(seed)
    data = rng.integers(97, 123, size=(B, L), dtype=np.uint8)
    # implant signatures in some rows
    data[0, 100:116] = np.frombuffer(b"attack-signature", dtype=np.uint8)
    data[1, L - 30:L - 19] = np.frombuffer(b"POST /evil!", dtype=np.uint8)
    data[2, 5:12] = np.frombuffer(b"xx123yy", dtype=np.uint8)
    lengths = rng.integers(L // 2, L, size=(B,)).astype(np.int32)
    lengths[0] = L
    lengths[1] = L
    lengths[2] = L
    return bank, jnp.asarray(data), jnp.asarray(lengths)


def test_sp_equals_sequential():
    bank, data, lengths = _setup()
    trans = jnp.asarray(bank.trans)
    bc = jnp.asarray(bank.byteclass)
    seq = dfa_scan(trans, bc, jnp.int32(bank.start), data, lengths)
    sp = payload_scan_sp(trans, bc, jnp.int32(bank.start), data, lengths,
                         block=128)
    np.testing.assert_array_equal(np.asarray(seq), np.asarray(sp))
    # signatures actually detected
    accept = np.asarray(bank.accept)[np.asarray(sp)]
    assert accept[0].any() and accept[1].any() and accept[2].any()


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_cp_ring_equals_sequential(n_dev):
    bank, data, lengths = _setup(L=2048)
    trans = jnp.asarray(bank.trans)
    bc = jnp.asarray(bank.byteclass)
    seq = dfa_scan(trans, bc, jnp.int32(bank.start), data, lengths)
    mesh = make_mesh((n_dev,), ("seq",), jax.devices()[:n_dev])
    cp = payload_scan_cp(mesh, trans, bc, bank.start, data, lengths,
                         seq_axis="seq", block=64)
    np.testing.assert_array_equal(np.asarray(seq), np.asarray(cp))


def test_sp_odd_lengths_and_padding():
    bank, data, lengths = _setup(L=1000)  # not a multiple of block
    trans = jnp.asarray(bank.trans)
    bc = jnp.asarray(bank.byteclass)
    seq = dfa_scan(trans, bc, jnp.int32(bank.start), data, lengths)
    sp = payload_scan_sp(trans, bc, jnp.int32(bank.start), data, lengths,
                         block=256)
    np.testing.assert_array_equal(np.asarray(seq), np.asarray(sp))
