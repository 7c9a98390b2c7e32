#!/usr/bin/env python
"""Settle SP integration (VERDICT r2 weak #6 / item 9): does the
blockwise associative-scan payload scanner (engine/longscan.py
``payload_scan_sp``) beat the sequential per-byte ``lax.scan`` on the
1024-byte header bucket at bench shapes?

The trade: the sequential scan does L steps of a [B]-wide gather; the
SP scan does (L/block) x block steps of [B, S]-wide COMPOSITION
gathers plus a log-depth combine — S-fold more work per byte, paid to
cut the sequential chain from L to block + log2(L/block). On a TPU the
sequential gather chain is latency-bound, so SP can only win when S is
tiny and L is large.

Prints one JSON line per (S, L) shape:
  {"metric": "sp_vs_seq_S{S}_L{L}", "value": speedup, ...}
value > 1 means SP is faster. Run on the bench accelerator.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--flows", type=int, default=10000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--states", default="16,64,256,1024")
    ap.add_argument("--lengths", default="1024,4096")
    ap.add_argument("--block", type=int, default=256)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from cilium_tpu.engine.longscan import payload_scan_sp

    def seq_scan(trans, byteclass, start, data, lengths):
        """The integrated path's shape: per-byte gather chain."""
        B, L = data.shape
        cls = byteclass[data]                       # [B, L]
        pos = jnp.arange(L)

        def step(state, xs):
            c, p = xs
            nxt = trans[state, c]
            return jnp.where(p < lengths, nxt, state), None

        init = jnp.broadcast_to(start, (B,)).astype(jnp.int32)
        final, _ = lax.scan(step, init, (cls.T, pos))
        return final

    rng = np.random.default_rng(0)
    B = args.flows
    for S in (int(s) for s in args.states.split(",")):
        for L in (int(x) for x in args.lengths.split(",")):
            K = 32
            trans = jnp.asarray(
                rng.integers(0, S, size=(S, K), dtype=np.int32))
            byteclass = jnp.asarray(
                rng.integers(0, K, size=256, dtype=np.int32))
            start = jnp.int32(0)
            data = jnp.asarray(
                rng.integers(0, 256, size=(B, L), dtype=np.uint8))
            lengths = jnp.asarray(
                rng.integers(L // 2, L + 1, size=B, dtype=np.int32))

            seq = jax.jit(seq_scan)
            sp = jax.jit(lambda t, bc, st, d, ln: payload_scan_sp(
                t, bc, st, d, ln, block=args.block))
            a = seq(trans, byteclass, start, data, lengths)
            b = sp(trans, byteclass, start, data, lengths)
            jax.block_until_ready((a, b))
            if not bool(jnp.all(a == b)):
                print(json.dumps({"metric": f"sp_vs_seq_S{S}_L{L}",
                                  "value": 0,
                                  "unit": "MISMATCH", "vs_baseline": 0.0}))
                continue

            def timeit(fn):
                t0 = time.perf_counter()
                outs = [fn(trans, byteclass, start, data, lengths)
                        for _ in range(args.iters)]
                jax.block_until_ready(outs)
                return (time.perf_counter() - t0) / args.iters

            t_seq = timeit(seq)
            t_sp = timeit(sp)
            print(json.dumps({
                "metric": f"sp_vs_seq_S{S}_L{L}",
                "value": round(t_seq / t_sp, 3),
                "unit": "seq_ms/sp_ms (>1 = SP wins)",
                "vs_baseline": 0.0,
                "seq_ms": round(t_seq * 1e3, 2),
                "sp_ms": round(t_sp * 1e3, 2),
                "flows": B, "block": args.block,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
