"""PP (pipeline parallel): overlapped stage execution across batches.

SURVEY.md §2.6: the reference's pipeline is the BPF tail-call chain
(ct → policy → L7 redirect → encap) — stages chained per packet. Under
XLA the per-batch stage chain (mapstate lookup → field scans → conjunction
→ verdict) is fused into ONE program on purpose: hand-scheduling stages
across devices would only add ICI hops for tensors XLA already keeps in
registers/VMEM. What *does* need pipelining on a TPU is the
**host↔device boundary** (SURVEY.md §2.7: "host↔device via
``jax.device_put`` with double-buffering"):

* ``device_put`` of batch *i+1* is issued while batch *i* executes —
  JAX dispatch is async, so staging ahead by one overlaps PCIe/ICI
  transfer with MXU compute (the classic double buffer).
* Readbacks are deferred to the end (or never issued): a readback is
  a synchronization point that stalls the pipeline.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Sequence

import jax
import numpy as np


def run_pipelined(
    step: Callable[[Dict, Dict], Dict],
    arrays: Dict[str, jax.Array],
    host_batches: Sequence[Dict[str, np.ndarray]],
    device=None,
    depth: int = 2,
) -> List[Dict[str, jax.Array]]:
    """Run ``step(arrays, batch)`` over ``host_batches`` with transfers
    double-buffered ``depth`` batches ahead of compute.

    Returns per-batch output dicts of (unread) device arrays; call
    ``jax.block_until_ready`` / ``np.asarray`` on them only after the
    loop — the pipeline stays readback-free.
    """
    if depth < 1:
        raise ValueError("depth must be >= 1")
    batches = list(host_batches)
    staged: List[Dict[str, jax.Array]] = []
    outputs: List[Dict[str, jax.Array]] = []
    put = lambda b: {k: jax.device_put(v, device) for k, v in b.items()}
    # prime the buffer
    for b in batches[:depth]:
        staged.append(put(b))
    for i in range(len(batches)):
        cur = staged[i]
        staged[i] = None  # release: keep only ~depth batches resident
        out = step(arrays, cur)
        if i + depth < len(batches):
            staged.append(put(batches[i + depth]))
        outputs.append(out)
    return outputs


def collect(outputs: Iterable[Dict[str, jax.Array]]
            ) -> List[Dict[str, np.ndarray]]:
    """Read back a pipeline's outputs (one sync point, after all work
    is enqueued)."""
    return [{k: np.asarray(v) for k, v in out.items()} for out in outputs]
