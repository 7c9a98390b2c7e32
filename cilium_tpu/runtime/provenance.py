"""Environment provenance for bench artifacts (the perf ledger's
identity stamp).

Round 5's "40× regression" was a ~100ms host↔device round trip, not
a code change
— but nothing on the artifact said so, and the comparison was
unfalsifiable until a human re-derived the environment from log
warnings. Every bench line now carries a **provenance fingerprint**:
platform, device kind/count, jax version, an H2D round-trip probe to
the attached backend, and the git revision that produced the number.
``cilium-tpu perf-report`` (``cilium_tpu/perf_report.py``) uses the
fingerprint to classify a cross-round delta as *code regression* vs
*environment change* instead of guessing.

Everything here is best-effort: a fingerprint must never break the
one-JSON-line bench contract, so a missing backend or absent git
checkout degrades fields to ``None`` rather than raising.
"""

from __future__ import annotations

import os
import platform
import subprocess
import sys
import time
from typing import Dict, Optional

#: version of the stamped bench-artifact schema — every new-schema
#: bench line/artifact carries ``"bench_schema": BENCH_SCHEMA`` next to
#: ``"provenance"``; the perf-report normalizer keys validation on it
BENCH_SCHEMA = 1


def git_revision(root: Optional[str] = None) -> Dict[str, object]:
    """``{"git_rev": short-hash or None, "git_dirty": bool or None}``
    for the checkout containing ``root`` (default: this file's repo)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    try:
        rev = subprocess.run(
            ["git", "-C", root, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10)
        if rev.returncode != 0:
            return {"git_rev": None, "git_dirty": None}
        dirty = subprocess.run(
            ["git", "-C", root, "status", "--porcelain"],
            capture_output=True, text=True, timeout=10)
        return {"git_rev": rev.stdout.strip(),
                "git_dirty": (bool(dirty.stdout.strip())
                              if dirty.returncode == 0 else None)}
    except (OSError, subprocess.TimeoutExpired):
        return {"git_rev": None, "git_dirty": None}


def rtt_probe(n: int = 7) -> Dict[str, Optional[float]]:
    """(p50, max) of a tiny H2D+readback round trip in ms — the
    transport-health marker (bench.py round 4: a 4× run-to-run spread is
    unfalsifiable without it). Requires an initialized jax backend;
    returns Nones when there isn't one."""
    try:
        import jax
        import numpy as np

        xs = np.zeros(16, dtype=np.int32)
        np.asarray(jax.device_put(xs))  # connection warm
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            np.asarray(jax.device_put(xs))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return {"rtt_p50_ms": round(ts[len(ts) // 2] * 1e3, 3),
                "rtt_max_ms": round(ts[-1] * 1e3, 3)}
    except Exception:  # noqa: BLE001 — probe is best-effort by contract
        return {"rtt_p50_ms": None, "rtt_max_ms": None}


def fingerprint(rtt: bool = True,
                root: Optional[str] = None) -> Dict[str, object]:
    """The full provenance fingerprint. ``rtt=False`` skips the
    backend probe (callers that never touch jax — the bench OUTER
    process — still get host/git identity)."""
    fp: Dict[str, object] = {
        "schema": BENCH_SCHEMA,
        # ctlint: disable=wall-clock  # provenance stamps record when the REAL world produced this artifact
        "captured_unix": int(time.time()),
        "host_platform": platform.platform(),
        "python": platform.python_version(),
        "argv0": os.path.basename(sys.argv[0]) if sys.argv else None,
        "jax_platforms_env": os.environ.get("JAX_PLATFORMS"),
        "jax_version": None,
        "backend": None,
        "device_kind": None,
        "device_count": None,
    }
    fp.update(git_revision(root))
    try:
        import jax

        fp["jax_version"] = jax.__version__
        devices = jax.devices()
        fp["backend"] = jax.default_backend()
        fp["device_kind"] = devices[0].device_kind if devices else None
        fp["device_count"] = len(devices)
    except Exception as e:  # noqa: BLE001 — no backend is a valid
        # environment; the fingerprint says so instead of raising
        fp["jax_error"] = str(e)[:120]
    if rtt and fp["backend"] is not None:
        fp.update(rtt_probe())
    else:
        fp.update({"rtt_p50_ms": None, "rtt_max_ms": None})
    return fp


def dst_stamp() -> Optional[Dict[str, object]]:
    """The deterministic-simulation provenance rider: when a lane runs
    under the DST harness (``CILIUM_TPU_DST_SEED`` set by `make dst` /
    the converted chaos/churn lanes), its bench lines carry the seed
    and schedule digest, so perf-report can tie a regression to the
    exact fault schedule that exposed it (replay:
    ``python -m cilium_tpu.runtime.dst --replay --seed N``)."""
    seed = os.environ.get("CILIUM_TPU_DST_SEED")
    if seed is None:
        return None
    out: Dict[str, object] = {}
    try:
        out["dst_seed"] = int(seed)
    except ValueError:
        out["dst_seed"] = seed
    digest = os.environ.get("CILIUM_TPU_DST_DIGEST")
    if digest:
        out["schedule_digest"] = digest
    mutation = os.environ.get("CILIUM_TPU_DST_MUTATION")
    if mutation:
        out["mutation"] = mutation
    return out


def stamp(obj: Dict, rtt: bool = True) -> Dict:
    """Stamp ``obj`` (a bench line or artifact dict) in place with the
    versioned schema tag + fingerprint; returns ``obj``. Never raises.

    Fleet lines additionally carry ``host_id`` — which host produced
    the number (``parallel/multihost.host_id``: ``CILIUM_TPU_HOST_ID``
    when the harness pins one, else the process identity). The id
    makes per-host numbers from the fleetserve lane attributable the
    way ``git_rev`` makes rounds attributable; callers that already
    set a ``host_id`` (the router stamping a replica's line) win."""
    try:
        obj["bench_schema"] = BENCH_SCHEMA
        obj["provenance"] = fingerprint(rtt=rtt)
        from cilium_tpu.parallel.multihost import host_id

        obj.setdefault("host_id", host_id())
        dst = dst_stamp()
        if dst is not None:
            obj["dst"] = dst
    except Exception as e:  # noqa: BLE001 — the bench line must still
        # print; the stamp records its own failure instead of raising
        obj.setdefault("provenance", None)
        obj["provenance_error"] = str(e)[:120]
    return obj
