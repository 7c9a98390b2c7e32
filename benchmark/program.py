"""The system under test, as the benchmark drives it. This is the one
module of ``benchmark/`` that imports ``cilium_tpu``: it hands the
program the generated inputs (CNP documents, capture files, stream
images) and reads back its verdicts and counters. Both configurations
run the same path: ``Loader`` → ``VerdictEngine`` → ``CaptureReplay``
or ``VerdictService``/``ServeLoop`` → the fused megakernel.
"""

from __future__ import annotations

import os
import types
from typing import Dict, List

from benchmark.worlds import resolve, to_flow

#: the staging phases of ``CaptureReplay`` (``_StagePhase`` labels of
#: ``cilium_tpu_capture_stage_seconds``)
STAGE_PHASES = ("tables", "featurize", "dedup", "table-h2d", "memo-fill")


def _modules():
    from cilium_tpu.core import flow, identity, labels
    from cilium_tpu.policy import mapstate, repository, selectorcache
    from cilium_tpu.policy.api import cnp

    return types.SimpleNamespace(
        flow=flow, identity=identity, labels=labels, cnp=cnp,
        selectorcache=selectorcache, repository=repository,
        mapstate=mapstate)


def enable_compile_cache(path: str) -> None:
    """JAX's persistent compilation cache at ``path`` (a fixed
    directory of the checkout), with every program kept: the served
    path's many sub-0.1 s compiles are set-up time too. Call before
    any compile; the program's own ``enable_persistent_cache`` honours
    ``JAX_COMPILATION_CACHE_DIR`` and is run first so its 0.1 s floor
    is overridden here."""
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    from cilium_tpu.runtime.xla_cache import enable_persistent_cache

    enable_persistent_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Program:
    """One ``Loader`` on one device, staged with the world's policy."""

    def __init__(self, docs: List[dict], endpoints: Dict[str, dict],
                 device, cache_dir: str, serve: bool = False):
        from cilium_tpu.core.config import Config
        from cilium_tpu.engine.verdict import VerdictEngine
        from cilium_tpu.runtime.loader import Loader

        mods = _modules()
        self.flowmod = mods.flow
        self.per_identity, self.ids = resolve(mods, docs, endpoints)
        cfg = Config()
        cfg.enable_tpu_offload = True
        cfg.serve.enabled = serve
        cfg.loader.cache_dir = os.path.join(cache_dir, "artifacts")
        self.loader = Loader(cfg, device=device)
        self.engine = self.loader.regenerate(self.per_identity,
                                             revision=1)
        if not isinstance(self.engine, VerdictEngine):
            raise RuntimeError(f"serving engine is "
                               f"{type(self.engine).__name__}, not "
                               f"VerdictEngine")

    # -- inputs ----------------------------------------------------------
    def flows(self, recs):
        return [to_flow(self.flowmod, r, self.ids) for r in recs]

    def write_segment(self, path: str, recs) -> int:
        """A capture file (v2/v3, with L7 sidecars) of ``recs``."""
        from cilium_tpu.ingest import binary

        return binary.write_capture_l7(path, self.flows(recs))

    def image(self, recs) -> bytes:
        """One stream chunk image of ``recs``."""
        from cilium_tpu.ingest.binary import capture_to_bytes

        return capture_to_bytes(self.flows(recs))

    @staticmethod
    def widths(images: List[bytes]) -> Dict[str, int]:
        """Per-field padded widths that hold every image's strings, so
        every chunk of every stream encodes to one shape."""
        from cilium_tpu.ingest.binary import (
            capture_field_widths,
            capture_from_bytes,
        )

        out: Dict[str, int] = {}
        for img in images:
            _, l7, offsets, _, _ = capture_from_bytes(img)
            for k, v in capture_field_widths(l7, offsets).items():
                out[k] = max(out.get(k, 0), v)
        return out

    # -- the replay path -------------------------------------------------
    def replay_segment(self, path: str):
        """One capture file → verdicts on the host, as a fresh
        ``CaptureReplay`` session: map the file, scan its string tables
        on the device, featurize and dedup every row, then verdict all
        records in one chunk. Returns ``(lanes, facts)``: the host lanes, and
        the records, unique rows and string-table bytes of the session."""
        from cilium_tpu.engine.verdict import CaptureReplay
        from cilium_tpu.ingest import binary

        ecfg = self.loader.config.engine
        rec = binary.map_capture(path)
        l7, offsets, blob = binary.read_l7_sidecar(path)
        replay = CaptureReplay(self.engine, l7, offsets, blob, ecfg,
                               gen=binary.read_gen_sidecar(path))
        replay.stage_rows(rec, l7)
        replay.stage_unique(
            drop_if_ratio_at_least=ecfg.stage_unique_drop_ratio)
        out = replay.verdict_chunk(rec, l7, start=0)
        return out, {"records": len(rec),
                     "unique_rows": int(replay.n_unique),
                     "table_bytes": int(offsets[-1] - offsets[0])}

    # -- the served path -------------------------------------------------
    def service(self, sock_path: str):
        from cilium_tpu.runtime.service import VerdictService

        svc = VerdictService(self.loader, sock_path)
        svc.start()
        return svc

    @staticmethod
    def warm_pack_buckets(svc, chunk_records: int) -> int:
        """Compile the ring's fused dispatch at every pow2 pack size it
        can take (32 records up to ``PACK_MAX``), and the slice of one
        chunk's verdicts out of each: how many chunks one pack cycle
        gathers depends on timing, so the window may use any of them.
        Call once the warm-up traffic has filled the session. Returns
        the number of sizes."""
        import numpy as np

        from cilium_tpu.engine.ring import PACK_MAX

        loop = svc.serveloop
        ring = loop.ring
        pairs = (loop.authed_pairs_fn()
                 if loop.authed_pairs_fn is not None else None)
        n, b = 0, 32
        while b <= PACK_MAX:
            with ring._session_lock:
                out = ring.session.serve_ids(np.zeros(b, np.int32),
                                             authed_pairs=pairs,
                                             provenance=ring.provenance)
            if b >= chunk_records:
                piece = (out.slice(0, chunk_records)
                         if hasattr(out, "slice")
                         else out[0:chunk_records])
                np.asarray(getattr(piece, "verdicts", piece))
            n += 1
            b *= 2
        return n

    @staticmethod
    def stream_client(sock_path: str, widths, timeout: float,
                      pipeline_depth: int):
        from cilium_tpu.runtime.stream import StreamClient

        return StreamClient(sock_path, widths=widths, timeout=timeout,
                            pipeline_depth=pipeline_depth)

    # -- counters --------------------------------------------------------
    @staticmethod
    def counters() -> Dict[str, float]:
        """The program's counters the per-layer metrics read, as one
        snapshot (the harness takes one at each edge of the window)."""
        from cilium_tpu.runtime.metrics import (
            CAPTURE_STAGE_SECONDS,
            METRICS,
            SERVE_PACK_RECORDS,
            SERVE_PACK_STREAMS,
        )

        out = {f"stage_s.{p}": METRICS.histo_sum(CAPTURE_STAGE_SECONDS,
                                                 {"phase": p})
               for p in STAGE_PHASES}
        out["packs"] = float(METRICS.histo_count(SERVE_PACK_RECORDS))
        out["pack_streams"] = METRICS.histo_sum(SERVE_PACK_STREAMS)
        return out

    def policy_array_bytes(self) -> Dict[str, int]:
        """Bytes of each staged policy array (the roofline counts the
        transition tables among them)."""
        return {k: int(a.nbytes) for k, a in self.engine._arrays.items()}
