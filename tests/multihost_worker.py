"""Worker process for the multi-host elasticity test.

Runs as one process of a 2-process ``jax.distributed`` CPU cluster
(tests/test_multihost_elastic.py launches two of these):

1. joins the cluster and proves the DCN runtime is real with a psum
   over the global mesh (each process contributes pid+1);
2. compiles + stages the SAME policy snapshot through a Loader backed
   by a SHARED content-addressed artifact cache (the reference
   property: every agent derives identical state from the common rule
   store, no cross-host state exchange);
3. verdicts its process-local slice of the flow stream (process_span);
4. writes results as JSON, then — when told to crash — dies via
   ``os._exit`` (no clean shutdown, like a killed agent).
"""

import json
import os
import sys


def main() -> int:
    (coord, nproc, pid, cache_dir, out_path, crash) = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4],
        sys.argv[5], sys.argv[6] == "crash")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from cilium_tpu.parallel.multihost import (
        global_mesh,
        init_multihost,
        process_span,
    )

    assert init_multihost(coord, nproc, pid)
    assert jax.process_count() == nproc

    # 1. DCN proof: psum across processes (1 CPU device per process)
    mesh = global_mesh()
    f = jax.jit(shard_map(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P()))
    ga = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")),
        np.array([float(pid + 1)], dtype=np.float32),
        (nproc,))
    out = f(ga)  # out_specs=P() → fully replicated on every process
    psum_total = float(np.asarray(out.addressable_data(0))[0])

    # 2. identical compile from the shared rule source
    from cilium_tpu.core.config import Config
    from cilium_tpu.ingest import synth
    from cilium_tpu.runtime.loader import Loader

    scenario = synth.synth_http_scenario(n_rules=32, n_flows=64)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = cache_dir
    engine = Loader(cfg).regenerate(per_identity, revision=1)

    artifacts = sorted(a for a in os.listdir(cache_dir)
                       if a.endswith(".pkl"))
    mtimes = {a: os.stat(os.path.join(cache_dir, a)).st_mtime_ns
              for a in artifacts}

    # 3. verdict MY slice of the stream
    idx, count = process_span()
    mine = scenario.flows[idx::count]
    verdicts = [int(v) for v in
                engine.verdict_flows(mine)["verdict"]]

    with open(out_path, "w") as fp:
        json.dump({"pid": pid, "psum": psum_total,
                   "artifacts": artifacts, "mtimes": mtimes,
                   "slice": [idx, count], "verdicts": verdicts}, fp)

    # final barrier (a second collective): the COORDINATOR must stay
    # alive until every worker finishes its slow phases — a leader that
    # exits early trips the peers' coordination-service error polling
    # and kills them mid-compile
    jax.block_until_ready(f(ga))

    # both exits skip jax.distributed's atexit shutdown handshake: the
    # crash case dies like a killed agent, and the clean case must not
    # hang/fail on a peer that already died dirty (agents shut down
    # independently; there is no fleet-wide handshake)
    os._exit(1 if crash else 0)


if __name__ == "__main__":
    main()
