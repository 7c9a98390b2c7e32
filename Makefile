# CI lanes (SURVEY §4/§5.2). No pip/apt — everything runs from the
# baked environment at the repo root.

PY ?= python

.PHONY: test shim lint precommit determinism dryrun chaos obs soak churn \
        churn-fleet churn-fleet-smoke dst dst-validate serve-soak \
        serve-fleet serve-fleet-smoke canary canary-smoke \
        bench bench-all bench-e2e bench-service bench-regen bench-sp \
        bench-stage bench-stream bench-kernel bench-multichip \
        bench-protocols perf-report check

test:            ## full suite (CPU, virtual 8-device mesh via conftest)
	$(PY) -m pytest tests/ -q

shim:            ## build the C++ proxylib-ABI shim
	$(MAKE) -C shim

# lint: ctlint codebase-aware static analysis (cilium_tpu/analysis —
# jit-purity, lock-order, registry consistency, swallowed exceptions,
# unused imports, the v2 dataflow families: shape-dtype,
# recompile-hazard, abi-surface, config-surface, the v3
# thread-safety family: guarded-field inference, check-then-act,
# lock-release windows, publication safety, plus the v4
# device-dataflow family: implicit-sync, hot-loop-h2d,
# readback-ordering, missing-donation over the serving hot path's
# residency lattice). Fails on any non-allowlisted finding;
# CTLINT.json is the CI report artifact (schema 4: findings
# byte-stable for a clean tree + timings_ms + racing-root and
# device-residency attribution). Rules run on a thread pool; the
# --wall-budget-ms gate (2x the v4 warm tree-wide baseline) keeps
# the lint lane's latency honest. Catalog: docs/ANALYSIS.md
lint:            ## ctlint static-analysis gate
	$(PY) -m cilium_tpu.analysis --format text --out CTLINT.json \
	    --wall-budget-ms 40000

# the pre-commit face: thread-safety + device-dataflow findings on
# changed files only — the two rule families whose hazards are
# cheapest to introduce in a hot-path edit and costliest to ship;
# fast enough (two families, changed-paths filter) to run on every
# commit without the full lint lane's latency
precommit:       ## changed-files thread-safety + device-dataflow lint
	$(PY) -m cilium_tpu.cli lint --rule thread-safety \
	    --rule implicit-sync --rule hot-loop-h2d \
	    --rule readback-ordering --rule missing-donation \
	    --changed-only

determinism:     ## deterministic-compile + debug_nans sanitizer lane
	$(PY) -m pytest tests/test_determinism.py -q

# chaos: golden corpus replayed under injected device failures /
# stream drops / mid-swap crashes (runtime/faults.py) — seeded and
# deterministic; marked slow so tier-1 timing never pays for it
chaos:           ## seeded fault-injection replay lane
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_faults.py -q -m chaos

# obs: flight-recorder tracing + metrics exposition tests, then a
# scrape-lint — expose the LIVE registry (after the tests populated
# it) and assert the Prometheus text parses with zero malformed lines
obs:             ## observability lane: tracing tests + scrape lint
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_tracing.py \
	    tests/test_observability.py tests/test_provenance.py \
	    tests/test_explain.py -q -m "not slow"
	JAX_PLATFORMS=cpu $(PY) -c "\
	from cilium_tpu.runtime.metrics import METRICS, lint_exposition; \
	METRICS.inc('cilium_tpu_scrape_lint_total'); \
	METRICS.observe('cilium_tpu_scrape_lint_seconds', 0.01); \
	text = METRICS.expose(); errs = lint_exposition(text); \
	assert not errs, errs; \
	print('scrape-lint OK:', len(text.splitlines()), 'lines')"

# soak: short synthetic overload (4× saturation) against the
# admission-controlled batcher path — asserts shed > 0 with the queue
# depth bounded at max_pending and admitted-request p99 within 2× the
# unloaded p99 (ISSUE 5 acceptance). Marked slow+soak so tier-1
# timing never pays for it.
# -s: the virtual-time fixture prints the simulated-vs-wall speedup
# on the lane output (ISSUE 10 — the lane now simulates its service
# times on an autojumping VirtualClock; one real-clock smoke stays)
soak:            ## synthetic-overload admission/shed lane
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_soak.py -q -s \
	    -m "soak and not churn and not serve"

# serve-soak: the ISSUE-11 acceptance lane — the DST load model
# (runtime/loadmodel.py) drives >=100k CONCURRENT virtual streams
# (heavy-tailed arrivals, diurnal swing, reconnect storms, seeded
# serve.lease/serve.ring_slot faults) through the continuously-
# batched serving loop (runtime/serveloop.py + engine/ring.py) under
# the autojumping VirtualClock, with lease-accounting / sampled-
# correctness / memo-honesty / explanation-decode invariants checked
# after every event.
# Gates: 0 violations, concurrency peak >= 95k, p99 <= 2x unloaded,
# shed rate bounded, memo-bypass bytes > 0, explanation coverage
# >= 0.999 of served verdicts, and declared-SLO burn rates <= 1.0
# over the whole-run window (ISSUE 14). One provenance-stamped
# line lands in BENCH_SERVE_r07.jsonl (consumed by perf-report).
serve-soak:      ## 100k-virtual-stream continuous-batching soak
	JAX_PLATFORMS=cpu $(PY) -m cilium_tpu.runtime.loadmodel \
	    --streams 100000 --out BENCH_SERVE_r07.jsonl

# serve-fleet: the ISSUE-16 acceptance lane — the DST fleet model
# (runtime/fleetserve.py) drives >=1M concurrent virtual streams
# across >=4 simulated hosts (each a real ServeLoop + ring + session
# over bank artifacts shared via the artifact store) behind the
# stream-affinity router, with mid-storm host KILL / partition /
# drain-restart / warm rejoin and seeded fleet.heartbeat +
# fleet.handoff faults. Gates: 0 invariant violations (fleet-exact
# lease books, lease conservation, sampled correctness + explanation
# honesty at the CITED generation), aggregate p99 <= 2x the committed
# single-host serve-soak baseline, shed rate <= 2%, zero survivor
# recompiles + a zero-compile warm restore on every rejoin, and zero
# unrecovered streams across the failovers. ISSUE 17 arms the fleet
# observability gates on the same run: >=400 handoffs with >=99%
# cross-host trace-stitch coverage, a non-empty merged Hubble flow
# export, a consistent fleet event journal, and observability
# overhead <= 2% of wall time.
serve-fleet:     ## 1M-stream serving fleet: failover + shedding soak
	JAX_PLATFORMS=cpu $(PY) -m cilium_tpu.runtime.fleetserve \
	    --streams 1050000 --hosts 4 --out BENCH_FLEET_SERVE_r08.jsonl

# the smoke face of the same driver — small enough for `make check`;
# the p99 gate stays off (tiny runs are all fixed overhead) and the
# handoff floor drops to 1 (a 60-virtual-second run can't stage 400
# failovers) but every failover/conservation/honesty gate — and the
# journal/books-consistency + stitch-coverage + flow-export +
# obs-overhead gates — is armed
serve-fleet-smoke: ## serving-fleet driver at check-sized smoke scale
	JAX_PLATFORMS=cpu $(PY) -m cilium_tpu.runtime.fleetserve \
	    --streams 2000 --hosts 4 --virtual-s 60 --storm-size 200 \
	    --no-p99-gate --min-handoffs 1 \
	    --out /tmp/BENCH_FLEET_SERVE_smoke.jsonl

# canary: the ISSUE-20 acceptance lane — shadow/canary policy rollout
# through a live ServeLoop (runtime/canary.py): stage a PLANTED bad
# generation (every verdict flipped to deny) as N+1 beside serving N,
# double-dispatch a sampled fraction of ring traffic through both
# engines in the same pack cycle, and prove the verdict-diff gate
# REFUSES the commit before a single bad verdict is served; then a
# clean rollout through the same pipeline must commit. Gates:
# diff_caught + serving_untouched + clean_committed + clean_verdicts
# + sampled, and double-dispatch overhead <= 5% of pack-cycle wall.
# One provenance-stamped line lands in BENCH_CANARY_r09.jsonl
# (consumed by perf-report, whose canary-budget gate holds the
# declared budget across rounds).
canary:          ## shadow-rollout verdict-diff gate + overhead budget
	JAX_PLATFORMS=cpu $(PY) -m cilium_tpu.runtime.canary \
	    --out BENCH_CANARY_r09.jsonl

# the smoke face of the same driver — small enough for `make check`;
# every gate stays armed (the lane is virtual-time cheap already)
canary-smoke:    ## canary rollout driver at check-sized smoke scale
	JAX_PLATFORMS=cpu $(PY) -m cilium_tpu.runtime.canary \
	    --chunks 48 --pool-chunks 12 \
	    --out /tmp/BENCH_CANARY_smoke.jsonl

# churn: the ISSUE-8 acceptance soak — sustained CNP add/delete +
# FQDN pattern churn through a live replay session across ≥50
# committed policy updates. Asserts zero ERROR verdicts and zero
# stale-allow/stale-deny vs the serving engine + sampled CPU oracle,
# bank-scoped compile work (O(Δ), not O(policy×updates)), and a
# steady-state memo hit ratio ≥0.99. Writes a provenance-stamped
# update→enforcement p99 bench line consumed by perf-report.
# CILIUM_TPU_DST_SEED: the lane's driving seed rides the bench line's
# provenance stamp (runtime/provenance.dst_stamp) so perf-report can
# tie an update-latency regression to the schedule that exposed it
churn:           ## sustained policy-churn soak (bank-scoped compile)
	JAX_PLATFORMS=cpu \
	CILIUM_TPU_CHURN_BENCH_OUT=BENCH_CHURN_r06.jsonl \
	CILIUM_TPU_DST_SEED=8 \
	$(PY) -m pytest tests/test_soak.py -q -m churn

# churn-fleet: the ISSUE-13 acceptance lane — BASELINE configs[4]
# scale (10k identities x 5k CNP over ~200 service classes) driven as
# a churn storm through one live Loader + replay session by
# runtime/fleet.py. Gates: zero stale/ERROR verdicts vs the serving
# engine + sampled oracle, bank compiles/update <= 1.1x the 27-bank
# churn ratio (O(Δ) survives two orders of magnitude more policy),
# update->enforcement p99 <= 2x the committed BENCH_CHURN_r06 number,
# and peak RSS under the declared bound (sharded registry +
# fingerprint store + artifact-cache LRU). One provenance-stamped
# line lands in BENCH_CHURN_FLEET_r07.jsonl (consumed by perf-report).
churn-fleet:     ## fleet-scale churn storm (10k ids x 5k CNP)
	JAX_PLATFORMS=cpu $(PY) -m cilium_tpu.runtime.fleet \
	    --identities 10000 --cnps 5000 --updates 56 \
	    --out BENCH_CHURN_FLEET_r07.jsonl

# the smoke face of the same driver — small enough for `make check`;
# the p99 gate stays off (the 27-bank baseline is not comparable at
# smoke scale) but every correctness gate is armed
churn-fleet-smoke: ## fleet churn driver at check-sized smoke scale
	JAX_PLATFORMS=cpu $(PY) -m cilium_tpu.runtime.fleet \
	    --identities 1000 --cnps 500 --updates 10 --no-p99-gate

# dst: deterministic simulation testing (runtime/dst.py) — seeded
# fault-SCHEDULE search under virtual time (runtime/simclock.py):
# each seed is a schedule of fault arms / policy churn / identity
# storms / drain-restore cycles / time advances against a real
# Loader+engine+breaker+session world, with standing invariants
# (oracle agreement, fail-closed, session/memo honesty, O(Δ) compile,
# breaker+quarantine liveness) checked after every event. The same
# CILIUM_TPU_DST_SEED replays byte-identically; a violation is
# delta-debugged to a minimal schedule under tests/dst/regressions/.
dst:             ## seeded fault-schedule search (DST) lane
	JAX_PLATFORMS=cpu $(PY) -m cilium_tpu.runtime.dst \
	    --schedules 200 --shrink --out BENCH_DST_r06.jsonl

# dst-validate: planted-bug proof — re-introduce a known FIXED bug
# behind the mutation flag and show the schedule search catches and
# shrinks it within a bounded seed budget (both known mutations).
dst-validate:    ## planted-bug validation of the DST searcher
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/dst/test_planted.py -q

dryrun:          ## driver multi-chip contract on a virtual CPU mesh
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -c "import jax; jax.config.update('jax_platforms','cpu'); \
	import __graft_entry__ as ge; ge.dryrun_multichip(8); \
	fn, a = ge.entry(); jax.block_until_ready(jax.jit(fn)(*a)); \
	print('entry OK')"

bench:           ## headline config on the attached accelerator
	$(PY) bench.py --config http --check

bench-all:       ## every BASELINE config, one JSON line each
	$(PY) bench.py --config all

bench-e2e:       ## file→verdict replay of a stored v2 Hubble capture
	$(PY) bench.py --config http --from-capture /tmp/ct_bench_capture.bin

bench-service:   ## socket→MicroBatcher→engine tail latency sweep
	$(PY) bench_service.py --shim --out SERVICE_LATENCY.json

bench-regen:     ## cold vs incremental vs restage regeneration latency
	$(PY) bench.py --config regen

bench-sp:        ## SP (associative-scan) vs sequential payload scan
	$(PY) bench_sp.py

# bench-stage: the fast staging microbench — columnar capture write +
# CaptureReplay session staging (tables/featurize/dedup/h2d phase
# split) + verdict-memo fill, one provenance-stamped line per lane.
# The cold stage_ms is the number the ISSUE-7 ≥10× budget tracks.
bench-stage:     ## capture→session staging microbench (phase split)
	$(PY) bench_stage.py

# bench-kernel: the megakernel microbench — fused verdict step (one
# dispatch) vs the three-op mapstate/scan/resolve path at the 1k-rule
# config, plus the per-bank-shape dense-DFA vs bitset-NFA autotune
# sweep. Provenance-stamped lines land in BENCH_KERNEL_r06.jsonl for
# perf-report; the lane FAILS (strict gate) if the fused speedup
# drops below 2x — the ROADMAP megakernel target.
bench-kernel:    ## fused megakernel vs three-op path + impl sweep
	$(PY) bench_kernel.py --min-speedup 2.0 --out BENCH_KERNEL_r06.jsonl

bench-stream:    ## online serving path: chunked binary stream transport
	$(PY) bench_service.py --stream --stream-only --rules 1000 \
	    --stream-chunk 16384 --stream-depth 16 \
	    --out SERVICE_LATENCY_stream.json

# bench-multichip: every §2.6 lane on the virtual 8-device mesh —
# DP (batch-sharded), DPxEP (auto-partitioned comparison), EP
# (one-shot all_to_all re-shard), CP (payload-sharded blockwise scan,
# one carry exchange per block), TP (state-axis fallback). STRICT
# gate (ISSUE 12): fails if DP constant-silicon efficiency < 0.8, CP
# or EP overhead_fraction > 0.1, or any lane records more ledger
# collectives per compiled block than the budget it declares on the
# line. The provenance-stamped artifact feeds perf-report, whose
# collective-budget gate holds the declared budgets across rounds.
bench-multichip: ## DP/EP/CP/TP scaling + collective-budget gate
	JAX_PLATFORMS=cpu $(PY) bench_multichip.py --devices 8 \
	    --flows-per-device 1024 --strict-gate \
	    --out MULTICHIP_PERF_r06.json

# bench-protocols: the ISSUE-15 lane — per-protocol verdict
# throughput for the frontend families (cassandra/memcache/r2d2 +
# the mixed protocols scenario, with an in-process http reference),
# each lane oracle-checked, plus the cross-cluster leg: a 50-update
# remote-identity churn storm streamed through clustermesh into the
# serving loader, gated on ZERO stale/ERROR verdicts and
# update->enforcement p99 <= 2x the committed single-cluster churn
# number. Provenance-stamped lines land in BENCH_PROTO_r07.jsonl
# (consumed by perf-report).
bench-protocols: ## frontend-family throughput + cross-cluster churn
	JAX_PLATFORMS=cpu $(PY) bench_protocols.py --updates 50 \
	    --out BENCH_PROTO_r07.jsonl

# perf-report: schema-validate every BENCH_*/MULTICHIP_*/SERVICE_*
# artifact, normalize them into the round trajectory
# (PERF_TRAJECTORY.json — the CI artifact), classify round-over-round
# deltas as code regression vs environment change (provenance/RTT
# evidence), and fail on an unexplained regression in the newest round
perf-report:     ## bench trajectory + regression gate
	$(PY) -m cilium_tpu.perf_report --root . --out PERF_TRAJECTORY.json

check: shim lint test determinism dryrun obs churn-fleet-smoke serve-fleet-smoke canary-smoke bench-multichip perf-report   ## the full CI gate
