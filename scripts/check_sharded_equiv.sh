#!/usr/bin/env bash
# One command, no TPU needed: run the sharded-vs-replicated server
# equivalence suite on the forced-8-device CPU mesh
# (docs/sharded_server.md). Pins, per mode family:
#   - fp32 --server_shard trajectories bit-identical to the replicated
#     plane (reduce-scatter/threshold-exchange/all-gather exactness);
#   - the int8 quantized reduce's conservation + EF-carry contracts and
#     its documented tolerance vs fp32;
#   - checkpoint round-trips of the sharded server state (both planes);
#   - the fused server epilogue's bit-identity to the composed path on
#     both planes (tests/test_fused_epilogue.py, docs/fused_epilogue.md —
#     megakernel through the Pallas interpreter);
#   - the sketch cells' client phase (the leaves sketched in groups at
#     their flat offsets): its table == the flat ravel+sketch route's for
#     1, 2 and 4 scan steps x weight decay, replicated/--server_shard x
#     composed/--fused_epilogue trajectories, plus the no-d-sized-movement
#     and tree-shaped-carry structural asserts (tests/test_stream_sketch.py,
#     docs/stream_sketch.md);
#   - the group plan: the planner's contracts, bit-identity under any
#     plan across the same matrix, and the launch-count == group-count,
#     once a round, structural assert (tests/test_sketch_coalesce.py,
#     docs/stream_sketch.md);
#   - the telemetry plane's non-perturbation (fp32 bit-identity with
#     --telemetry on/off on BOTH planes) and its strict zero-host-sync
#     audit with guards+telemetry through the engine
#     (tests/test_telemetry.py, docs/observability.md);
#   - the continuous-observability plane (tests/test_watch.py,
#     docs/observability.md): the schema-v3 histogram block's fp32
#     bit-identity on/off on BOTH planes, the strict zero-host-sync
#     audit with guards + telemetry + histograms + watch through the
#     engine, watch-rule grammar/EWMA/reaction contracts, an injected
#     fault's alert + round-aligned triggered trace capture reproduced
#     from the JSONL alone, v1/v2/v3 schema cross-parse, and the
#     obs_report --follow torn-tail live reader + --compare delta table;
#   - the per-leg compressed-collective plan (--collective_plan,
#     docs/compressed_collectives.md): the fp32 plan bit-identical to the
#     legacy --reduce_dtype path across both planes x both epilogues, the
#     quantized downlink's dres conservation/telescoping contracts
#     (mirroring the qres suite), int4/fp8 pack-unpack round-trips,
#     payload_bytes == ledger == actual payload agreement, quarantine
#     leaving dres untouched, and the fp32-plan -> compressed-plan
#     checkpoint warn path (tests/test_compressed_collectives.py);
#   - the participation layer (--participation / --inject_client_fault,
#     docs/fault_tolerance.md §client faults): full participation
#     bit-identical to the pre-participation path across both planes x
#     both epilogues, the partial-cohort exact-reweighting linearity
#     identity, the staleness-decayed late landing pinned against a
#     hand-computed reweighting, a seeded drop+slow+corrupt run
#     deterministic and guard-quarantine-free, and the strict
#     zero-host-sync audit with late landing in flight
#     (tests/test_participation.py);
#   - the million-client host-offload data plane (docs/host_offload.md):
#     the memmap row store bit-identical to the device-tier streamer,
#     cohort prefetch on/off bit-transparent, participation x offload
#     composition bit-identical across host/disk tiers AND
#     replicated/--server_shard planes, the gather(t+1)-before-
#     finish_round(t) structural overlap assert under the strict
#     zero-host-sync audit, disk-tier mid-epoch crash->resume
#     bit-exactness, and the 10^6-client RSS bound
#     (tests/test_host_offload.py — non-slow tier);
#   - the storage-fault-tolerant offload data plane
#     (docs/fault_tolerance.md §storage faults): seeded transient
#     eio/short/torn/stall injection BIT-invisible below the retry
#     budget (store-level AND e2e through cv_train on the forced disk
#     tier), the watchdog deadline turning a hung op into one actionable
#     error, row quarantine's counted degradation, the full persistent-
#     fault ladder (retries -> quarantine -> watch-forced checkpoint ->
#     terminal error) reproduced from the JSONL log alone, coalesced-
#     vs-per-row gather bit-identity, bounded-queue + close-report
#     shutdown hygiene (tests/test_io_faults.py);
#   - the end-to-end integrity plane (docs/fault_tolerance.md §silent
#     corruption): per-row checksum round trips (holes, coalesced
#     blocks, scatter RMW), checksums-on BIT-identical to checksums-off
#     on the clean path (store-level AND e2e), seeded silent flip/storn
#     injection detected on every verified read with the repair ladder
#     behind it (verifying re-read -> bit-exact .rows-snapshot repair ->
#     quarantine), the bounded background scrubber finding cold-row
#     corruption before a snapshot inherits it, and the flip e2e's
#     detection story reproduced from the JSONL alone
#     (tests/test_integrity.py);
#   - the self-healing supervisor (docs/fault_tolerance.md
#     §self-healing supervisor): crash + hang (heartbeat deadline)
#     detection and relaunch with --resume auto, bounded restart budget
#     + exponential backoff, poison-checkpoint exclusion through the
#     find_resume_checkpoint exclude seam (skip reasons logged), the
#     shared profiling.parse_heartbeat format, supervisor JSONL rendered
#     by obs_report (tests/test_supervise.py — the real SIGKILL/SIGSTOP/
#     silent-corruption recovery drill is its @slow crash-matrix leg);
#   - asynchronous buffered federation (--async_buffer K,
#     docs/async.md): the engine's buffered K-fold trajectory
#     bit-identical to a hand-computed twin applying the exact
#     jitted-helper fold sequence on BOTH server planes (incl. the
#     buffered-dispatch-consumes-no-model-RNG contract), exact
#     fold-counted staleness from version tags (Δ = server_version -
#     version_read, not wall-clock), per-contribution finiteness
#     masking with the all-masked fold degrading to a zero update,
#     mid-buffer checkpoint/resume bit-exactness through the part/*
#     seam, async-off fp32 bit-identity across both planes x both
#     epilogues (parity row A21), the contributions == folded +
#     async_expired + expired conservation audit reproduced from the
#     telemetry JSONL alone, the strict zero-host-sync audit with
#     buffering + folds in flight, and the heartbeat buf/stale fields
#     feeding supervise.py --max-stale (tests/test_async.py);
#   - the multi-host data plane (docs/multihost.md): the virtual 2D
#     (clients x shard) mesh bit-identical to the 1D mesh under the fp32
#     plan (round step, engine dispatch, checkpoint restore ACROSS mesh
#     shapes), the per-mesh-axis --collective_plan grammar
#     (uplink=ici:fp32/dcn:int8) resolving/validating at startup with
#     hierarchical lowering + per-level EF-carry conservation pins
#     (tests/test_compressed_collectives.py §7), the 2-process cohort
#     restart unit (tests/test_supervise.py TestCohortSupervise), the
#     ledger's >= 3.99x DCN-byte acceptance ratio with ICI bytes
#     unchanged, and run_start mesh-topology telemetry rendered by
#     obs_report (tests/test_multihost.py — the REAL 2-process
#     jax.distributed legs gate on a jaxlib whose CPU backend compiles
#     multi-process computations);
#   - multi-tenant run packing (scripts/orchestrate.py, docs/packing.md):
#     bounded fair-share admission (deterministic FIFO under
#     --max-concurrent), the cache-warmup admission gate (first tenant
#     exclusive until its first heartbeat; the second identical jax
#     tenant observes a warm shared cache), per-tenant restart isolation
#     (killing tenant 1 restarts ONLY tenant 1 with --resume auto while
#     tenants 0/2 heartbeat uninterrupted), the COMMEFFICIENT_RUN_DIR /
#     _TENANT_ID namespace seams (make_logdir pinning, per-tenant
#     checkpoint/state dirs), the --max-lead SIGSTOP/SIGCONT fair-share
#     throttle, and the fleet JSONL conservation audit (admitted ==
#     finished + gave_up + in_flight) rendered from the log alone by
#     obs_report --fleet (tests/test_packing.py — the real packed-vs-
#     sequential cv_train drill with bit-identity is its @slow
#     TestPackingBench leg);
#   - the always-on service plane (docs/service.md): the --churn grammar
#     + RowDirectory lifecycle (allocate/retire/compact with hole reuse
#     as fresh zero state), the seeded PopulationManager trajectory
#     (deterministic events + the registered == active + departed +
#     quarantined conservation audit, bit-exact pop/* state round trip,
#     spec-change warn), the loader's open-vs-closed-world pad-lane id,
#     SnapshotTracker handoff over crafted checksummed run states
#     (monotone model_version, torn-candidate skip, pin lease) with
#     prune_run_states never GCing a pinned checkpoint, the
#     ServingReplica request plane, and the obs_report Churn/Serving
#     sections rebuilt from the JSONL alone (tests/test_service.py — the
#     disk-tier churn e2e with mid-churn SIGKILL/resume bit-identity and
#     the live-replica bit-identity leg are its @slow TestServiceE2E
#     legs).
# Any extra args are passed through to pytest (e.g. -k bit_identical).
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu \
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m pytest tests/test_sharded_server.py tests/test_fused_epilogue.py \
    tests/test_stream_sketch.py tests/test_sketch_coalesce.py \
    tests/test_telemetry.py tests/test_watch.py \
    tests/test_compressed_collectives.py \
    tests/test_participation.py tests/test_host_offload.py \
    tests/test_io_faults.py tests/test_integrity.py \
    tests/test_supervise.py tests/test_multihost.py \
    tests/test_async.py tests/test_packing.py tests/test_service.py \
    -q -m "not slow" -p no:cacheprovider "$@"
