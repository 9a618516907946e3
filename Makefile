# Convenience targets; every recipe is the same command the docs cite.
PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all test lint native native-check bench bench-emu chaos dryrun smoke csv tune

all: lint native   ## default flow: syntax gate first, then the native build

lint: native-check ## fast syntax gate + blocking/lane + shm-leak + pallas-import + metrics-catalog lints
	$(PY) -m compileall -q accl_tpu benchmarks tests
	$(PY) scripts/check_blocking.py
	$(PY) scripts/check_shm_leaks.py
	$(PY) scripts/check_pallas_import.py
	$(PY) scripts/check_metrics_catalog.py

native:            ## build the C++ rank daemon + host driver demo
	$(MAKE) -C native

native-check:      ## strict native gate: -Wall -Wextra -Werror syntax pass
	$(MAKE) -C native check-build

native-asan:       ## sanitizer build of the daemon (drive with the soak/demo)
	g++ -O1 -g -fsanitize=address,undefined -std=c++17 -Wall -pthread \
	    -o native/cclo_emud_asan native/cclo_emud.cpp

test: lint         ## full corpus on the 8-device virtual CPU mesh
	-$(MAKE) -C native  # best effort: corpus skips native tests if absent
	$(CPU_ENV) $(PY) -m pytest tests/ -q

tune:              ## emulator-tier algorithm sweep -> bench_out/tuning.json
	$(PY) -m benchmarks --tune --out bench_out

bench:             ## headline JSON line on the chip (exits non-zero without a TPU)
	$(PY) bench.py

bench-emu:         ## emulator-tier headline (<300s): executor + algorithm + plan-cache + hierarchical + multi-tenant saturation + disaggregated-serving + chaos-goodput + reshard-under-traffic + checksum-overhead + shm-dataplane + compiled-combine ladders; asserts streamed ≥1.2x over the SERIAL reference engine measured as paired rounds in the same process (self-relative since PR 14 — the old absolute vs-window ≥1.2 threshold failed on unmodified code on saturated hosts and is now a warning; serial-paired measures ~1.8-2.2x), log-depth ≥1.3x over ring at small messages, plan-cache ≥1.3x per-call on repeated small collectives, hierarchical ≥1.3x over flat ring on the slow-inter-tier 4 MiB allreduce (benchmarks/hierarchy.py), the N-tier ladder's 3-tier recursive program ≥1.8x over flat ring on a 3-tier beta gradient (4 chips x 2 racks, 0.2/0.02 GB/s boundaries) AND strictly faster than a FORCED two-tier lowering of the same call on the same devices (>1.0x no-collapse floor — the 2-core host caps the margin well under the cost model's prediction; measured ~3.5x vs flat / ~1.7x vs 2-tier) with the ladder hard-raising unless full-precision legs are bit-identical to the serial oracle, the per-tier-quantized leg (slow boundary tiers fp8 block-scaled, intra exact) lands inside the typed requantization bound, and a throttled 3-tier reshard holds the sampled shard+chunk memory bound (benchmarks/hierarchy.py headline3), 4-tenant Jain fairness ≥0.8 with concurrent aggregate ≥0.6x serialized (no-collapse floor — a fully CPU-bound 2-core emulator has no idle for overlap to reclaim; see benchmarks/saturation.py) and bounded small-call p99 under a 16 MiB storm, decode-step p99 ≤ max(75ms, solo + OS-noise floor) under a one-sided prefill KV storm with aggregate landed KV ≥0.05 GB/s (benchmarks/serving.py — the rendezvous-path rx-pool-isolation gate; measured ~8ms p99 / ~0.5 GB/s), the request-level serving control plane (KV-block cache + continuous batching + put-with-notify, benchmarks/serving.py request ladder) holding TTFT p99 ≤ max(2000ms, solo + floor) at saturation (measured ~130ms storm / ~20ms solo) with prefix-cache hit ratio >0 at ZERO wire bytes per hit, the notify poll loop issuing ZERO collective calls, a decode-rank-kill chaos cell completing typed-clean bit-identical to the fault-free oracle after shrink+requeue, and a mid-storm grow_communicator + block-cyclic KV-arena reshard landing bit-exact under the shard+chunk memory bound while moving a fraction of the gather-reshard-scatter oracle's elements, goodput ≥0.4x clean under seeded 1% frame loss with ZERO call errors (benchmarks/chaos.py — the reliability layer's recovery gate), elastic-membership reshards of a 4 MiB state completing p50 ≤500ms with a bystander tenant's p99 ≤ max(75ms, solo + floor) and zero errors (benchmarks/reshard.py — the membership-change-under-traffic gate; measured ~8ms reshard / ~11ms bystander p99), payload-checksum overhead ≤1.6x on the 16 MiB TCP-daemon allreduce csum-on/off pair (benchmarks/integrity.py — Tier-1 integrity must stay cheap enough to be on by default on the socket tier, whose fabrics checksum every frame; measured ~1.15x via hardware crc32c), shm-vs-TCP 16 MiB allreduce ≥1.0x (no-collapse floor, saturation-convention: the CPU-bound 2-core emulator bottlenecks both worlds on the Python executor and measures ~1.05-1.25x; a wire-dominated host should clear 2.0 — benchmarks/shm.py documents the GIL analysis) with the ladder hard-raising on oracle divergence or ANY integrity drop, compiled combine beating numpy dispatch ≥1.05x at its WORST 4-64 KiB segment size (measured 1.07-2x), fp8-block-scaled 16 MiB allreduce moving ≥3x fewer wire bytes than f32 AND winning ≥1.2x wall-clock on the wire-dominated link profile (benchmarks/quantize.py — measured ~3.9x bytes / ~1.8x time, f32 leg bit-exact, fp8 leg inside the typed per-hop error bound), the vectorized block-scale codec beating the scalar path ≥1.0x at its worse direction on the 16 MiB rung with bit-identical packed bytes (benchmarks/quantize.py codec microladder — never-lose floor; measured ~13x/direction on the AVX2 CI host, ~3-5x SSE2-only), the device-tier fused Pallas codec (interpret mode on CPU — the hardware path rides the chip queue, never CI) bit-identical to the quant.py reference with its per-hop wire payload (codes + scale sidecar) ≥3x smaller than f32 and ring numerics inside the typed bound (benchmarks/quantize.py device microladder; fp8×block-128 lands ~3.88x), compute-overlapped workloads (ring attention's double-buffered KV rotation + MoE's microbatched alltoallv dispatch/combine, benchmarks/workloads.py) hiding ≥0.45 of their in-flight communication behind their own matmuls on the throttled wire (measured ~0.7 — the GIL ceiling; serial contrast legs ~0.0-0.3; both legs hard-raise on oracle divergence, the fp8 dispatch leg inside its error bound; best-of-three like the other gates), AND zero fabric drop/corruption counters beyond the chaos ladder's declared injections (metrics_snapshot block rides the JSON line)
	ACCL_BENCH_TIER=emu ACCL_BENCH_MIN_STREAM_RATIO=1.2 ACCL_BENCH_MIN_RD_RATIO=1.3 ACCL_BENCH_MIN_PLANCACHE_RATIO=1.3 ACCL_BENCH_MIN_HIER_RATIO=1.3 ACCL_BENCH_MIN_HIER3_RATIO=1.8 ACCL_BENCH_MIN_FAIRNESS=0.8 ACCL_BENCH_MIN_AGG_RATIO=0.6 ACCL_BENCH_MAX_DECODE_P99_MS=75 ACCL_BENCH_MIN_KV_GBPS=0.05 ACCL_BENCH_MAX_TTFT_P99_MS=2000 ACCL_BENCH_MIN_CHAOS_GOODPUT=0.4 ACCL_BENCH_MAX_RESHARD_MS=500 ACCL_BENCH_MAX_RESHARD_BYST_P99_MS=75 ACCL_BENCH_MAX_CSUM_OVERHEAD=1.6 ACCL_BENCH_MIN_SHM_RATIO=1.0 ACCL_BENCH_MIN_COMBINE_RATIO=1.05 ACCL_BENCH_MIN_QUANT_WIRE_RATIO=3.0 ACCL_BENCH_MIN_QUANT_TIME_RATIO=1.2 ACCL_BENCH_MIN_CODEC_RATIO=1.0 ACCL_BENCH_MIN_DEVICE_QUANT_WIRE_RATIO=3.0 ACCL_BENCH_MIN_OVERLAP_FRAC=0.45 ACCL_BENCH_REQUIRE_CLEAN_FABRIC=1 JAX_PLATFORMS=cpu $(PY) bench.py

chaos:             ## seeded deterministic chaos sweep: every fault kind (incl. corrupt_payload — bit-flips only the checksum tier can catch) x algorithm x world through the reliability layer (+ shm-fabric cells for every kind through a shared-memory daemon world with drop cells asserting retransmission engaged and payload cells asserting integrity drops, an RMA rendezvous-lane payload-corrupt cell, the hier drop/payload cells plus 3-tier hier3 cells whose faults are CONFINED to the cross-rack (slowest-tier) directed pairs with retransmission/integrity engagement asserted there, uneven-alltoallv drop/payload cells (skewed count matrix with zero-count peers, bit-identical to the matrix oracle with retransmission/integrity engagement asserted), block-scaled quantized-wire cells — drop + payload corruption TARGETING the scale-header region via FaultRule.flip_at across ring/RD/hier, proving a corrupt scale recovers like a corrupt payload — the elastic kill→shrink→reshard→grow→reshard loop per kind, a heal_after flap-partition cell, and mixed py/native cells — a C++ cclo_emud rank 0 + python ranks at FULL default protocol with faults in both directions (seeded FaultPlan on the python senders, the daemon's deterministic $ACCL_TPU_CHAOS_TX_DROP/_CORRUPT knobs on the native one), bit-identical to a clean mixed world with engagement asserted on the native daemon's own retx/integrity counter dump), bit-identical to the serial/numpy oracles with integrity_failed_total>0 asserted on every payload-corrupt cell (scripts/chaos_sweep.py; $ACCL_TPU_CHAOS_SEED reproduces a run)
	JAX_PLATFORMS=cpu $(PY) scripts/chaos_sweep.py

dryrun:            ## multi-chip sharding dryrun on 8 virtual devices
	$(CPU_ENV) $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

smoke:             ## main path once on the chip: driver, codec, 8B-width model
	$(PY) chip_smoke.py

csv:               ## regenerate the CPU-tier BASELINE CSVs + aggregate
	$(PY) -m benchmarks --config 1 --out benchmarks/results
	$(PY) -m benchmarks --config 1 --backend daemon --tag daemon --platform cpu --out benchmarks/results
	$(PY) -m benchmarks --config 1 --backend native --tag native --platform cpu --out benchmarks/results
	$(PY) -m benchmarks --config 1 --backend daemon --stack udp --tag daemon_udp --platform cpu --out benchmarks/results
	$(PY) -m benchmarks --config 1 --backend native --stack udp --tag native_udp --platform cpu --out benchmarks/results
	$(CPU_ENV) $(PY) -m benchmarks --config 2 --platform cpu --tag xla --out benchmarks/results
	$(CPU_ENV) $(PY) -m benchmarks --config 2 --platform cpu --algorithm ring --tag ring --out benchmarks/results
	$(CPU_ENV) $(PY) -m benchmarks --config 3 --platform cpu --out benchmarks/results
	$(CPU_ENV) $(PY) -m benchmarks --config 4 --platform cpu --out benchmarks/results
	$(CPU_ENV) $(PY) -m benchmarks --config 5 --platform cpu --out benchmarks/results
	$(CPU_ENV) $(PY) -m benchmarks --sweep allreduce --algorithm ring --wire-dtype float8_e4m3fn --platform cpu --sizes 4096,65536,1048576,4194304 --tag fp8 --out benchmarks/results
	$(PY) -m benchmarks.chained --out benchmarks/results
	$(PY) -m benchmarks --elaborate benchmarks/results
