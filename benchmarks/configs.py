"""The five BASELINE.json benchmark configurations.

| # | Config                                               | Tier     |
|---|------------------------------------------------------|----------|
| 1 | 2-rank send/recv ping-pong, fp32                     | emulator |
| 2 | 8-rank ring all-reduce, fp32, 1 KiB-256 MiB sweep    | mesh     |
| 3 | all-gather + reduce-scatter, fp16/bf16 wire lanes    | mesh     |
| 4 | 32-rank tree bcast/scatter/gather over a 2D mesh     | mesh     |
| 5 | DP gradient all-reduce, Llama-3-8B bucketed grads    | mesh     |

Each runner emits a SweepResult (CSV rows); the CLI writes them under an
output directory for benchmarks.elaborate. "mesh" runs use every device
of the default platform (virtual CPU mesh in tests, real chips on TPU) —
sizes auto-scale down on the CPU emulation platform so CI stays fast.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


from accl_tpu.parallel import make_mesh
from .sweep import SweepResult, sweep_collective


def _size_sweep(lo: int, hi: int, stride: int = 4) -> list[int]:
    """Geometric size ladder from lo, always ending exactly at hi."""
    out = []
    n = lo
    while n < hi:
        out.append(n)
        n *= stride
    out.append(hi)
    return out


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


# bf16 MXU peak of the local chip (v5e-class: ~197 TFLOP/s, the same
# public spec family as roofline.LOCAL_HBM_SPEC_GBS's 819 GB/s HBM);
# denominator of the attention sweep's MFU column
LOCAL_BF16_PEAK_TFLOPS = 197.0


def config1_pingpong(sizes=None, world=2, backend: str = "emu",
                     stack: str = "tcp") -> SweepResult:
    """Send/recv ping-pong latency (fp32) on a CPU tier.

    ``backend``: "emu" = in-process emulated device (the reference's
    cclo_emu analog), "daemon" = Python rank daemons over the socket
    protocol, "native" = the C++ rank daemons (build: make -C native) —
    the out-of-process tiers pay the wire, the native one shows the
    C++ engine's latency floor. ``stack`` selects the daemon eth fabric
    (tcp or udp, the reference's dual-stack axis); the emu tier has no
    wire and ignores it."""
    import concurrent.futures

    sizes = sizes or _size_sweep(64, 1 << 20)
    procs = []
    if backend == "emu":
        from accl_tpu.testing import emu_world
        accls = emu_world(world, bufsize=max(sizes) + 64)
    elif backend == "daemon":
        from accl_tpu.testing import sim_world
        accls = sim_world(world, bufsize=max(sizes) + 64, stack=stack)
    elif backend == "native":
        import os
        import subprocess

        from accl_tpu.testing import connect_world, free_port_base
        binary = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "native", "cclo_emud")
        if not os.path.exists(binary):
            raise FileNotFoundError("native daemon not built "
                                    "(make -C native)")
        port_base = free_port_base()
        procs = [subprocess.Popen(
            [binary, "--rank", str(r), "--world", str(world),
             "--port-base", str(port_base), "--stack", stack,
             "--bufsize", str(max(sizes) + 64)])
            for r in range(world)]
        try:
            accls = connect_world(port_base, world)
        except Exception:
            # a daemon that failed to bind/start must not outlive the
            # failed run holding its port block
            for p in procs:
                p.kill()
                p.wait()
            raise
    else:
        raise ValueError(f"unknown backend {backend!r}")
    a0, a1 = accls[0], accls[1]
    pool = concurrent.futures.ThreadPoolExecutor(2)
    algo = backend if (stack == "tcp" or backend == "emu") \
        else f"{backend}-{stack}"
    try:
        return _pingpong_rows(a0, a1, pool, sizes, world,
                              algorithm=algo,
                              tier="emulator" if backend == "emu"
                              else "daemon")
    finally:
        for a in accls:
            a.deinit()
        for p in procs:
            p.kill()
            p.wait()
        pool.shutdown(wait=False)


def _pingpong_rows(a0, a1, pool, sizes, world,
                   algorithm: str = "emu",
                   tier: str = "emulator") -> SweepResult:
    """Steady-state ping-pong: each rank loops its send/recv sequence
    inside one long-lived thread (the reference's chained-iteration
    method, test.py:923-1156) so per-iteration harness dispatch does not
    pollute the latency floor."""
    import time as _time

    rows = []
    for nbytes in sizes:
        count = nbytes // 4
        s0 = a0.buffer(data=np.ones(count, np.float32))
        r0 = a0.buffer((count,), np.float32)
        s1 = a1.buffer(data=np.ones(count, np.float32))
        r1 = a1.buffer((count,), np.float32)

        def pair(iters):
            def rank0():
                for _ in range(iters):
                    a0.send(s0, count, dst=1, tag=7)
                    a0.recv(r0, count, src=1, tag=9)

            def rank1():
                for _ in range(iters):
                    a1.recv(r1, count, src=0, tag=7)
                    a1.send(s1, count, dst=0, tag=9)

            f0 = pool.submit(rank0)
            f1 = pool.submit(rank1)
            f0.result(120)
            f1.result(120)

        iters = max(10, min(200, (1 << 22) // max(nbytes, 1)))
        pair(3)  # warmup
        samples = []
        for _ in range(5):
            t0 = _time.perf_counter()
            pair(iters)
            samples.append((_time.perf_counter() - t0) / iters)
        t = float(np.median(samples)) / 2  # one-way
        rows.append({
            "collective": "sendrecv", "algorithm": algorithm,
            "world": world,
            "dtype": "float32", "wire_dtype": "", "nbytes": nbytes,
            "seconds_per_op": t, "bus_gbps": round(nbytes / t / 1e9, 4),
            "tier": tier,
        })
    return SweepResult(rows)


def config2_allreduce_sweep(sizes=None, algorithm: str = "xla"
                            ) -> SweepResult:
    hi = (1 << 22) if _is_cpu() else (1 << 28)
    sizes = sizes or _size_sweep(1 << 10, hi)
    mesh = make_mesh()
    return sweep_collective(mesh, "allreduce", sizes, algorithm=algorithm,
                            tier="mesh")


def config3_compressed(sizes=None) -> SweepResult:
    hi = (1 << 22) if _is_cpu() else (1 << 27)
    sizes = sizes or _size_sweep(1 << 12, hi)
    mesh = make_mesh()
    rows = []
    for op in ("allgather", "reduce_scatter"):
        for wire in ("bfloat16", "float16"):
            r = sweep_collective(mesh, op, sizes, algorithm="ring",
                                 wire_dtype=wire, tier="mesh")
            rows.extend(r.rows)
    return SweepResult(rows)


def config4_tree(sizes=None) -> SweepResult:
    hi = (1 << 22) if _is_cpu() else (1 << 26)
    sizes = sizes or _size_sweep(1 << 12, hi)
    ndev = len(jax.devices())
    if ndev >= 32:
        shape = (8, 4)
    elif ndev >= 8:
        shape = (4, 2)
    else:
        shape = (2, 2) if ndev >= 4 else (2, 1)
    mesh = make_mesh(shape, ("outer", "inner"))
    rows = []
    for op in ("bcast", "scatter", "gather"):
        r = sweep_collective(mesh, op, sizes, algorithm="tree",
                             tier="mesh")
        rows.extend(r.rows)
    return SweepResult(rows)


def config5_llama_grads(bucket_bytes: int = 25 << 20) -> SweepResult:
    """Bucketed DP gradient all-reduce on Llama-shaped gradients.

    CPU emulation uses the tiny geometry; on real multi-chip hardware the
    full Llama-3-8B parameter set is used (32 GB of fp32 gradients spread
    over the DP axis as replicas — per-chip memory holds one replica, as
    in DDP).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accl_tpu.models import Llama, LlamaConfig
    from accl_tpu.parallel import bucketed_allreduce, make_bucket_plan

    from .timing import slope_time

    mesh = make_mesh(axis_names=("dp",))
    W = mesh.shape["dp"]
    if _is_cpu():
        config = LlamaConfig.tiny(dim=128, n_layers=4, n_heads=4,
                                  n_kv_heads=4, ffn_dim=256)
        bucket_bytes = 64 << 10
    else:
        config = (LlamaConfig.llama3_8b() if W > 1
                  else LlamaConfig.tiny(dim=1024, n_layers=8, n_heads=16,
                                        n_kv_heads=16, ffn_dim=4096))
    model = Llama(config)
    shapes = jax.eval_shape(lambda k: model.init(k), jax.random.key(0))
    plan = make_bucket_plan(shapes, bucket_bytes)
    total = plan.total_bytes

    # grads replicated per rank: leading dp axis, same bytes per chip
    grads = jax.tree.map(
        lambda s: jax.device_put(
            jnp.full((W,) + s.shape, 1e-3, s.dtype),
            NamedSharding(mesh, P("dp"))), shapes)

    def make_chain(K):
        def shard_fn(g):
            local = jax.tree.map(lambda x: x[0], g)

            def body(i, acc):
                return bucketed_allreduce(acc, "dp", plan=plan)

            out = jax.lax.fori_loop(0, K, body, local)
            leaf = jax.tree.leaves(out)[0]
            return jnp.sum(leaf.reshape(-1)[:1])[None]

        from jax.sharding import PartitionSpec as P2
        f = jax.shard_map(shard_fn, mesh=mesh, in_specs=P2("dp"),
                          out_specs=P2("dp"), check_vma=False)
        return jax.jit(lambda v: f(v)[0])

    t = slope_time(make_chain, (grads,), k_lo=2, k_hi=8, reps=3)
    gbps = 2 * (W - 1) / W * total / t / 1e9
    row = {
        "collective": "bucketed_grad_allreduce", "algorithm": "xla",
        "world": W, "dtype": "float32", "wire_dtype": "",
        "nbytes": total, "seconds_per_op": t,
        "bus_gbps": round(gbps, 4), "tier": "mesh",
    }
    return SweepResult([row])


def _chip_slope(mk, args, work: float, assumed_rate: float,
                cap: int = 2_000_000, floor: int = 4,
                cpu_k: tuple[int, int] | None = None) -> float:
    """Shared chain-length policy + clamped-slope retry for the chip
    sweeps. The chain targets ~50 ms of device work at ``assumed_rate``
    (work units/s for ``work`` units/op) so the slope clears host
    noise; a clamped (<= 2 ns) slope means transient noise beat the
    chain, so retry once with a 4x longer one — k points must stay
    distinct even at the cap, else the polyfit is rank-deficient and
    returns a bogus slope. ``cpu_k`` pins a minimal functional chain on
    the CPU tier (interpreted Pallas: a smoke run, not a bandwidth
    claim)."""
    from .timing import slope_time

    if cpu_k is not None and _is_cpu():
        return slope_time(mk, args, k_lo=cpu_k[0], k_hi=cpu_k[1])
    k_hi = int(min(cap, max(9 * floor, 0.05 * assumed_rate / work)))
    t = slope_time(mk, args, k_lo=max(floor, k_hi // 9), k_hi=k_hi)
    if t <= 2e-9:
        hi2 = min(cap, 4 * k_hi)
        t = slope_time(mk, args, k_lo=max(floor, hi2 // 9), k_hi=hi2)
    return t


def chip_combine_sweep(sizes=None) -> SweepResult:
    """Single-device size sweep of the combine dataplane (the reduce_sum
    plugin equivalent): the Pallas VPU kernel vs the raw XLA elementwise
    op, 4 KiB - 256 MiB. This is the real-chip curve behind bench.py's
    single 256 MiB point; traffic per iteration = 3x nbytes (read acc +
    read y + write acc)."""
    from accl_tpu.constants import ReduceFunc
    from accl_tpu.ops.combine import combine_pallas

    hi = (1 << 22) if _is_cpu() else (1 << 28)
    sizes = sizes or _size_sweep(1 << 12, hi)
    tier = f"{jax.default_backend()}-chip"
    rows = []
    for nbytes in sizes:
        # whole 1024-lane fp32 rows; report the EFFECTIVE size so odd
        # --sizes values cannot inflate bus_gbps via silent truncation
        n = max(1, nbytes // 4096) * 1024
        nbytes = n * 4
        cols = 1024
        a = jax.random.normal(jax.random.key(0), (n // cols, cols),
                              jnp.float32)
        b = jax.random.normal(jax.random.key(1), (n // cols, cols),
                              jnp.float32)

        def make_pallas(K):
            @jax.jit
            def f(x, y):
                def body(i, acc):
                    return combine_pallas(acc, y, ReduceFunc.SUM)
                return jax.lax.fori_loop(0, K, body, x)[0, 0]
            return f

        def make_xla(K):
            @jax.jit
            def f(x, y):
                def body(i, acc):
                    return acc + y
                return jax.lax.fori_loop(0, K, body, x)[0, 0]
            return f

        # working sets that fit VMEM run at multi-TB/s (no HBM trips), so
        # the assumed rate — hence the chain length — scales with regime
        # or small ops stay flat across K and the slope is garbage
        assumed = 5e12 if 3 * nbytes < (100 << 20) else 1e12
        for algo, mk in (("pallas", make_pallas), ("xla", make_xla)):
            t = _chip_slope(mk, (a, b), 3 * nbytes, assumed)
            rows.append({
                "collective": "combine", "algorithm": algo, "world": 1,
                "dtype": "float32", "wire_dtype": "", "nbytes": nbytes,
                "seconds_per_op": t,
                "bus_gbps": round(3 * nbytes / t / 1e9, 4),
                "tier": tier,
            })
    return SweepResult(rows)


def chip_attention_sweep(seqs=None) -> SweepResult:
    """Single-device sequence-length sweep of the fused attention kernel
    (ops/attention.flash_attention, the compute half of the long-context
    story) against the same math as a plain XLA program that materializes
    the (Sq, Skv) score matrix. Causal, bf16 activations, fp32 softmax.

    nbytes = the kernel's minimum HBM traffic (Q+K+V+O); bus_gbps = that
    traffic over the measured seconds_per_op, so rows stay comparable to
    the other dataplane curves. The table's pallas-vs-xla gap at long
    sequence is the win from never writing scores to HBM."""
    from accl_tpu.ops.attention import flash_attention

    H, D = 8, 128
    seqs = seqs or ([256, 1024] if _is_cpu()
                    else [512, 1024, 2048, 4096, 8192])
    tier = f"{jax.default_backend()}-chip"
    rows = []
    for S in seqs:
        # the XLA baseline materializes a (B, H, S, S) fp32 score tensor;
        # shrink batch at long sequence so it stays on-chip (the per-row
        # nbytes column reflects the actual shapes)
        B = max(1, min(4, 8192 // S))
        key = jax.random.key(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, H, S, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, H, S, D), jnp.bfloat16)
        nbytes = 4 * B * H * S * D * 2  # Q+K+V+O in bf16

        def xla_attn(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32)
            s = s * (float(D) ** -0.5)
            qpos = jnp.arange(S)[:, None]
            kpos = jnp.arange(S)[None, :]
            s = jnp.where(kpos <= qpos, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p.astype(jnp.bfloat16), v)

        def make_pallas(K):
            @jax.jit
            def f(q, k, v):
                def body(i, acc):
                    return flash_attention(acc, k, v, causal=True)
                out = jax.lax.fori_loop(0, K, body, q)
                return out[0, 0, 0, 0].astype(jnp.float32)
            return f

        def make_xla(K):
            @jax.jit
            def f(q, k, v):
                def body(i, acc):
                    return xla_attn(acc, k, v)
                out = jax.lax.fori_loop(0, K, body, q)
                return out[0, 0, 0, 0].astype(jnp.float32)
            return f

        # ~2*B*H*S^2*D useful FLOPs per op (causal halves the 4x matmul
        # count); assume a conservative 50 TFLOP/s for the chain budget
        flops = 2 * B * H * S * S * D
        for algo, mk in (("pallas", make_pallas), ("xla", make_xla)):
            t = _chip_slope(mk, (q, k, v), flops, 50e12, cap=20_000,
                            floor=2, cpu_k=(1, 3))
            # S in the label: batch shrinks as sequence grows, so rows
            # at different S can share nbytes and must not aggregate
            tfl = flops / t / 1e12
            rows.append({
                "collective": f"attention_causal_s{S}", "algorithm": algo,
                "world": 1, "dtype": "bfloat16", "wire_dtype": "",
                "nbytes": nbytes, "seconds_per_op": t,
                "bus_gbps": round(nbytes / t / 1e9, 4), "tier": tier,
                # MFU vs bf16 peak is the headline column on chip; the
                # CPU tier's interpreted smoke run leaves it blank
                "tflops": round(tfl, 2),
                "mfu": ("" if _is_cpu()
                        else round(tfl / LOCAL_BF16_PEAK_TFLOPS, 4)),
            })
    return SweepResult(rows)


def chip_decode_sweep(kvlens=None) -> SweepResult:
    """Single-device KV-cache decode sweep: the fused ``flash_decode``
    kernel (cache-native layout, dynamic fill length) vs an XLA einsum
    that attends over the whole max_len cache — the cost model decode
    pays without a length-aware kernel. Decode is HBM-bound: the floor
    per step is reading the FILLED K/V prefix once, so bus_gbps = that
    prefix's bytes over the measured step time, directly comparable to
    the chip's HBM curve (chip_combine.csv). A second 'tokens/s' row per
    fill level reports B / step for throughput readers."""
    from accl_tpu.ops.attention import flash_decode

    # CPU tier = interpreted-Pallas functional smoke, so shapes shrink
    # hard (the real curve needs the chip)
    B, H, Hkv, D = (2, 8, 2, 64) if _is_cpu() else (8, 32, 8, 128)
    T = 128 if _is_cpu() else 8192
    kvlens = kvlens or ([32, 128] if _is_cpu()
                        else [512, 2048, 8192])
    tier = f"{jax.default_backend()}-chip"
    kk = jax.random.split(jax.random.key(0), 3)
    kc = jax.random.normal(kk[0], (B, Hkv, T, D), jnp.bfloat16)
    vc = jax.random.normal(kk[1], (B, Hkv, T, D), jnp.bfloat16)
    q = jax.random.normal(kk[2], (B, H, 1, D), jnp.bfloat16)

    def xla_decode(q, kc, vc, kvlen):
        # length-oblivious baseline: repeated-KV einsum over max_len
        rep = H // Hkv
        kt = jnp.repeat(kc, rep, 1)
        vt = jnp.repeat(vc, rep, 1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kt,
                       preferred_element_type=jnp.float32)
        s = s * (float(D) ** -0.5)
        s = jnp.where(jnp.arange(T)[None, None, None] < kvlen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(jnp.bfloat16), vt)

    rows = []
    for kvlen in kvlens:
        n = jnp.int32(kvlen)
        # HBM floor: read the filled K+V prefix once per step
        nbytes = 2 * B * kvlen * Hkv * D * 2

        def make_pallas(K):
            @jax.jit
            def f(q, kc, vc, n):
                def body(i, acc):
                    o = flash_decode(acc, kc, vc, n)
                    return o
                out = jax.lax.fori_loop(0, K, body, q)
                return out[0, 0, 0, 0].astype(jnp.float32)
            return f

        def make_xla(K):
            @jax.jit
            def f(q, kc, vc, n):
                def body(i, acc):
                    return xla_decode(acc, kc, vc, n)
                out = jax.lax.fori_loop(0, K, body, q)
                return out[0, 0, 0, 0].astype(jnp.float32)
            return f

        for algo, mk in (("pallas", make_pallas), ("xla", make_xla)):
            t = _chip_slope(mk, (q, kc, vc, n), nbytes, 200e9,
                            cap=50_000, floor=2, cpu_k=(1, 3))
            rows.append({
                "collective": f"decode_kv{kvlen}", "algorithm": algo,
                "world": 1, "dtype": "bfloat16", "wire_dtype": "",
                "nbytes": nbytes, "seconds_per_op": t,
                "bus_gbps": round(nbytes / t / 1e9, 4), "tier": tier,
            })
            rows.append({
                "collective": f"decode_kv{kvlen}_tput", "algorithm": algo,
                "world": 1, "dtype": "bfloat16", "wire_dtype": "",
                "nbytes": nbytes, "seconds_per_op": t,
                "bus_gbps": round(B / t, 2), "units": "tokens/s",
                "tier": tier,
            })
    return SweepResult(rows)


def chip_compression_sweep(sizes=None) -> SweepResult:
    """Single-device size sweep of the wire-compression lanes (the
    fp_hp/hp_fp_stream_conv plugin equivalents plus the scaled-fp8
    codec): a full encode+decode round trip per iteration, Pallas lanes
    vs the same math as plain XLA ops.

    nbytes = the fp32 payload; bus_gbps counts the round trip's actual
    HBM traffic (read fp32 + write wire + read wire + write fp32 =
    (8 + 2*wire_size) bytes/element) so lanes of different wire widths
    stay comparable."""
    from accl_tpu.ops.compression import (cast_lane, compress_fp8,
                                          decompress_fp8, fp8_dequantize,
                                          fp8_quantize)

    hi = (1 << 22) if _is_cpu() else (1 << 27)
    sizes = sizes or _size_sweep(1 << 14, hi)
    tier = f"{jax.default_backend()}-chip"

    # The XLA baselines put an optimization barrier between encode and
    # decode: without it XLA fuses the round trip into one kernel that
    # never materializes the wire tensor — but a wire codec MUST
    # materialize it (that is the payload that ships), so the fused form
    # would be an apples-to-oranges baseline. Note the fp16 lane lowers
    # to the XLA cast by design (f16 is not Mosaic-native; see
    # ops/combine._MOSAIC_DTYPES), so its two rows measure the same code
    # modulo the barrier.
    def fp16_pallas(x):
        return cast_lane(cast_lane(x, jnp.float16), jnp.float32)

    def fp16_xla(x):
        w = jax.lax.optimization_barrier(x.astype(jnp.float16))
        return w.astype(jnp.float32)

    def bf16_pallas(x):
        return cast_lane(cast_lane(x, jnp.bfloat16), jnp.float32)

    def bf16_xla(x):
        w = jax.lax.optimization_barrier(x.astype(jnp.bfloat16))
        return w.astype(jnp.float32)

    def fp8_pallas(x):
        q, scale = compress_fp8(x)
        return decompress_fp8(q, scale)

    def fp8_xla(x):
        q, scale = jax.lax.optimization_barrier(
            fp8_quantize(x, jnp.float8_e4m3fn))
        return fp8_dequantize(q, scale)

    lanes = [("clane_fp16", 2, fp16_pallas, fp16_xla),
             ("clane_bf16", 2, bf16_pallas, bf16_xla),
             ("clane_fp8", 1, fp8_pallas, fp8_xla)]
    rows = []
    for nbytes in sizes:
        n = max(1, nbytes // 4096) * 1024
        nbytes = n * 4
        x = jax.random.normal(jax.random.key(0), (n // 1024, 1024),
                              jnp.float32)
        for name, wire_size, pallas_fn, xla_fn in lanes:
            traffic = n * (8 + 2 * wire_size)

            def make_chain(roundtrip):
                def mk(K):
                    @jax.jit
                    def f(x):
                        def body(i, acc):
                            return roundtrip(acc)
                        return jax.lax.fori_loop(0, K, body, x)[0, 0]
                    return f
                return mk

            for algo, fn in (("pallas", pallas_fn), ("xla", xla_fn)):
                t = _chip_slope(make_chain(fn), (x,), traffic, 1e12,
                                cap=500_000, cpu_k=(2, 6))
                rows.append({
                    "collective": name, "algorithm": algo, "world": 1,
                    "dtype": "float32", "wire_dtype": "",
                    "nbytes": nbytes, "seconds_per_op": t,
                    "bus_gbps": round(traffic / t / 1e9, 4), "tier": tier,
                })
    return SweepResult(rows)


def chip_llama_sweep() -> SweepResult:
    """Model-family throughput on one chip: Llama train step (fwd + bwd +
    adamw) and KV-cache decode. The rows carry tokens/s in the bus_gbps
    column, marked ``units=tokens/s`` so aggregators keep them apart
    from bandwidth rows.

    CPU tier runs the tiny geometry as a functional smoke."""
    import optax

    from accl_tpu.models import Llama, LlamaConfig

    from .timing import slope_time

    import dataclasses as _dc

    if _is_cpu():
        config = LlamaConfig.tiny()
        B, S = 2, 32
        dec_prompt, dec_hi = 8, 6
    else:
        # ~200M-param single-chip geometry: fits fp32 train state + seq
        # 1024 activations comfortably in one chip's HBM
        config = LlamaConfig(vocab_size=32000, dim=1024, n_layers=12,
                             n_heads=16, n_kv_heads=8, ffn_dim=2816,
                             max_seq_len=2048)
        B, S = 8, 1024
        dec_prompt, dec_hi = 64, 72
    # Mixtral-style sibling: same geometry with a routed 4-expert FFN
    # (top-2) — the second model family's train-throughput row
    moe_config = _dc.replace(config, n_experts=4, moe_top_k=2)
    model = Llama(config)
    params = model.init(jax.random.key(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    optimizer = optax.adamw(1e-4)
    opt_state = optimizer.init(params)
    train = model.make_train_step(optimizer)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, config.vocab_size, (B, S)), jnp.int32)
    tier = f"{jax.default_backend()}-chip"
    rows = []

    def train_chain(step_fn):
        """Chained train-step benchmark factory (shared by the dense and
        MoE rows so the chaining pattern cannot diverge)."""
        def mk(K):
            @jax.jit
            def f(params, opt_state, tokens):
                def body(i, c):
                    p, o = c
                    p, o, _ = step_fn(p, o, tokens)
                    return (p, o)
                p, _ = jax.lax.fori_loop(0, K, body, (params, opt_state))
                return jax.tree.leaves(p)[0].reshape(-1)[0]
            return f
        return mk

    t = slope_time(train_chain(train), (params, opt_state, tokens),
                   k_lo=2, k_hi=8, reps=3)
    model_dtype = str(np.dtype(config.dtype))
    rows.append({
        "collective": "llama_train_step", "algorithm": "chip", "world": 1,
        "dtype": model_dtype, "wire_dtype": "", "nbytes": B * S,
        "seconds_per_op": t, "bus_gbps": round(B * S / t, 1),
        "units": "tokens/s", "tier": tier,
    })
    log_tr = (f"train: {B * S / t:.0f} tokens/s "
              f"({6 * n_params * B * S / t / 1e12:.1f} TFLOP/s, "
              f"{n_params / 1e6:.0f}M params)")

    # decode: greedy single-token steps against a growing KV cache
    cache = model.init_kv_cache(B, dec_prompt + dec_hi + 8)
    logits, cache = model._jit_forward_cached()(
        params, tokens[:, :dec_prompt], cache)
    tok = jnp.argmax(logits[:, -1:], axis=-1)

    def mk_dec(K):
        @jax.jit
        def f(params, tok, cache):
            def body(i, c):
                tk, ca = c
                lg, ca = model.forward_cached(params, tk, ca)
                return (jnp.argmax(lg[:, -1:], axis=-1), ca)
            tk, _ = jax.lax.fori_loop(0, K, body, (tok, cache))
            return tk[0, 0]
        return f

    t = slope_time(mk_dec, (params, tok, cache),
                   k_lo=max(2, dec_hi // 9), k_hi=dec_hi, reps=3)
    rows.append({
        "collective": "llama_decode", "algorithm": "chip", "world": 1,
        "dtype": model_dtype, "wire_dtype": "", "nbytes": B,
        "seconds_per_op": t, "bus_gbps": round(B / t, 1),
        "units": "tokens/s", "tier": tier,
    })
    print(log_tr)
    print(f"decode: {B / t:.0f} tokens/s at batch {B}")

    # Mixtral-style MoE sibling: the second model family's
    # train-throughput row (same geometry, routed 4-expert FFN). Free
    # the dense model's train state + cache first — holding ~GBs of
    # dead references while the larger MoE state allocates could OOM or
    # fragment HBM mid-benchmark on smaller chips
    del params, opt_state, cache, tok, logits
    moe_model = Llama(moe_config)
    moe_params = moe_model.init(jax.random.key(1))
    moe_opt_state = optimizer.init(moe_params)
    moe_train = moe_model.make_train_step(optimizer)

    t = slope_time(train_chain(moe_train),
                   (moe_params, moe_opt_state, tokens),
                   k_lo=2, k_hi=8, reps=3)
    rows.append({
        "collective": "moe_llama_train_step", "algorithm": "chip",
        "world": 1, "dtype": model_dtype, "wire_dtype": "",
        "nbytes": B * S, "seconds_per_op": t,
        "bus_gbps": round(B * S / t, 1), "units": "tokens/s",
        "tier": tier,
    })
    print(f"moe train: {B * S / t:.0f} tokens/s "
          f"({moe_config.n_experts} experts, top-{moe_config.moe_top_k})")
    return SweepResult(rows)


CONFIGS = {
    1: config1_pingpong,
    2: config2_allreduce_sweep,
    3: config3_compressed,
    4: config4_tree,
    5: config5_llama_grads,
}
