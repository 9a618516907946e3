#!/usr/bin/env python
"""Chip smoke: the main path once on a TPU, at the sizes users run.

With no option it runs three phases on one chip:

* driver — ``tpu_world(1)`` and the ``ACCL`` API: copy, combine (SUM,
  MAX), allreduce and bcast at 25 MiB (PyTorch DDP's default bucket) in
  f32 and bf16 and at 256 MiB in f32, on host-mirror and device-resident
  buffers, each checked bit-exactly against numpy;
* codec — the Pallas block-scaled codec (``bs_quantize``,
  ``bs_dequantize``, ``bs_combine_requant``) for fp8-e4m3, fp8-e5m2 and
  int8 at block 128 on 25 MiB of f32, bit-identical to ``quant.py``;
* model — ``Llama`` at Llama-3-8B widths cut to 4 layers (bf16 params):
  prefill B=2 x 1024 tokens, one cached decode step, then 16 greedy
  tokens through ``Llama.generate``; the prefill and decode-step
  logits checked against the dense attention path, and a control
  (kv heads misrouted) that the check must reject.

``--chips 4`` runs only the cross-chip phase on four chips: the dense
collectives through ``tpu_world(4)``, the fp8/int8 block-scaled ring
allreduce against the ``quant.py`` reference ring, and
``MeshCollectives.allreduce`` inside a user jit.

Every Pallas kernel a phase runs must show a ``tpu_custom_call`` in its
compiled HLO. Every phase runs; any failure exits non-zero and prints
no ``ok`` line, and a backend other than TPU is a failure. The last
line of stdout is ``{"ok": true, "device": {"platform", "kind",
"count"}}``; timings on earlier lines are information only.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax.sharding import NamedSharding

from accl_tpu import quant
from accl_tpu.constants import ReduceFunc
from accl_tpu.device.tpu import tpu_world
from accl_tpu.models.llama import Llama, LlamaConfig
from accl_tpu.ops import compression as comp
from accl_tpu.testing import run_ranks
from accl_tpu.utils.platform import use_compile_cache

MiB = 1 << 20
DDP_BUCKET = 25 * MiB
BF16 = np.dtype(ml_dtypes.bfloat16)

# logits of the fused-kernel model vs the dense-attention model, both
# bf16 end to end: max |diff| over max |dense logit|. The two paths
# round the softmax differently (f32 in the kernel, bf16 probabilities
# in the dense einsum). At 8B widths, 4 layers, the chip read 0.0100
# for the prefill and 0.0112 for a decode step, and 1.397 for a step
# with misrouted kv heads (PERF.md, PR 21): the limit is ~2x the
# largest sound reading.
LOGIT_TOL = 2.5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def _bits(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.itemsize])


def check_bits(got, want, what: str) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.dtype != want.dtype or got.shape != want.shape:
        raise AssertionError(f"{what}: got {got.dtype}{got.shape}, "
                             f"want {want.dtype}{want.shape}")
    bad = _bits(got) != _bits(want)
    if bad.any():
        i = int(np.argmax(bad.reshape(-1)))
        raise AssertionError(
            f"{what}: {int(bad.sum())}/{bad.size} elements differ, first "
            f"at {i}: {got.reshape(-1)[i]!r} != {want.reshape(-1)[i]!r}")


def check_close(got, want64, scale64, eps: float, what: str) -> None:
    """|got - want| <= eps * scale elementwise (f64 reference)."""
    err = np.abs(np.asarray(got, np.float64) - want64)
    bad = err > eps * scale64
    if bad.any():
        raise AssertionError(f"{what}: {int(bad.sum())} elements beyond "
                             f"{eps} x scale, max err {err.max()}")


def check_kernels(fn, *args, what: str) -> None:
    """On a TPU every Pallas kernel is Mosaic-compiled: the compiled HLO
    of ``fn`` must hold a ``tpu_custom_call``. (The CPU tier interprets
    the kernels, so there is nothing to find there.)"""
    if jax.default_backend() != "tpu":
        return
    text = jax.jit(fn).lower(*args).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: no tpu_custom_call in compiled HLO")


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def _steady(fn, *args, reps: int = 5):
    """(result, best seconds per call) of a warmed-up jitted call."""
    out = jax.block_until_ready(fn(*args))
    best = min(_timed(lambda: jax.block_until_ready(fn(*args)))[1]
               for _ in range(reps))
    return out, best


def _buffer(a, kind: str, v: np.ndarray):
    """A host-mirror or device-resident ACCL buffer holding ``v``."""
    if kind == "device":
        return a.buffer(data=jax.device_put(v, a.device.my_device))
    return a.buffer(data=v.copy())


def _read(a, kind: str, b) -> np.ndarray:
    """A buffer's contents; a device-resident result must live on the
    rank's own device."""
    if kind == "host":
        return b.data
    if b.jax.devices() != {a.device.my_device}:
        raise AssertionError(f"rank {a.rank} result on {b.jax.devices()}")
    return np.asarray(b.jax)


# -- driver ------------------------------------------------------------------

def phase_driver(seed: int, cases=((np.float32, DDP_BUCKET),
                                   (BF16, DDP_BUCKET),
                                   (np.float32, 256 * MiB))) -> None:
    """copy / combine / allreduce / bcast through ``tpu_world(1)``."""

    a = tpu_world(1)[0]
    rng = np.random.default_rng(seed)
    try:
        for dt, nbytes in cases:
            dt = np.dtype(dt)
            n = nbytes // dt.itemsize
            x = rng.standard_normal(n, np.float32).astype(dt)
            y = rng.standard_normal(n, np.float32).astype(dt)
            for kind in ("host", "device"):
                def buf(v=None):
                    return _buffer(a, kind, np.zeros(n, dt) if v is None
                                   else v)

                def host(b):
                    return _read(a, kind, b)

                tag = f"{dt.name} {nbytes // MiB} MiB {kind}"
                src, other = buf(x), buf(y)
                dst = buf()
                _, t = _timed(lambda: a.copy(src, dst))
                check_bits(host(dst), x, f"copy {tag}")
                times = [f"copy {t * 1e3:.1f}"]
                for func, ref in ((ReduceFunc.SUM, np.add),
                                  (ReduceFunc.MAX, np.maximum)):
                    out = buf()
                    _, t = _timed(lambda: a.combine(n, func, src, other,
                                                    out))
                    check_bits(host(out), ref(x, y),
                               f"combine {func.name} {tag}")
                    times.append(f"combine-{func.name} {t * 1e3:.1f}")
                out = buf()
                _, t = _timed(lambda: a.allreduce(src, out, n))
                check_bits(host(out), x, f"allreduce {tag}")
                times.append(f"allreduce {t * 1e3:.1f}")
                b = buf(x)
                _, t = _timed(lambda: a.bcast(b, n, root=0))
                check_bits(host(b), x, f"bcast {tag}")
                times.append(f"bcast {t * 1e3:.1f}")
                log(f"driver {tag}: ok (ms, first call incl. compile: "
                    f"{', '.join(times)})")
    finally:
        a.deinit()


# -- codec -------------------------------------------------------------------

def phase_codec(seed: int, nbytes: int = DDP_BUCKET,
                block: int = 128) -> None:
    """The block-scaled Pallas codec, bit-identical to quant.py."""

    rng = np.random.default_rng(seed)
    n = nbytes // 4
    # scale-mixed finite payloads: blocks from denormal-producing to
    # near-overflow scales
    x = (rng.standard_normal(n, np.float32)
         * np.float32(10.0) ** rng.integers(-20, 20, n).astype(np.float32))
    other = rng.standard_normal(n, np.float32)
    xd, od = jnp.asarray(x), jnp.asarray(other)
    for qd in (np.dtype(ml_dtypes.float8_e4m3fn),
               np.dtype(ml_dtypes.float8_e5m2), np.dtype(np.int8)):
        one, qmax = comp._bs_scalars(qd.name)

        def quantize(v, o, m):
            return comp.bs_quantize(v, qd, block, scalars=(o, m))

        def dequantize(q, s):
            return comp.bs_dequantize(q, s, block)

        def combine_requant(q, s, v, o, m):
            return comp.bs_combine_requant(q, s, v, ReduceFunc.SUM, qd,
                                           block, scalars=(o, m))

        (q, s), tq = _steady(jax.jit(quantize), xd, one, qmax)
        ref_s, ref_q = quant._np_quantize(x, qd, block)
        check_bits(s, ref_s, f"bs_quantize scales {qd.name}")
        check_bits(q, ref_q, f"bs_quantize codes {qd.name}")
        d, td = _steady(jax.jit(dequantize), q, s)
        ref_d = quant._np_dequant(ref_s, ref_q, block)
        check_bits(d, ref_d, f"bs_dequantize {qd.name}")
        (q2, s2), tc = _steady(jax.jit(combine_requant), q, s, od, one, qmax)
        ref_s2, ref_q2 = quant._np_quantize(np.add(other, ref_d), qd, block)
        check_bits(s2, ref_s2, f"bs_combine_requant scales {qd.name}")
        check_bits(q2, ref_q2, f"bs_combine_requant codes {qd.name}")
        check_kernels(quantize, xd, one, qmax, what=f"bs_quantize {qd.name}")
        check_kernels(dequantize, q, s, what=f"bs_dequantize {qd.name}")
        check_kernels(combine_requant, q, s, od, one, qmax,
                      what=f"bs_combine_requant {qd.name}")
        log(f"codec {qd.name} block {block} {nbytes // MiB} MiB: "
            f"bit-identical (ms per call after warm-up, host clock, "
            f"information only: quantize {tq * 1e3:.3f}, dequantize "
            f"{td * 1e3:.3f}, combine_requant {tc * 1e3:.3f})")


# -- model -------------------------------------------------------------------

def model_config(n_layers: int = 4):
    """Llama-3-8B widths, cut to ``n_layers``, bf16 params."""
    return dataclasses.replace(LlamaConfig.llama3_8b(), n_layers=n_layers,
                               param_dtype=jnp.bfloat16)


def phase_model(seed: int, config=None, batch: int = 2,
                prompt_len: int = 1024, new_tokens: int = 16) -> None:

    cfg = config or model_config()
    flash = Llama(cfg)
    dense = Llama(dataclasses.replace(cfg, attention="dense"))
    params = jax.jit(flash.init)(jax.random.key(seed))
    n_params = flash.param_count(params)
    prompt = jax.random.randint(jax.random.key(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab_size,
                                jnp.int32)
    max_len = prompt_len + new_tokens
    cache = flash.init_kv_cache(batch, max_len)
    log(f"model: dim {cfg.dim}, {cfg.n_heads}/{cfg.n_kv_heads} heads, ffn "
        f"{cfg.ffn_dim}, vocab {cfg.vocab_size}, {cfg.n_layers} layers, "
        f"{n_params} params ({n_params * 2 / 1e9:.2f} GB bf16)")

    # the prefill program generate() runs (fused flash_decode kernel)
    step = flash._jit_forward_cached()
    check_kernels(flash.forward_cached, params, prompt, cache,
                  what="forward_cached prefill (flash_decode)")
    check_kernels(flash.forward_cached, params, prompt[:, :1], cache,
                  what="forward_cached decode step (flash_decode)")

    def last_logits(p, t):
        return flash.forward(p, t)[:, -1]

    check_kernels(last_logits, params, prompt,
                  what="forward (flash_attention)")

    jax.block_until_ready(step(params, prompt, cache))       # compile
    (logits, filled), t_prefill = _timed(lambda: jax.block_until_ready(
        step(params, prompt, cache)))
    first = np.asarray(logits[:, -1])
    dense_step = dense._jit_forward_cached()
    ref = np.asarray(dense_step(params, prompt, cache)[0][:, -1])
    fwd = np.asarray(jax.jit(last_logits)(params, prompt))
    # one S_new=1 decode step of each path on the same filled cache: the
    # flash_decode program generate() runs for every new token
    tok = jnp.asarray(first.argmax(-1)[:, None], jnp.int32)
    dec = np.asarray(step(params, tok, filled)[0][:, -1])
    dec_ref = np.asarray(dense_step(params, tok, filled)[0][:, -1])
    # control: the same step with every query group routed to the next
    # kv head (the cache's kv-head axis rolled by one) must miss
    rolled = dict(filled, k=jnp.roll(filled["k"], 1, axis=2),
                  v=jnp.roll(filled["v"], 1, axis=2))
    wrong = np.asarray(step(params, tok, rolled)[0][:, -1])
    for name, got, want in (("forward_cached prefill", first, ref),
                            ("forward prefill", fwd, ref),
                            ("forward_cached decode step", dec, dec_ref),
                            ("control: kv heads misrouted", wrong,
                             dec_ref)):
        if got.shape != (batch, cfg.vocab_size) or not np.isfinite(got).all():
            raise AssertionError(f"{name} logits: shape {got.shape} or "
                                 f"non-finite values")
        rel = float(np.abs(got - want).max() / np.abs(want).max())
        log(f"model {name} logits vs dense: max |diff| / max |logit| "
            f"{rel:.6g} (tolerance {LOGIT_TOL})")
        if name.startswith("control"):
            if not rel > LOGIT_TOL:
                raise AssertionError(f"the logit check cannot tell a "
                                     f"misrouted kernel: {rel}")
        elif not rel <= LOGIT_TOL:
            raise AssertionError(f"{name} logits off the dense path by "
                                 f"{rel} > {LOGIT_TOL}")

    flash.generate(params, prompt, new_tokens)              # compile
    toks, t_gen = _timed(lambda: np.asarray(
        flash.generate(params, prompt, new_tokens)))
    if toks.shape != (batch, new_tokens) or toks.min() < 0 \
            or toks.max() >= cfg.vocab_size:
        raise AssertionError(f"generate: bad tokens {toks.shape}")
    if not (toks[:, 0] == first.argmax(-1)).all():
        raise AssertionError("generate's first token is not the argmax of "
                             "the prefill logits")
    per_token = (t_gen - t_prefill) / max(new_tokens - 1, 1)
    log(f"model: prefill {batch}x{prompt_len} {t_prefill * 1e3:.1f} ms, "
        f"generate {new_tokens} tokens {t_gen * 1e3:.1f} ms "
        f"(~{per_token * 1e3:.1f} ms per decode step; host clock, "
        f"information only)")


# -- cross-chip --------------------------------------------------------------

def _run(accls, kind: str, body) -> list:
    """``body(a, buf)`` on every rank at once; each rank's result."""

    def fn(a):
        return _read(a, kind, body(a, lambda v: _buffer(a, kind, v)))
    return run_ranks(accls, fn, timeout=600)


def phase_cross_chip(seed: int, world: int = 4, nbytes: int = DDP_BUCKET,
                     block: int = 128) -> None:
    """Collectives across ``world`` chips through ``tpu_world`` and
    ``MeshCollectives`` inside a user jit."""

    accls = tpu_world(world)
    W = len(accls)
    devs = [a.device.my_device for a in accls]
    if len(set(devs)) != W:
        raise AssertionError(f"ranks share devices: {devs}")
    rng = np.random.default_rng(seed)
    try:
        for dt in (np.dtype(np.float32), BF16):
            n = nbytes // dt.itemsize
            n -= n % W
            ins = [rng.standard_normal(n, np.float32).astype(dt)
                   for _ in range(W)]
            ins64 = np.stack(ins).astype(np.float64)
            total, mag = ins64.sum(0), np.abs(ins64).sum(0)
            # one rounding per hop of a W-rank reduction in dt
            eps = W * float(ml_dtypes.finfo(dt).eps)
            for kind in ("host", "device"):
                tag = f"{dt.name} {nbytes // MiB} MiB/rank {kind}"

                def run(body):
                    return _run(accls, kind, body)

                def allreduce(a, buf):
                    out = buf(np.zeros(n, dt))
                    a.allreduce(buf(ins[a.rank]), out, n)
                    return out

                def allgather(a, buf):
                    out = buf(np.zeros(W * n, dt))
                    a.allgather(buf(ins[a.rank]), out, n)
                    return out

                def reduce_scatter(a, buf):
                    out = buf(np.zeros(n // W, dt))
                    a.reduce_scatter(buf(ins[a.rank]), out, n // W)
                    return out

                def alltoall(a, buf):
                    out = buf(np.zeros(n, dt))
                    a.alltoall(buf(ins[a.rank]), out, n // W)
                    return out

                def bcast(a, buf):
                    b = buf(ins[a.rank])
                    a.bcast(b, n, root=1)
                    return b

                t0 = time.perf_counter()
                for r, got in enumerate(run(allreduce)):
                    check_close(got, total, mag, eps, f"allreduce r{r} {tag}")
                for r, got in enumerate(run(allgather)):
                    check_bits(got, np.concatenate(ins),
                               f"allgather r{r} {tag}")
                chunk = n // W
                for r, got in enumerate(run(reduce_scatter)):
                    sl = slice(r * chunk, (r + 1) * chunk)
                    check_close(got, total[sl], mag[sl], eps,
                                f"reduce_scatter r{r} {tag}")
                for r, got in enumerate(run(alltoall)):
                    want = np.concatenate(
                        [ins[s][r * chunk:(r + 1) * chunk] for s in range(W)])
                    check_bits(got, want, f"alltoall r{r} {tag}")
                for r, got in enumerate(run(bcast)):
                    check_bits(got, ins[1], f"bcast r{r} {tag}")
                log(f"cross-chip {tag}: allreduce, allgather, reduce_scatter,"
                    f" alltoall, bcast ok ({time.perf_counter() - t0:.2f} s "
                    f"incl. compile)")

        # block-scaled quantized ring allreduce (the fused Pallas codec)
        n = nbytes // 4
        n -= n % W
        ins = [(rng.standard_normal(n, np.float32)
                * np.float32(10.0) ** rng.integers(-3, 4, n).astype(
                    np.float32)) for _ in range(W)]
        for qd in (np.dtype(ml_dtypes.float8_e4m3fn), np.dtype(np.int8)):
            ref = quant.ring_allreduce_reference(ins, ReduceFunc.SUM, qd,
                                                 block)
            for kind in ("host", "device"):
                def bs_allreduce(a, buf):
                    out = buf(np.zeros(n, np.float32))
                    a.allreduce(buf(ins[a.rank]), out, n, compress_dtype=qd,
                                block_scale=block)
                    return out
                t0 = time.perf_counter()
                for r, got in enumerate(_run(accls, kind, bs_allreduce)):
                    check_bits(got, ref[r], f"bs ring {qd.name} r{r} {kind}")
                log(f"cross-chip block-scaled ring allreduce {qd.name} block "
                    f"{block} {nbytes // MiB} MiB/rank {kind}: bit-identical "
                    f"to quant.py ({time.perf_counter() - t0:.2f} s incl. "
                    f"compile)")
        coll = accls[0].device.ctx.coll
        x = coll.shard(ins)
        for qd in (np.dtype(ml_dtypes.float8_e4m3fn), np.dtype(np.int8)):
            check_kernels(lambda v: coll.allreduce(
                v, algorithm="ring", wire_dtype=qd, qblock=block), x,
                what=f"bs ring program {qd.name}")

        # MeshCollectives.allreduce inside the user's own jit
        @jax.jit
        def user_step(v):
            return coll.allreduce(v * 2.0) + 1.0

        res = user_step(x)
        got = np.asarray(res)
        if not isinstance(res.sharding, NamedSharding) or \
                res.sharding.spec[0] != coll.axis_name:
            raise AssertionError(f"user jit output sharding {res.sharding}")
        ins64 = np.stack(ins).astype(np.float64)
        want = 2.0 * ins64.sum(0) + 1.0
        scale = 2.0 * np.abs(ins64).sum(0) + 1.0
        eps = W * float(np.finfo(np.float32).eps)
        for r in range(W):
            check_close(got[r], want, scale, eps, f"user-jit allreduce r{r}")
        log("cross-chip MeshCollectives.allreduce inside a user jit: ok")
    finally:
        for a in accls:
            a.deinit()


# -- entry -------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: driver, codec and model phases on one chip; "
                         "4: only the cross-chip phase on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    log(f"compile cache: {use_compile_cache()}")

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d0.platform!r}); this script runs on the chip")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")
    log(f"device: {d0.device_kind} x {len(devices)}, jax {jax.__version__}")
    t0 = time.perf_counter()
    phases = ((phase_cross_chip,) if args.chips == 4
              else (phase_driver, phase_codec, phase_model))
    failed = []
    for phase in phases:
        # every phase runs, so one chip call reports every fault
        name, t = phase.__name__[6:], time.perf_counter()
        try:
            phase(args.seed)
        except Exception:  # noqa: BLE001 — reported, then raised below
            traceback.print_exc()
            failed.append(name)
        log(f"phase {name}: {'FAILED' if name in failed else 'ok'} "
            f"({time.perf_counter() - t:.1f} s)")
    if failed:
        raise SystemExit(f"chip_smoke: phases failed: {', '.join(failed)}")
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
