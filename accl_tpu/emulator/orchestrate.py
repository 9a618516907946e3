"""CI-style test orchestrator for the emulator tier.

Parity: the reference's ``test/host/test_all.py`` — it compiles the
emulator/simulator, launches N ranks under mpirun, runs each collective's
test with a timeout, captures per-test logs, and greps for success
(Config test_all.py:35-58, run_emulator :71-95, run_test :152-181). Here:

* the "emulator build" step is ``make -C native`` (C++ rank daemon),
* the "mpirun launch" step is spawning N daemon processes (``--backend
  python`` runs ``python -m accl_tpu.emulator.daemon`` per rank;
  ``--backend native`` runs ``native/cclo_emud``),
* each collective test drives the daemons through :class:`SimDevice`
  (the same driver the unit tests and the C++ ``accl_demo`` use) and
  checks results against a numpy golden with root rotation,
* every test gets a fresh world (daemon state cannot leak across tests),
  a wall-clock timeout, and a per-test logfile under ``--log-dir``.

Run:  ``python -m accl_tpu.emulator.orchestrate --world 4 --backend both``
Exit status is nonzero if any test fails — usable directly in CI.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NATIVE_DAEMON = os.path.join(REPO, "native", "cclo_emud")


# -- collective test bodies (numpy-golden correctness, root rotation) -------
def _rng(rank: int) -> np.ndarray:
    return np.random.default_rng(1234 + rank)


def _inputs(world: int, n: int) -> list[np.ndarray]:
    return [_rng(r).standard_normal(n).astype(np.float32)
            for r in range(world)]


def t_sendrecv(accls):
    """2+-rank ping-pong with tag matching (BASELINE config 1 shape)."""
    from accl_tpu.testing import run_ranks
    n = 256

    def body(a):
        W = a.world_size
        buf = a.buffer((n,), np.float32)
        nxt, prv = (a.rank + 1) % W, (a.rank - 1) % W
        buf.data[:] = a.rank
        a.send(buf, n, dst=nxt, tag=7)
        rbuf = a.buffer((n,), np.float32)
        a.recv(rbuf, n, src=prv, tag=7)
        assert np.allclose(rbuf.data, prv), (a.rank, rbuf.data[:4])
        return True

    return all(run_ranks(accls, body))


def t_copy_combine(accls):
    from accl_tpu.constants import ReduceFunc
    from accl_tpu.testing import run_ranks
    n = 128

    def body(a):
        x = a.buffer(data=np.arange(n, dtype=np.float32))
        y = a.buffer(data=np.full(n, 2.0, np.float32))
        z = a.buffer((n,), np.float32)
        a.copy(x, z)
        assert np.allclose(z.data, x.data)
        a.combine(n, ReduceFunc.SUM, x, y, z)
        assert np.allclose(z.data, x.data + 2.0)
        return True

    return all(run_ranks(accls, body))


def _rotate_roots(accls, fn):
    from accl_tpu.testing import run_ranks
    for root in range(len(accls)):
        results = run_ranks(accls, lambda a: fn(a, root))
        if not all(results):
            return False
    return True


def t_bcast(accls):
    n = 300
    ins = _inputs(len(accls), n)

    def body(a, root):
        buf = a.buffer(data=ins[root].copy() if a.rank == root
                       else np.zeros(n, np.float32))
        a.bcast(buf, n, root=root)
        return np.allclose(buf.data, ins[root])

    return _rotate_roots(accls, body)


def t_scatter(accls):
    W = len(accls)
    n = 64
    ins = _inputs(W, W * n)

    def body(a, root):
        src = a.buffer(data=ins[root]) if a.rank == root else None
        dst = a.buffer((n,), np.float32)
        a.scatter(src, dst, n, root=root)
        return np.allclose(dst.data, ins[root][a.rank * n:(a.rank + 1) * n])

    return _rotate_roots(accls, body)


def t_gather(accls):
    W = len(accls)
    n = 64
    ins = _inputs(W, n)

    def body(a, root):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((W * n,), np.float32) if a.rank == root else None
        a.gather(src, dst, n, root=root)
        if a.rank == root:
            return np.allclose(dst.data, np.concatenate(ins))
        return True

    return _rotate_roots(accls, body)


def t_reduce(accls):
    W = len(accls)
    n = 200
    ins = _inputs(W, n)
    golden = np.sum(ins, axis=0)

    def body(a, root):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((n,), np.float32) if a.rank == root else None
        a.reduce(src, dst, n, root=root)
        if a.rank == root:
            return np.allclose(dst.data, golden, atol=1e-4)
        return True

    return _rotate_roots(accls, body)


def t_allgather(accls):
    from accl_tpu.testing import run_ranks
    W = len(accls)
    n = 64
    ins = _inputs(W, n)

    def body(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((W * n,), np.float32)
        a.allgather(src, dst, n)
        return np.allclose(dst.data, np.concatenate(ins))

    return all(run_ranks(accls, body))


def t_allreduce(accls):
    from accl_tpu.testing import run_ranks
    W = len(accls)
    n = 500
    ins = _inputs(W, n)
    golden = np.sum(ins, axis=0)

    def body(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((n,), np.float32)
        a.allreduce(src, dst, n)
        return np.allclose(dst.data, golden, atol=1e-4)

    return all(run_ranks(accls, body))


def t_reduce_scatter(accls):
    from accl_tpu.testing import run_ranks
    W = len(accls)
    n = 48
    ins = _inputs(W, W * n)
    golden = np.sum(ins, axis=0)

    def body(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((n,), np.float32)
        a.reduce_scatter(src, dst, n)
        return np.allclose(dst.data,
                           golden[a.rank * n:(a.rank + 1) * n], atol=1e-4)

    return all(run_ranks(accls, body))


def t_alltoall(accls):
    from accl_tpu.testing import run_ranks
    W = len(accls)
    n = 32
    ins = _inputs(W, W * n)

    def body(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((W * n,), np.float32)
        a.alltoall(src, dst, n)
        golden = np.concatenate(
            [ins[s][a.rank * n:(a.rank + 1) * n] for s in range(W)])
        return np.allclose(dst.data, golden)

    return all(run_ranks(accls, body))


def t_barrier(accls):
    from accl_tpu.testing import run_ranks

    def body(a):
        a.barrier()
        return True

    return all(run_ranks(accls, body))


def t_compressed_allreduce(accls):
    """Wire-compressed (fp16 on the fabric) allreduce — the clane path."""
    from accl_tpu.testing import run_ranks
    W = len(accls)
    n = 128
    ins = [(np.arange(n) % 17).astype(np.float32) + r for r in range(W)]
    golden = np.sum(ins, axis=0)

    def body(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((n,), np.float32)
        a.allreduce(src, dst, n, compress_dtype=np.float16)
        return np.allclose(dst.data, golden, rtol=1e-2, atol=1e-1)

    return all(run_ranks(accls, body))


TESTS = {
    "sendrecv": t_sendrecv,
    "copy_combine": t_copy_combine,
    "bcast": t_bcast,
    "scatter": t_scatter,
    "gather": t_gather,
    "reduce": t_reduce,
    "allgather": t_allgather,
    "allreduce": t_allreduce,
    "reduce_scatter": t_reduce_scatter,
    "alltoall": t_alltoall,
    "barrier": t_barrier,
    "compressed_allreduce": t_compressed_allreduce,
}


# -- world lifecycle --------------------------------------------------------
def build_native(log) -> bool:
    """Compile the C++ daemon (the reference's run_emulator build step)."""
    proc = subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                          capture_output=True, text=True)
    log.write(proc.stdout + proc.stderr)
    return proc.returncode == 0


def launch_daemons(world: int, backend: str, port_base: int, log,
                   stack: str = "tcp"):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if backend == "native":
        argv0 = [NATIVE_DAEMON]
    else:
        argv0 = [sys.executable, "-m", "accl_tpu.emulator.daemon"]
    procs = []
    for r in range(world):
        procs.append(subprocess.Popen(
            argv0 + ["--rank", str(r), "--world", str(world),
                     "--port-base", str(port_base), "--stack", stack],
            env=env, stdout=log, stderr=subprocess.STDOUT))
    return procs


def stop_daemons(procs):
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def run_one(name: str, world: int, backend: str, timeout: float,
            log_path: str, stack: str = "tcp") -> tuple[bool, float, str]:
    """Fresh world -> connect -> run -> teardown, under a wall-clock budget.

    Returns (ok, seconds, detail). Parity: run_test (test_all.py:152-181).
    """
    from accl_tpu.testing import connect_world, free_port_base

    t0 = time.monotonic()
    with open(log_path, "w") as log:
        for attempt in range(3):
            port_base = free_port_base(span=2 * world + 8)
            procs = launch_daemons(world, backend, port_base, log, stack)
            accls = []
            try:
                with concurrent.futures.ThreadPoolExecutor(1) as pool:
                    fut = pool.submit(_connect_and_run, name, world,
                                      port_base, accls)
                    ok = fut.result(timeout=timeout)
                detail = "" if ok else "wrong result"
            except concurrent.futures.TimeoutError:
                ok, detail = False, f"timeout after {timeout}s"
            except Exception as exc:  # noqa: BLE001 — report, keep going
                ok, detail = False, f"{type(exc).__name__}: {exc}"
            finally:
                for a in accls:
                    try:
                        a.deinit()
                    except Exception:  # noqa: BLE001 — teardown best-effort
                        pass
                stop_daemons(procs)
            # a port was stolen between probe and daemon bind: relaunch on a
            # fresh base (the daemon exits on bind failure -> conn refused)
            if not ok and "ConnectionRefused" in detail and attempt < 2:
                log.write(f"\n[{name}] retrying on a fresh port base\n")
                continue
            break
        log.write(f"\n[{name}] {'succeeded' if ok else 'FAILED: ' + detail}\n")
    return ok, time.monotonic() - t0, detail


def _connect_and_run(name: str, world: int, port_base: int,
                     accls_out: list) -> bool:
    from accl_tpu.testing import connect_world

    accls_out.extend(connect_world(port_base, world, timeout=30.0))
    return TESTS[name](accls_out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="accl_tpu emulator-tier test orchestrator "
                    "(test_all.py parity)")
    ap.add_argument("--world", "-n", type=int, default=4)
    ap.add_argument("--backend", choices=["python", "native", "both"],
                    default="both")
    ap.add_argument("--tests", nargs="*", default=sorted(TESTS),
                    choices=sorted(TESTS))
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-test wall-clock budget (s)")
    ap.add_argument("--stack", choices=["tcp", "udp", "shm"],
                    default="tcp",
                    help="eth fabric between rank daemons (tcp/udp: "
                         "dual-stack parity, reference use_tcp/use_udp; "
                         "shm: the shared-memory dataplane for "
                         "co-located ranks)")
    ap.add_argument("--log-dir", default="/tmp/accl_tpu_orchestrate")
    args = ap.parse_args(argv)

    os.makedirs(args.log_dir, exist_ok=True)
    backends = (["python", "native"] if args.backend == "both"
                else [args.backend])

    if "native" in backends:
        with open(os.path.join(args.log_dir, "build.log"), "w") as blog:
            if not build_native(blog):
                print("native build FAILED (see build.log); "
                      "skipping native backend")
                backends = [b for b in backends if b != "native"]

    failures = 0
    print(f"{'backend':<8}{'stack':<6}{'test':<24}{'result':<10}{'secs':>8}")
    for backend in backends:
        for name in args.tests:
            log_path = os.path.join(
                args.log_dir, f"{backend}_{args.stack}_{name}.log")
            ok, secs, detail = run_one(name, args.world, backend,
                                       args.timeout, log_path,
                                       stack=args.stack)
            failures += 0 if ok else 1
            status = "ok" if ok else f"FAIL"
            print(f"{backend:<8}{args.stack:<6}{name:<24}{status:<10}"
                  f"{secs:>8.2f}"
                  + (f"  {detail} [{log_path}]" if not ok else ""))
    print(f"\n{failures} failure(s); logs in {args.log_dir}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
