"""The TPU tier's launch plans: what the first launch of a collective
signature on one placement of its buffers resolves is kept beside the
communicator's programs, and later launches of the signature check their
members against it and go straight to assembly, dispatch and rebind (or,
for host mirrors, to landing the rows and reading the results back).

Covers: plan hits bit-identical to the full resolution for every op that
launches on device; host-plan hits of the dense four bit-identical to the
staged path, on one rank and on four; both checked against the stacked
``MeshCollectives`` program on the same rows; buffers re-registered with another
geometry and host-mirror members of a device plan falling back; host-side
compression and empty collectives never planned; a device and a host
plan of one signature side by side; split and shrunk communicators never reaching an older plan;
misplaced shards still moved; the verify-once guard of the unchecked
assembly; the store's cap; and the ``tpu_launch_plan_total`` counter.
"""

import numpy as np
import pytest

import jax

from accl_tpu import ACCLError, ErrorCode, ReduceFunc
from accl_tpu.buffer import ACCLBuffer
from accl_tpu.device import tpu
from accl_tpu.device.tpu import tpu_world
from accl_tpu.testing import run_ranks
from accl_tpu.tracing import METRICS

W = 4


@pytest.fixture(scope="module")
def world():
    accls = tpu_world(W, platform="cpu")
    yield accls
    for a in accls:
        a.deinit()


@pytest.fixture(scope="module")
def world1():
    accls = tpu_world(1, platform="cpu")
    yield accls
    for a in accls:
        a.deinit()


@pytest.fixture(params=[1, W], ids=["1rank", f"{W}rank"])
def ranks(request, world, world1):
    """The one-rank or the four-rank world."""
    return world1 if request.param == 1 else world


def counts() -> dict:
    c = METRICS.snapshot()["counters"].get("tpu_launch_plan_total", {})
    return {k: c.get(f"result={k}", 0) for k in ("hit", "miss", "fallback")}


def moved(before: dict) -> dict:
    now = counts()
    return {k: now[k] - before[k] for k in now}


def plans(coll) -> dict:
    return {k: v for k, v in list(coll._cache.items()) if k[0] == "plan"}


def drop_plans(coll) -> None:
    for k in plans(coll):
        coll._cache.pop(k, None)


def data(n: int, seed: int, rank: int) -> np.ndarray:
    return np.random.default_rng([seed, rank]).standard_normal(n).astype(
        np.float32)


def dev(a, arr):
    return a.buffer(data=jax.device_put(arr, a.device.my_device))


# op -> (operand elements, result elements, call(a, src, dst, n)); rooted
# ops at root 2
ROOT = 2
OPS = {
    "allreduce": (1, 1, lambda a, s, d, n: a.allreduce(s, d, n)),
    "allgather": (1, W, lambda a, s, d, n: a.allgather(s, d, n)),
    "reduce_scatter": (W, 1, lambda a, s, d, n: a.reduce_scatter(s, d, n)),
    "alltoall": (W, W, lambda a, s, d, n: a.alltoall(s, d, n)),
    "bcast": (1, 1, lambda a, s, d, n: a.bcast(s, n, root=ROOT)),
    "reduce": (1, 1, lambda a, s, d, n: a.reduce(s, d, n, ROOT,
                                                 ReduceFunc.MAX)),
    "scatter": (W, 1, lambda a, s, d, n: a.scatter(s, d, n, ROOT)),
    "gather": (1, W, lambda a, s, d, n: a.gather(s, d, n, ROOT)),
}


def launch(world, op: str, n: int, seed: int, reps: int = 1) -> list:
    """Every rank's result of ``reps`` launches of ``op`` on fresh
    device-resident buffers holding operands made from ``seed`` (a bcast's
    result is its buffer)."""
    k_in, k_out, fn = OPS[op]

    def body(a):
        src = dev(a, data(k_in * n, seed, a.rank))
        dst = a.buffer((k_out * n,), np.float32, device_resident=True)
        for _ in range(reps):
            fn(a, src, dst, n)
        return (src if op == "bcast" else dst).data.copy()

    return run_ranks(world, body)


# dense op -> a rank's (operand, result) elements at count n on w ranks
DENSE = {"allreduce": lambda w, n: (n, n),
         "allgather": lambda w, n: (n, w * n),
         "reduce_scatter": lambda w, n: (w * n, n),
         "alltoall": lambda w, n: (w * n, w * n)}


def dense_launch(accls, op: str, n: int, seed: int, reps: int = 1,
                 host: bool = True) -> list:
    """Every rank's result of ``reps`` launches of the dense ``op`` on fresh
    buffers, host mirrors (or device-resident where not ``host``) of the
    op's exact geometry, holding operands made from ``seed``."""
    n_in, n_out = DENSE[op](len(accls), n)
    fn = OPS[op][2]

    def body(a):
        x = data(n_in, seed, a.rank)
        src = a.buffer(data=x) if host else dev(a, x)
        dst = a.buffer((n_out,), np.float32, device_resident=not host)
        for _ in range(reps):
            fn(a, src, dst, n)
        out = dst.data.copy()
        src.free_buffer()
        dst.free_buffer()
        return out

    return run_ranks(accls, body)


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.dtype == b.dtype and a.tobytes() == b.tobytes()


# which comm-local ranks a rooted op at ROOT writes a result to
RECEIVES = {"bcast": lambda r: r != ROOT, "reduce": lambda r: r == ROOT,
            "gather": lambda r: r == ROOT}


def stacked(coll, op: str, n: int, seed: int) -> list:
    """Every rank's result of ``op`` on the operands ``launch`` and
    ``dense_launch`` make from ``seed``, computed outside the driver by the
    stacked ``MeshCollectives`` program on the same rows: a second
    lowering of the op. A rank an op writes no result to keeps what its
    buffer held: a bcast root its operand, any other rank zeros."""
    k_in, k_out, _ = OPS[op]
    if op in DENSE:
        k_in, k_out = (k // n for k in DENSE[op](coll.W, n))
    rows = [data(k_in * n, seed, r) for r in range(coll.W)]
    x = coll.shard(rows)
    out = np.asarray({
        "allreduce": lambda: coll.allreduce(x),
        "allgather": lambda: coll.allgather(x),
        "reduce_scatter": lambda: coll.reduce_scatter(x),
        "alltoall": lambda: coll.alltoall(x),
        "bcast": lambda: coll.bcast(x, root=ROOT),
        "reduce": lambda: coll.reduce(x, root=ROOT, func=ReduceFunc.MAX),
        "scatter": lambda: coll.scatter(x, root=ROOT),
        "gather": lambda: coll.gather(x, root=ROOT),
    }[op]())
    receives = RECEIVES.get(op, lambda r: True)
    return [out[r] if receives(r)
            else rows[r] if op == "bcast"
            else np.zeros(k_out * n, np.float32) for r in range(coll.W)]


@pytest.mark.parametrize("op", sorted(OPS))
def test_plan_hit_bit_identical_to_full_resolution(world, op):
    """A plan's hits leave the bits of the launch that built it, and that
    launch the bits of the stacked program on the same rows."""
    coll = world[0].device.ctx.coll
    n = 24
    drop_plans(coll)
    c0 = counts()
    miss = launch(world, op, n, seed=1)
    assert moved(c0) == {"hit": 0, "miss": 1, "fallback": 0}
    assert len(plans(coll)) == 1
    for m, ref in zip(miss, stacked(coll, op, n, seed=1)):
        assert same_bits(m, ref)
    c0 = counts()
    hit = launch(world, op, n, seed=1, reps=2)
    assert moved(c0) == {"hit": 2, "miss": 0, "fallback": 0}
    for m, h in zip(miss, hit):
        np.testing.assert_array_equal(h, m)
    # operands are read on every launch: a hit on other data matches the
    # full resolution on that data
    hit2 = launch(world, op, n, seed=2)
    drop_plans(coll)
    miss2 = launch(world, op, n, seed=2)
    for m, h in zip(miss2, hit2):
        np.testing.assert_array_equal(h, m)
    assert not all(np.array_equal(a, b) for a, b in zip(hit, hit2))


@pytest.mark.parametrize("change", ["size", "dtype"])
def test_reregistered_buffer_falls_back(world, change):
    """After the plan is built, rank 1 frees its destination and registers
    another buffer, of another size or dtype, at the same address: the
    plan's check refuses it and the staged path runs, exactly as it does
    with no plan at all."""
    coll = world[0].device.ctx.coll
    n = 40

    def scenario(keep_plan: bool):
        def body(a):
            src = dev(a, data(n, 5, a.rank))
            dst = a.buffer((n,), np.float32, device_resident=True)
            a.allreduce(src, dst, n)          # builds the plan
            out = dst
            if a.rank == 1:
                dst.free_buffer()
                shape, dt = (((2 * n,), np.float32) if change == "size"
                             else ((n,), np.float16))
                out = ACCLBuffer(shape, dt, device=a.device,
                                 data=jax.device_put(np.zeros(shape, dt),
                                                     a.device.my_device),
                                 address=dst.address)
                if not keep_plan:
                    drop_plans(coll)
            a.allreduce(src, dst, n)          # the descriptor is unchanged
            got = out.data.copy()
            out.free_buffer()
            return got

        drop_plans(coll)
        c0 = counts()
        res = run_ranks(world, body)
        return res, moved(c0)

    fell_back, c = scenario(keep_plan=True)
    assert c == {"hit": 0, "miss": 1, "fallback": 1}
    no_plan, c = scenario(keep_plan=False)
    assert c == {"hit": 0, "miss": 2, "fallback": 0}
    assert not plans(coll)    # a staged launch builds no plan
    gold = sum(data(n, 5, r) for r in range(W))
    for r in range(W):
        np.testing.assert_array_equal(fell_back[r], no_plan[r])
        tol = 1e-2 if (r == 1 and change == "dtype") else 1e-5
        np.testing.assert_allclose(fell_back[r][:n].astype(np.float32),
                                   gold, rtol=tol, atol=tol)
    if change == "size":
        assert not fell_back[1][n:].any()


@pytest.mark.parametrize("op", sorted(DENSE))
def test_host_plan_hit_bit_identical_to_staged(ranks, monkeypatch, op):
    """The first launch of a dense op on host mirrors builds a plan of its
    own; its hits, on the data it was built on and on other data, leave in
    each host-mirror destination the bits the staged path leaves there,
    and the bits of the stacked program on the same rows."""
    coll = ranks[0].device.ctx.coll
    n = 24
    drop_plans(coll)
    c0 = counts()
    miss = dense_launch(ranks, op, n, seed=16)
    assert moved(c0) == {"hit": 0, "miss": 1, "fallback": 0}
    (key,) = plans(coll)
    assert key[-1] == "host" and plans(coll)[key].host
    c0 = counts()
    hit = dense_launch(ranks, op, n, seed=16, reps=2)
    hit2 = dense_launch(ranks, op, n, seed=17)
    assert moved(c0) == {"hit": 3, "miss": 0, "fallback": 0}
    # the reference: the plans dropped, and none built, so every launch
    # takes the staged path
    drop_plans(coll)
    monkeypatch.setattr(tpu.TpuDevice, "_resolve_host",
                        lambda self, plan, descs, devs: None)
    c0 = counts()
    staged = dense_launch(ranks, op, n, seed=16)
    staged2 = dense_launch(ranks, op, n, seed=17)
    assert moved(c0) == {"hit": 0, "miss": 2, "fallback": 0}
    assert not plans(coll)
    ref = stacked(coll, op, n, seed=16)
    for r in range(len(ranks)):
        assert same_bits(miss[r], ref[r])
        assert same_bits(miss[r], staged[r])
        assert same_bits(hit[r], staged[r])
        assert same_bits(hit2[r], staged2[r])
    assert not all(np.array_equal(a, b) for a, b in zip(hit, hit2))


@pytest.mark.parametrize("change", ["size", "dtype"])
def test_reregistered_host_buffer_falls_back(world, change):
    """After the host plan is built, rank 1 frees its host-mirror
    destination and registers another, of another size or dtype but as
    many bytes or more, at the same address: the plan's check refuses it
    and the staged path runs, exactly as it does with no plan at all."""
    coll = world[0].device.ctx.coll
    n = 40

    def scenario(keep_plan: bool):
        def body(a):
            src = a.buffer(data=data(n, 18, a.rank))
            dst = a.buffer((n,), np.float32)
            a.allreduce(src, dst, n)          # builds the host plan
            out = dst
            if a.rank == 1:
                dst.free_buffer()
                shape, dt = (((2 * n,), np.float32) if change == "size"
                             else ((n,), np.int32))
                out = ACCLBuffer(shape, dt, device=a.device,
                                 address=dst.address)
                if not keep_plan:
                    drop_plans(coll)
            a.allreduce(src, dst, n)          # the descriptor is unchanged
            got = out.data.copy()
            out.free_buffer()
            src.free_buffer()
            return got

        drop_plans(coll)
        c0 = counts()
        res = run_ranks(world, body)
        return res, moved(c0)

    fell_back, c = scenario(keep_plan=True)
    assert c == {"hit": 0, "miss": 1, "fallback": 1}
    no_plan, c = scenario(keep_plan=False)
    assert c == {"hit": 0, "miss": 2, "fallback": 0}
    assert not plans(coll)    # a staged launch builds no plan
    gold = sum(data(n, 18, r) for r in range(W))
    for r in range(W):
        assert same_bits(fell_back[r], no_plan[r])
        got = fell_back[r].view(np.float32)[:n]
        np.testing.assert_allclose(got, gold, rtol=1e-5, atol=1e-5)
    if change == "size":
        assert not fell_back[1][n:].any()


@pytest.mark.parametrize("which", ["operand", "result"])
def test_host_compression_never_takes_a_host_plan(ranks, which):
    """A call that keeps its operand or its result compressed in host
    memory (f16 beside f32) stages on every launch and builds no plan."""
    coll = ranks[0].device.ctx.coll
    n = 48
    drop_plans(coll)
    c0 = counts()

    def body(a):
        x = data(n, 19, a.rank)
        src = a.buffer(data=x.astype(np.float16) if which == "operand"
                       else x)
        dst = a.buffer((n,), np.float16 if which == "result"
                       else np.float32)
        for _ in range(2):
            a.allreduce(src, dst, n)
        return dst.data.astype(np.float32)

    res = run_ranks(ranks, body)
    assert moved(c0) == {"hit": 0, "miss": 2, "fallback": 0}
    assert not plans(coll)
    gold = sum(data(n, 19, r) for r in range(len(ranks)))
    for out in res:
        np.testing.assert_allclose(out, gold, rtol=1e-2, atol=1e-2)


def test_empty_host_collective_stages(ranks):
    """A dense op of no elements on host mirrors builds no plan and
    completes, launch after launch, without running a program."""
    coll = ranks[0].device.ctx.coll
    drop_plans(coll)
    c0 = counts()
    out = dense_launch(ranks, "allreduce", 0, seed=22, reps=2)
    assert moved(c0) == {"hit": 0, "miss": 2, "fallback": 0}
    assert not plans(coll)
    assert all(o.size == 0 for o in out)


@pytest.mark.parametrize("op", ["allreduce", "alltoall"])
def test_device_and_host_plans_of_one_signature(ranks, op):
    """One signature launched on device-resident buffers and on host
    mirrors keeps one plan of each placement, side by side, and each
    placement hits its own; both run the same program, to the same
    bits."""
    coll = ranks[0].device.ctx.coll
    n = 52
    drop_plans(coll)
    c0 = counts()
    dev_miss = dense_launch(ranks, op, n, seed=20, host=False)
    host_miss = dense_launch(ranks, op, n, seed=20)
    assert moved(c0) == {"hit": 0, "miss": 2, "fallback": 0}
    kept = plans(coll)
    (host_key,) = [k for k in kept if kept[k].host]
    (dev_key,) = [k for k in kept if not kept[k].host]
    assert host_key == dev_key + ("host",)
    c0 = counts()
    dev_hit = dense_launch(ranks, op, n, seed=20, host=False)
    host_hit = dense_launch(ranks, op, n, seed=20)
    dev_hit2 = dense_launch(ranks, op, n, seed=20, host=False)
    assert moved(c0) == {"hit": 3, "miss": 0, "fallback": 0}
    assert plans(coll) == kept
    for outs in (host_miss, dev_hit, host_hit, dev_hit2):
        for r in range(len(ranks)):
            assert same_bits(outs[r], dev_miss[r])


def test_host_mirror_member_falls_back(world):
    """A plan built by device-resident members; one member then calls
    with a host-mirror operand: the launch leaves the plan, stages, and
    every rank still gets the sum."""
    coll = world[0].device.ctx.coll
    n = 56
    drop_plans(coll)
    launch(world, "allreduce", n, seed=3)
    c0 = counts()

    def body(a):
        x = data(n, 4, a.rank)
        src = a.buffer(data=x) if a.rank == 2 else dev(a, x)
        dst = a.buffer((n,), np.float32, device_resident=True)
        a.allreduce(src, dst, n)
        return dst.data.copy()

    res = run_ranks(world, body)
    assert moved(c0) == {"hit": 0, "miss": 0, "fallback": 1}
    gold = sum(data(n, 4, r) for r in range(W))
    for out in res:
        np.testing.assert_allclose(out, gold, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["split", "shrink"])
def test_new_communicator_never_runs_an_older_plan(world, kind):
    """A split or shrunk communicator runs over its own device set, whose
    collectives hold their own plans: its first launch of a signature the
    world communicator has a plan for is a miss, and is correct."""
    ctx = world[0].device.ctx
    n = 32
    drop_plans(ctx.coll)
    launch(world, "allreduce", n, seed=6)
    world_plans = plans(ctx.coll)
    assert len(world_plans) == 1
    members = [0, 1] if kind == "split" else [0, 1, 2]
    c0 = counts()

    def body(a):
        if a.rank not in members:
            return None
        comm = (a.split_communicator(members) if kind == "split"
                else a.shrink_communicator([3]))
        src = dev(a, data(n, 7, a.rank))
        dst = a.buffer((n,), np.float32, device_resident=True)
        a.allreduce(src, dst, n, comm=comm)
        a.allreduce(src, dst, n, comm=comm)
        return comm.comm_id, dst.data.copy()

    res = run_ranks(world, body)
    assert moved(c0) == {"hit": 1, "miss": 1, "fallback": 0}
    sub = ctx._subcolls[res[0][0]]
    assert sub is not ctx.coll and len(plans(sub)) == 1
    assert plans(ctx.coll) == world_plans
    gold = sum(data(n, 7, r) for r in members)
    for r in members:
        np.testing.assert_allclose(res[r][1], gold, rtol=1e-5, atol=1e-5)


def test_misplaced_shard_is_moved(world):
    """A member's operand array found on another rank's device on a plan
    hit is still moved to its own before assembly."""
    ctx = world[0].device.ctx
    n = 20
    drop_plans(ctx.coll)
    launch(world, "allreduce", n, seed=8)
    devices = ctx.coll.device_list
    c0 = counts()

    def body(a):
        src = dev(a, data(n, 9, a.rank))
        if a.rank == 1:
            src._swap(jax.device_put(src.jax, devices[3]))
        dst = a.buffer((n,), np.float32, device_resident=True)
        a.allreduce(src, dst, n)
        return dst.data.copy(), dst.jax.device

    res = run_ranks(world, body)
    assert moved(c0) == {"hit": 1, "miss": 0, "fallback": 0}
    gold = sum(data(n, 9, r) for r in range(W))
    for r, (out, d) in enumerate(res):
        np.testing.assert_allclose(out, gold, rtol=1e-5, atol=1e-5)
        assert d == devices[r]


@pytest.mark.parametrize("guard", ["agrees", "disagrees", "missing"])
def test_unchecked_assembly_only_where_proven(world, monkeypatch, guard):
    """The plan proves once that the unchecked constructor builds the
    public one's array; where it disagrees, or is missing, every launch
    keeps the public, checked constructor, and the results are the
    same."""
    coll = world[0].device.ctx.coll
    n = 28
    real = tpu._unchecked_array
    if guard == "disagrees":
        monkeypatch.setattr(tpu, "_unchecked_array",
                            lambda aval, sh, arrays: real(aval, sh,
                                                          arrays[::-1]))
    elif guard == "missing":
        monkeypatch.setattr(tpu, "_ArrayImpl", None)
    drop_plans(coll)
    launch(world, "allgather", n, seed=10)
    (plan,) = plans(coll).values()
    assert (plan.aval is not None) == (guard == "agrees")
    checked = []
    public = jax.make_array_from_single_device_arrays

    def spy(*args, **kw):
        checked.append(1)
        return public(*args, **kw)

    monkeypatch.setattr(jax, "make_array_from_single_device_arrays", spy)
    hit = launch(world, "allgather", n, seed=11, reps=3)
    assert len(checked) == (0 if guard == "agrees" else 3)
    gold = np.concatenate([data(n, 11, r) for r in range(W)])
    for out in hit:
        np.testing.assert_array_equal(out, gold)


def test_rebind_keeps_each_destination_geometry(world):
    """A 1-D destination takes the result shard as it is; a destination of
    another shape takes it reshaped, on a plan hit as on the miss."""
    coll = world[0].device.ctx.coll
    n = 36
    drop_plans(coll)

    def body(a):
        src = dev(a, data(n, 12, a.rank))
        shape = (n,) if a.rank % 2 else (6, 6)
        dst = a.buffer(shape, np.float32, device_resident=True)
        outs = []
        for _ in range(2):
            a.allreduce(src, dst, n)
            outs.append((dst.shape, dst.jax.shape, dst.dtype,
                         dst.data.copy()))
        return outs

    res = run_ranks(world, body)
    gold = sum(data(n, 12, r) for r in range(W))
    for r, outs in enumerate(res):
        shape = (n,) if r % 2 else (6, 6)
        for buf_shape, arr_shape, dt, out in outs:
            assert buf_shape == arr_shape == shape
            assert dt == np.float32
            np.testing.assert_allclose(out.reshape(-1), gold, rtol=1e-5,
                                       atol=1e-5)


def test_plan_store_is_capped(world, monkeypatch):
    coll = world[0].device.ctx.coll
    monkeypatch.setattr(tpu.TpuContext, "_MAX_PLANS", 3)
    drop_plans(coll)
    for n in (3, 5, 7, 9, 11):
        launch(world, "allreduce", n, seed=13)
    kept = sorted(k[2] for k in plans(coll))
    assert kept == [7, 9, 11]
    programs = [k for k in coll._cache if k[0] == "flat"]
    assert programs        # the programs themselves stay


def test_counter_counts_hit_miss_and_fallback(world):
    coll = world[0].device.ctx.coll
    n = 44
    drop_plans(coll)
    c0 = counts()
    launch(world, "reduce_scatter", n, seed=14, reps=3)

    def host(a):
        src = a.buffer(data=data(W * n, 15, a.rank))
        dst = a.buffer((n,), np.float32)
        a.reduce_scatter(src, dst, n)        # the plan's signature on
        #                                      host mirrors: a plan of its own
        for _ in range(2):                   # buffers too large for n - 1:
            a.reduce_scatter(src, dst, n - 1)  # never planned

    run_ranks(world, host)
    assert moved(c0) == {"hit": 2, "miss": 4, "fallback": 0}
    text = METRICS.to_prometheus()
    for result in ("hit", "miss", "fallback"):
        assert f'tpu_launch_plan_total{{result="{result}"}}' in text


def test_rooted_op_refuses_a_root_outside_the_communicator(world):
    def body(a):
        buf = a.buffer((8,), np.float32, device_resident=True)
        with pytest.raises(ACCLError) as err:
            a.bcast(buf, 8, root=W)
        return err.value.error_word

    assert run_ranks(world, body) == [int(ErrorCode.INVALID_CALL)] * W
