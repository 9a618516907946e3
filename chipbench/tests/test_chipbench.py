"""CPU rehearsal of the chip benchmark.

Every cell runs here through ``tpu_world`` on four virtual CPU devices at a
tiny size: the same set-up, window and comparison the chip runs, with the
harness's look for a chip skipped. These tests show the wiring and the
comparison, never a speed.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accl_tpu.device.tpu import TpuDevice, tpu_world
from accl_tpu.parallel.collectives import MeshCollectives

from chipbench import calibrate, check, data, drive, peaks, run, sets, spec
from chipbench import traffic as tr
from chipbench.trace_reduce import (Chip, Reduction, collective_base,
                                    parse_op, reduce_trace)

HERE = Path(__file__).resolve().parent
SEED = 2**33 + 17          # seeds wider than 32 bits are welcome
# tiny sizes of each cell's configuration: three buckets, the last short;
# three sizes; five decode rows
TINY = {"ddp25.f32": {"parameters": 3000, "bucket_cap_mb": 0.004},
        "acclbench.local": {"sizes_bytes": [8, 800, 8000]},
        "acclbench.host": {"sizes_bytes": [8, 800, 8000]},
        "tp4-decode.bf16": {"batch": 2, "hidden_size": 128,
                            "num_hidden_layers": 2}}


def tiny(name: str) -> spec.Cell:
    cell = spec.find_cell(name)
    return dataclasses.replace(cell, config=dict(cell.config, **TINY[name]))


def one_run(cell: spec.Cell, seconds: float = 0.3, seed: int = SEED):
    """Set-up, window and comparison, as ``run.main`` makes them:
    (window, failed, result_err, control reading)."""
    accls = tpu_world(cell.chips)
    try:
        r = drive.Run(accls, cell, seed)
        r.warm()
        win = r.run(seconds=seconds)
        failed = r.failed
        results = r.results()
    finally:
        for a in accls:
            a.deinit()
    ref = check.Reference(cell, seed, cell.chips)
    control = check.result_err(ref, check.control_results(ref, list(results)))
    return win, failed, check.result_err(ref, results), control


def limit(cell: spec.Cell) -> float:
    return cell.traffic["limits"][cell.config["dtype"]]["result_err"]


# -- configurations, traffic and the lookup by name ----------------------------

def test_every_cell_resolves_to_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"], bench)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m["name"]))
        for m in cell.per_layer:
            assert m["moves"] in e2e
        plan = tr.plan(cell.traffic, len(tr.sizes(cell.config)), SEED)
        for op, _ in plan.entries:
            assert spec.module("ops", op).OPERANDS <= tr.MAX_OPERANDS
        assert limit(cell) > 0


def test_ddp25_buckets_are_ddps():
    sizes = tr.sizes(spec.find_cell("ddp25.f32").config)
    assert sizes == [6_553_600] * 51 + [5_766_400]
    assert sum(sizes) == 340_000_000


def test_a_cell_added_as_files_is_found_and_runs(tmp_path, monkeypatch):
    """A configuration with its own buffer rule, a traffic mix of a new
    shape in bfloat16, a new call and a new end-to-end metric, added as new
    files plus new BENCHMARK.json entries: no code changes."""
    home = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, home,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (home / "configs" / "tiny.json").write_text(json.dumps(
        {"buffers": "thirds", "dtype": "bfloat16", "total": 3000}))
    (home / "buffers" / "thirds.py").write_text(
        "def sizes(config, itemsize):\n"
        "    return [config['total'] // 3] * 3\n")
    (home / "ops" / "double.py").write_text(
        "OPERANDS = 1\nRESULT = True\n\n"
        "def issue(a, srcs, dst, n, options):\n"
        "    from accl_tpu.constants import ReduceFunc\n"
        "    a.combine(n, ReduceFunc.SUM, srcs[0], srcs[0], dst)\n\n"
        "def terms(xs, rank):\n"
        "    return [xs[rank][0], xs[rank][0]]\n")
    (home / "traffic" / "double_then_sync.json").write_text(json.dumps(
        {"step": [{"ops": [{"op": "double", "weight": 1}], "size": "draw"},
                  {"ops": [{"op": "allreduce", "weight": 1}],
                   "size": "each"}],
         "operands": "draw", "operand_sets": 3, "steps": 16,
         "warm_steps": 1, "sample": {"every": 1, "max": 64},
         "limits": {"bfloat16": {"result_err": 0.02}}}))
    (home / "metrics" / "steps_per_s.py").write_text(
        "def read(run):\n    return run.window.steps / run.window.seconds\n")
    bench = {"configs": [{"name": "tiny", "file": "chipbench/configs/tiny.json"}],
             "workloads": [{"name": "tiny.dbl", "config": "tiny",
                            "traffic": "double_then_sync", "chips": 4}],
             "end_to_end": [{"name": "steps_per_s", "unit": "1/s",
                             "workloads": ["tiny.dbl"]},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": [{"name": "idle_share.bucket", "unit": "%",
                            "moves": "steps_per_s"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "HERE", home)
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    cell = spec.find_cell("tiny.dbl")
    assert [m["name"] for m in cell.end_to_end] == ["steps_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["idle_share.bucket"]
    win, failed, err, control = one_run(cell)
    assert win.steps > 0 and failed == 0
    assert win.calls == win.steps * 4      # one drawn call, three buckets
    assert win.bytes == win.calls * 1000 * 2
    assert err <= limit(cell) < control
    reading = run.Reading(cell, 1.5, win, {}, None, None)
    got = {m["name"]: spec.reader(m["name"])(reading)
           for m in cell.end_to_end}
    assert got["steps_per_s"] == pytest.approx(win.steps / win.seconds)
    assert got["setup_s"] == 1.5


def test_a_dtype_the_benchmark_cannot_make_is_refused():
    with pytest.raises(ValueError, match="dtype"):
        data.dtype("int8")
    config = dict(spec.find_cell("ddp25.f32").config, dtype="int8")
    with pytest.raises(ValueError, match="dtype"):
        tr.sizes(config)


# -- seeded traffic and data ---------------------------------------------------

def test_same_seed_same_calls():
    cell = spec.find_cell("acclbench.local")
    n = len(tr.sizes(cell.config))
    p1, p2, p3 = (tr.plan(cell.traffic, n, s) for s in (SEED, SEED, SEED + 1))
    for f in ("op", "slot", "opnd", "sampled"):
        assert np.array_equal(getattr(p1, f), getattr(p2, f))
    assert not np.array_equal(p1.op, p3.op)


def test_draws_are_balanced_so_every_seed_asks_for_the_same_work():
    cell = spec.find_cell("acclbench.local")
    n = len(tr.sizes(cell.config))
    block = len(cell.traffic["step"][0]["ops"]) * n
    counts = []
    for seed in (SEED, SEED + 1, 7):
        p = tr.plan(cell.traffic, n, seed)
        pairs = list(zip(p.op[:, 0].tolist(), p.slot[:, 0].tolist()))
        for b in range(0, 10 * block, block):
            assert len(set(pairs[b:b + block])) == block
        counts.append(collections.Counter(pairs[:100 * block]))
    assert counts[0] == counts[1] == counts[2]


@pytest.mark.parametrize("name,slots", [("ddp25.f32", 52),
                                        ("tp4-decode.bf16", 65)])
def test_ddp_steps_alternate_input_sets_over_every_bucket(name, slots):
    cell = spec.find_cell(name)
    p = tr.plan(cell.traffic, slots, SEED)
    assert p.steps == 2 and p.op.shape == (2, slots)
    assert (p.slot == np.arange(slots)).all()
    assert (p.opnd[0] == 0).all() and (p.opnd[1] == 1).all()


def test_tp_rows_are_a_decode_steps_allreduces():
    """The embedding's allreduce, then each layer's mixer and MLP: 65 rows
    of batch x hidden, in the order a step issues them."""
    config = spec.find_cell("tp4-decode.bf16").config
    assert (config["hidden_size"], config["num_hidden_layers"],
            config["batch"], config["tensor_parallel"]) == (4096, 32, 32, 4)
    assert len(config["mixer_types"]) == 32
    assert tr.sizes(config) == [32 * 4096] * 65
    assert tr.sizes(dict(config, **TINY["tp4-decode.bf16"])) == [256] * 5


def test_buffers_live_where_the_traffic_says():
    """``"placement": "host"`` gives host-mirror buffers with no device
    array; the default gives device arrays; any other value is refused."""
    accls = tpu_world(1)
    try:
        for name, host in (("acclbench.local", False),
                           ("acclbench.host", True)):
            r = drive.Run(accls, tiny(name), SEED)
            bufs = r.outs[0] + [b for row in r.ins[0] for b in row]
            assert len(bufs) == 5 * 3
            assert all(b.is_device_resident != host for b in bufs)
            assert all(isinstance(b.data, np.ndarray) for b in bufs)
            if host:
                for b in bufs:
                    with pytest.raises(ValueError, match="device-resident"):
                        b.jax
            else:
                assert all(isinstance(b.jax, jax.Array) for b in bufs)
            r.results()
        cell = tiny("acclbench.host")
        cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                      placement="pinned"))
        with pytest.raises(ValueError, match="placement 'pinned'"):
            drive.Run(accls, cell, SEED)
    finally:
        for a in accls:
            a.deinit()


def test_host_results_are_copied_only_where_kept(monkeypatch):
    """A host mirror's sampled result is copied out in the window only
    while fewer than ``sample.max`` are kept; the last step's are copied
    after the window, and every kept result is the call's own."""
    cell = tiny("acclbench.host")
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, sample={"every": 1, "max": 3}))
    copies = []
    result = drive.Run._result
    monkeypatch.setattr(drive.Run, "_result",
                        lambda self, dst: copies.append(1) or result(self, dst))
    win, failed, err, _ = one_run(cell)
    assert failed == 0 and err <= limit(cell)
    # one call a step, every call with a result sampled: 3 kept, 1 last
    assert win.steps > 20 and len(copies) <= 4


def test_values_match_on_device_and_in_numpy():
    key = data.stream_key(SEED, 1, 3, 51)
    for dt in ("float32", "bfloat16"):
        host = data.values(np, key, 10_000, data.dtype(dt))
        dev = jax.jit(lambda k: data.values(jnp, k, 10_000, data.dtype(dt)))(
            np.uint32(key))
        assert host.dtype == np.asarray(dev).dtype == data.dtype(dt)
        assert host.tobytes() == np.asarray(dev).tobytes()
    host = data.values(np, key, 10_000)
    assert -1 <= host.min() and host.max() < 1
    assert len(np.unique(np.floor(np.log2(np.abs(host[host != 0]))))) >= 8
    assert data.stream_key(SEED, 0, 0, 0) != data.stream_key(SEED + 2**32,
                                                             0, 0, 0)


# -- each cell on the CPU mesh: sound runs pass, the control does not ----------

@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_runs_correct_and_its_control_does_not(name):
    cell = tiny(name)
    win, failed, err, control = one_run(cell)
    assert win.steps > 0 and failed == 0
    assert win.seconds > 0 and win.bytes > 0
    assert err <= limit(cell) < control, (err, control)


def test_calibrate_reads_program_and_control():
    cell = tiny("acclbench.local")
    accls = tpu_world(1)
    lines = []
    try:
        out = calibrate.readings(cell, accls, [SEED, SEED + 1], 1, 0.2,
                                 emit=lines.append)
    finally:
        for a in accls:
            a.deinit()
    assert len(lines) == 3 and out["seeds"] == 2 and out["controls"] == 1
    assert out["program_max"] <= limit(cell) < out["control_min"]


# -- faults planted under the timed path make the run not correct -------------

def _drop(monkeypatch):
    """Results never land: the step leaves every destination as it was."""
    monkeypatch.setattr(TpuDevice, "_rebind_out_shards",
                        lambda self, coll, out, dst_map, devs: None)
    monkeypatch.setattr(TpuDevice, "_write_result",
                        lambda self, addr, data, desc: None)


def _alter(monkeypatch):
    """One element of every answer altered where it is produced."""
    rebind, write = TpuDevice._rebind_out_shards, TpuDevice._write_result

    def rebind_altered(self, coll, out, dst_map, devs):
        return rebind(self, coll, out.at[0].add(0.5), dst_map, devs)

    def write_altered(self, addr, data, desc):
        data = np.array(data, copy=True).reshape(-1)
        data[0] += 0.5
        return write(self, addr, data, desc)

    monkeypatch.setattr(TpuDevice, "_rebind_out_shards", rebind_altered)
    monkeypatch.setattr(TpuDevice, "_write_result", write_altered)


def _half(monkeypatch):
    """Half of each operand left out: the second half of every result is
    the first operand, unreduced, or stale."""
    program, write = MeshCollectives._program_flat, TpuDevice._write_result

    def half_program(self, *args, **kw):
        prog = program(self, *args, **kw)

        def run_half(x, *rest):
            n = x.shape[0] // self.W
            keep = (jnp.arange(x.shape[0]) % n) < n // 2
            return jnp.where(keep, prog(x, *rest), x)
        return run_half

    def write_half(self, addr, data, desc):
        data = np.asarray(data).reshape(-1)
        return write(self, addr, data[:len(data) // 2], desc)

    monkeypatch.setattr(MeshCollectives, "_program_flat", half_program)
    monkeypatch.setattr(TpuDevice, "_write_result", write_half)


def _no_exchange(monkeypatch):
    """The exchange between chips left out: each rank keeps its own."""
    monkeypatch.setattr(MeshCollectives, "_program_flat",
                        lambda self, *a, **kw: (lambda x, *rest: x))


FAULTS = {"dropped": _drop, "altered": _alter, "half": _half,
          "no_exchange": _no_exchange}
MULTICHIP = {"ddp25.f32", "tp4-decode.bf16"}     # cells with an exchange
CASES = [(n, f) for n in sorted(TINY) for f in FAULTS
         if f != "no_exchange" or n in MULTICHIP]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(monkeypatch, name, fault):
    cell = tiny(name)
    FAULTS[fault](monkeypatch)
    win, failed, err, _ = one_run(cell)
    assert win.calls > 0
    assert failed > 0 or err > limit(cell), err


# -- metric arithmetic, peaks and the trace reduction -------------------------

def _reduction(busy, window, coll, chips=4):
    c = Chip(plane="/device:TPU:0", busy_s=busy, op_s={}, collective_s=coll,
             gap_s={})
    return Reduction(window_s=window, chips=[c] * chips)


def _window(**kw):
    base = dict(steps=4, seconds=2.0, step_k=[0, 1, 0, 1],
                step_s=[0.5] * 4, calls=8, bytes=8_000_000_000,
                issued=collections.Counter(), itemsize=4)
    return drive.Window(**dict(base, **kw))


def test_end_to_end_readers_on_synthetic_timings():
    cell = spec.find_cell("ddp25.f32")
    r = run.Reading(cell, 19.5, _window(), {}, None, None)
    assert spec.reader("grad_gbs")(r) == 4.0
    assert spec.reader("call_us")(r) == 250_000.0
    assert spec.reader("step_ms")(r) == 500.0
    assert spec.reader("setup_s")(r) == 19.5
    empty = dataclasses.replace(r, window=_window(steps=0, calls=0,
                                                  seconds=0.0))
    assert spec.reader("grad_gbs")(empty) is None
    assert spec.reader("call_us")(empty) is None
    assert spec.reader("step_ms")(empty) is None


def test_a_tagged_metric_falls_back_to_its_base_reader(tmp_path):
    """``<base>.<tag>`` reads ``metrics/<base>.<tag>.py`` where it exists,
    and ``metrics/<base>.py`` otherwise."""
    for tag in ("bucket", "small", "decode"):
        assert spec.reader(f"idle_share.{tag}") is spec.reader("idle_share")
    assert spec.reader("coll_roofline.decode") is spec.reader("coll_roofline")
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "n.py").write_text(
        "def read(run):\n    return 1\n")
    (tmp_path / "metrics" / "n.own.py").write_text(
        "def read(run):\n    return 2\n")
    assert spec.reader("n.other", tmp_path)(None) == 1
    assert spec.reader("n.own", tmp_path)(None) == 2
    with pytest.raises(KeyError, match="no metrics file 'm'"):
        spec.reader("m.decode", tmp_path)


def test_a_misspelt_tag_is_refused():
    """A tag that differs from the others moving the same end-to-end
    metric would fall back to its base reader unseen; the cell is refused."""
    bench = spec.load_benchmark()
    spec.check_tags(bench)
    typo = dict(next(m for m in bench["per_layer"]
                     if m["name"] == "idle_share.decode"),
                name="idle_share.decod")
    bad = dict(bench, per_layer=bench["per_layer"] + [typo])
    with pytest.raises(ValueError, match="decod"):
        spec.find_cell("tp4-decode.bf16", bad)


def test_per_layer_readers_on_synthetic_timings():
    class Rec:
        def __init__(self, us):
            self.duration_us = us
    peak = peaks.peak_for("TPU v5 lite")
    n = 6_553_600
    least = 10 * 2 * 3 / 4 * n * 4 / (1600e9 / 8)
    cell = spec.find_cell("ddp25.f32")
    win = _window(issued=collections.Counter({("allreduce", n): 10}))
    ctx = run.Reading(cell, 1.0, win, peak, [Rec(u) for u in (5, 1, 9, 3)],
                      _reduction(0.25, 1.0, least * 2))
    assert spec.reader("driver_call_us.bucket")(ctx) == 4.0
    assert spec.reader("idle_share.bucket")(ctx) == pytest.approx(75.0)
    assert spec.reader("coll_roofline")(ctx) == pytest.approx(50.0)
    assert peaks.allreduce_least_s(n, 4, peak)[1] == "ici"
    # a decode step's bf16 rows: the least time counts two bytes an element
    rows = 32 * 4096
    decode = dataclasses.replace(
        ctx, cell=spec.find_cell("tp4-decode.bf16"),
        window=_window(itemsize=2,
                       issued=collections.Counter({("allreduce", rows): 65})),
        trace=_reduction(0.01, 1.0, 65 * 2 * 3 / 4 * rows * 2 / 200e9 * 4))
    assert spec.reader("coll_roofline.decode")(decode) == pytest.approx(25.0)
    one = dataclasses.replace(ctx, cell=dataclasses.replace(cell, chips=1))
    assert spec.reader("coll_roofline")(one) is None
    assert spec.reader("driver_call_us.small")(
        dataclasses.replace(ctx, records=[])) is None
    untraced = dataclasses.replace(ctx, records=None, trace=None)
    assert spec.reader("idle_share.small")(untraced) is None
    assert spec.reader("coll_roofline")(untraced) is None


@pytest.mark.parametrize("name,cell", [("plan_hit_share.bucket", "ddp25.f32"),
                                       ("plan_hit_share.small",
                                        "acclbench.local"),
                                       ("plan_hit_share.decode",
                                        "tp4-decode.bf16")])
def test_plan_hit_share_on_synthetic_counts(name, cell):
    share = spec.reader(name)
    cell = spec.find_cell(cell)

    def read(counts):
        return share(run.Reading(cell, 1.0, _window(), {}, None, None,
                                 plan_launches=counts))
    assert read({"hit": 192, "miss": 0, "fallback": 0}) == 100.0
    assert read({"hit": 6, "miss": 1, "fallback": 1}) == 75.0
    assert read({"hit": 0, "miss": 0, "fallback": 0}) is None
    assert read({}) is None and read(None) is None


def test_spread_of_a_set():
    """The quartiles' distance over the median; none for a count whose
    median is 0, such as the lowerings of a warmed-up window."""
    assert sets.spread([10.0, 11.0, 9.0, 10.0, 12.0, 8.0]) == pytest.approx(
        (11.25 - 8.75) / 10.0)
    assert sets.spread([0, 0, 0]) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak_for("TPU v9 imaginary")


def test_wire_and_hbm_bytes():
    assert peaks.allreduce_wire_bytes(1000, 4) == 6000.0
    assert peaks.allreduce_wire_bytes(1000, 1) == 0.0
    assert peaks.allreduce_hbm_bytes(1000) == 8000.0
    assert peaks.allreduce_wire_bytes(1000, 4, elem_bytes=2) == 3000.0


def test_parse_op_names():
    assert parse_op("%copy.1 = f32[6553600]{0:T(1024)} copy(f32[6553600]"
                    "{0:T(1024)} %x.1)") == ("copy.1 f32[6553600]", "copy")
    label, op = parse_op("%all-reduce-start.2 = (f32[8]{0}, u32[]{:S(2)}) "
                         "all-reduce-start(f32[8]{0} %p)")
    assert op == "all-reduce-start" and label.startswith("all-reduce-start.2")
    assert collective_base(op) == "all-reduce"
    assert collective_base("copy") is None


def test_reduce_recorded_one_chip_trace():
    """A 50 ms window of ``acclbench.local`` on one TPU v5 lite chip
    (``record_trace.py``): one-rank allreduces run a device copy, copy and
    combine run on the host."""
    red = reduce_trace(str(HERE / "data" / "local.xplane.pb"), [0])
    assert [c.plane for c in red.chips] == ["/device:TPU:0"]
    assert red.window_s == pytest.approx(0.05053053, rel=1e-6)
    assert red.busy_s == pytest.approx(2.2484e-05, rel=1e-6)
    assert red.collective_s == 0.0
    ops = dict(red.top("op_s"))
    assert set(ops) == {"copy.1 f32[200000]", "copy.1 f32[2000]",
                        "copy.1 f32[200]", "copy.1 f32[2]",
                        "copy.1 f32[20000]"}
    gaps = dict(red.top("gap_s"))
    assert set(gaps) == {"chipbench.call.allreduce", "chipbench.call.combine",
                         "chipbench.call.copy"}
    assert sum(gaps.values()) + red.busy_s == pytest.approx(red.window_s)
    with pytest.raises(ValueError, match="planes"):
        reduce_trace(str(HERE / "data" / "local.xplane.pb"), [0, 1])


def test_reduce_recorded_four_chip_trace():
    """A 10 ms window of ``ddp25.f32`` on a TPU v5 lite 2x2 host: the
    allreduce program's one operation is an ``all-reduce`` (instruction
    ``psum_invariant.7``), so all busy time is collective time."""
    red = reduce_trace(str(HERE / "data" / "ddp4.xplane.pb"), [0, 1, 2, 3])
    assert len(red.chips) == 4
    assert red.window_s == pytest.approx(0.077451911, rel=1e-6)
    assert red.busy_s == pytest.approx(0.024043487, rel=1e-6)
    assert red.collective_s == pytest.approx(red.busy_s)
    ops = dict(red.top("op_s"))
    assert ops == pytest.approx({"psum_invariant.7 f32[6553600]": 0.023635971,
                                 "psum_invariant.7 f32[5766400]": 0.000407516})
    gaps = dict(red.top("gap_s"))
    assert gaps == pytest.approx({"chipbench.call.allreduce": 0.041489031,
                                  "chipbench.wait": 0.008062583,
                                  "chipbench.window": 0.00385681})


# -- the command ---------------------------------------------------------------

def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", "acclbench.local",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_exits_nonzero_without_a_tpu():
    p = _cli(spec.ROOT)
    assert p.returncode != 0 and p.stdout == ""
    lines = [ln for ln in p.stderr.splitlines() if ln.strip()]
    assert lines and all("cpu" in ln for ln in lines), p.stderr


def test_cli_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
