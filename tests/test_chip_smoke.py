"""chip_smoke.py and the entry points' chip contract, on the CPU tier.

Each smoke phase runs here at a tiny size on the 8-device virtual CPU
mesh (Pallas interpreted) — the same code the chip runs at full size.
The entry points must refuse to report anything without a TPU, and the
compile cache must land where the contract says.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phase_driver_tiny():
    chip_smoke.phase_driver(0, cases=((np.float32, 4096),
                                      (chip_smoke.BF16, 4096)))


def test_phase_codec_tiny():
    chip_smoke.phase_codec(1, nbytes=1 << 15)


def test_phase_model_tiny():
    from accl_tpu.models.llama import LlamaConfig
    cfg = dataclasses.replace(LlamaConfig.tiny(), param_dtype=jnp.bfloat16)
    chip_smoke.phase_model(2, config=cfg, batch=2, prompt_len=16,
                           new_tokens=4)


def test_phase_cross_chip_on_four_virtual_devices():
    chip_smoke.phase_cross_chip(3, world=4, nbytes=1 << 14)


def test_model_config_is_llama3_8b_widths():
    c = chip_smoke.model_config()
    assert (c.dim, c.n_heads, c.n_kv_heads, c.ffn_dim, c.vocab_size,
            c.n_layers) == (4096, 32, 8, 14336, 128256, 4)


def test_main_without_tpu_fails_and_prints_no_result(capsys):
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        with pytest.raises(SystemExit) as e:
            chip_smoke.main([])
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("ACCL_BENCH_TIER", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


def test_bench_chip_path_without_tpu_exits_nonzero():
    proc = subprocess.run([sys.executable, "bench.py"], cwd=REPO,
                          env=_cpu_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip(), proc.stdout


def test_compile_cache_lands_in_env_dir(tmp_path):
    where = tmp_path / "cc"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from accl_tpu.utils.platform import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env=_cpu_env(JAX_COMPILATION_CACHE_DIR=str(where)))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(where)
    assert any(where.iterdir())


def test_compile_cache_default_is_the_checkout(monkeypatch):
    from accl_tpu.utils.platform import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

