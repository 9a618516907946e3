"""Test config: the 8-device virtual CPU platform, unless ACCL_TEST_TPU=1.

Three execution tiers (the reference's emulation/simulation/hardware
story, SURVEY §4):

1. default (CPU): the full corpus on the virtual 8-device CPU platform —
   Pallas kernels run interpreted; shard_map programs run on the
   virtual mesh. Fast, no TPU needed.
2. hardware (``ACCL_TEST_TPU=1``, on the chip through the chip tool):
   the same corpus against the real chip — Pallas kernels
   Mosaic-compile, and the gated ``test_tpu_world_real_chip`` drives the
   driver tier on-device. The last on-chip pass is recorded in
   CHANGES.md (PR 21).
3. multi-chip dryrun: ``__graft_entry__.dryrun_multichip`` (hermetic
   CPU-mesh child process) covering dp/tp/pp/ep/sp/ddp.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

if not os.environ.get("ACCL_TEST_TPU"):
    import jax

    jax.config.update("jax_platforms", "cpu")


def dense_attention(q, k, v, causal):
    """Shared golden reference for every attention test (flash, ring,
    ulysses): fp32 softmax(QK^T/sqrt(d))V with optional causal mask."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    if causal:
        Sq, Skv = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((Sq, Skv), bool), k=Skv - Sq)
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak/chaos tests (tier-1 deselects them "
        "with -m 'not slow'; run explicitly or via the full corpus)")


def _shm_leftovers():
    try:
        return sorted(f for f in os.listdir("/dev/shm")
                      if f.startswith("accl_shm_"))
    except FileNotFoundError:  # non-tmpfs platform
        return []


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _shm_leak_sweep():
    """Post-test /dev/shm sweep (the ShmFabric teardown contract,
    emulator/shm.py): every ``accl_shm_*`` segment must be unlinked by
    world teardown. Sweeping after EVERY test makes the leaking test
    fail itself instead of poisoning a later victim; leaked names are
    removed so the rest of the run is not double-punished. Listing
    /dev/shm is one getdents call — noise-free for the 99% of tests
    that never touch the fabric."""
    pre = _shm_leftovers()
    yield
    leaked = [f for f in _shm_leftovers() if f not in pre]
    if leaked:
        for name in leaked:
            try:
                os.unlink(os.path.join("/dev/shm", name))
            except OSError:
                pass
        pytest.fail(
            f"test leaked {len(leaked)} shm segment(s): {leaked} — "
            f"ShmFabric worlds must be torn down (a.deinit() / "
            f"daemon.shutdown()) before the test returns")


@pytest.fixture(autouse=True)
def _window_leak_sweep():
    """Post-test RMA-window sweep (the shm-sweep convention applied to
    the one-sided address namespace, rma/window.py): a CLOSED registry
    still holding registrations means a test registered windows after
    deinit, or a teardown path forgot to purge — stale windows would
    keep accepting peer puts into reclaimed memory. Leftovers are
    cleared so the leaking test fails itself instead of poisoning a
    later victim."""
    from accl_tpu.rma.window import sweep_leaked
    sweep_leaked()                 # pre-clean prior crashes' leftovers
    yield
    leaked = sweep_leaked()
    if leaked:
        pytest.fail(
            f"test leaked RMA window registrations: {leaked} — a "
            f"deinitialized world's registry must be empty")
