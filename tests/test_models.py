"""Llama model family tests (CPU)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp


from accl_tpu.models import Llama, LlamaConfig

CPU = jax.devices("cpu")[0]


@pytest.fixture(scope="module")
def tiny():
    config = LlamaConfig.tiny()
    model = Llama(config)
    with jax.default_device(CPU):
        params = model.init(jax.random.key(0))
    return config, model, params


def test_forward_shapes(tiny):
    config, model, params = tiny
    tokens = jnp.zeros((2, 16), jnp.int32)
    with jax.default_device(CPU):
        logits = jax.jit(model.forward)(params, tokens)
    assert logits.shape == (2, 16, config.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_causality(tiny):
    """Changing a future token must not affect earlier logits."""
    config, model, params = tiny
    rng = np.random.default_rng(0)
    t1 = rng.integers(0, config.vocab_size, (1, 16)).astype(np.int32)
    t2 = t1.copy()
    t2[0, -1] = (t2[0, -1] + 1) % config.vocab_size
    with jax.default_device(CPU):
        l1 = model.forward(params, jnp.asarray(t1))
        l2 = model.forward(params, jnp.asarray(t2))
    np.testing.assert_allclose(np.asarray(l1)[0, :-1], np.asarray(l2)[0, :-1],
                               atol=1e-5)


def test_train_step_reduces_loss(tiny):
    import optax
    config, model, params = tiny
    optimizer = optax.adam(1e-2)
    with jax.default_device(CPU):
        opt_state = optimizer.init(params)
        step = jax.jit(model.make_train_step(optimizer))
        tokens = jnp.asarray(np.random.default_rng(1).integers(
            0, config.vocab_size, (4, 32)), jnp.int32)
        losses = []
        for _ in range(8):
            params, opt_state, loss = step(params, opt_state, tokens)
            losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_llama3_8b_geometry():
    config = LlamaConfig.llama3_8b()
    model = Llama(config)
    # analytic param count for the 8B geometry (no need to materialize)
    c = config
    per_layer = (2 * c.dim  # norms
                 + c.dim * c.n_heads * c.head_dim      # wq
                 + 2 * c.dim * c.n_kv_heads * c.head_dim  # wk, wv
                 + c.n_heads * c.head_dim * c.dim      # wo
                 + 3 * c.dim * c.ffn_dim)              # gate, up, down
    total = (c.vocab_size * c.dim * 2                  # embed + lm_head
             + c.n_layers * per_layer + c.dim)
    assert 7.9e9 < total < 8.2e9, total
    assert model.config.head_dim == 128


def test_grad_buckets(tiny):
    _, model, params = tiny
    buckets = model.grad_buckets(params, bucket_bytes=1 << 16)
    keys = [k for b in buckets for k in b]
    assert len(set(keys)) == len(keys)
    n_leaves = len(jax.tree.leaves(params))
    assert len(keys) == n_leaves


def test_sharded_forward_on_mesh(tiny):
    """dp x tp sharded forward on the virtual CPU mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    config, model, params = tiny
    devs = jax.devices("cpu")
    mesh = Mesh(np.asarray(devs[:8]).reshape(2, 4), ("dp", "tp"))
    sharded = model.shard_params(params, mesh)
    tokens = jax.device_put(
        jnp.zeros((4, 16), jnp.int32), NamedSharding(mesh, P("dp", None)))
    with jax.set_mesh(mesh):
        logits = jax.jit(lambda p, t: model.forward(p, t, dp="dp"))(sharded,
                                                                    tokens)
    with jax.default_device(CPU):
        ref = model.forward(params, jnp.zeros((4, 16), jnp.int32))
    # bf16 compute: sharded matmuls accumulate in different orders
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_kv_cache_decode_matches_full_forward():
    """forward_cached (prefill + per-token decode) must reproduce the full
    forward's next-token logits exactly — the standard KV-cache
    consistency check."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.models import Llama, LlamaConfig

    config = LlamaConfig.tiny(dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                              ffn_dim=64, max_seq_len=64)
    config = dataclasses.replace(config, dtype=jnp.float32)
    model = Llama(config)
    params = model.init(jax.random.key(0))
    B, S = 2, 10
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, config.vocab_size, (B, S)),
        jnp.int32)

    full = model.forward(params, tokens)          # (B, S, V)

    cache = model.init_kv_cache(B, max_len=S)
    # prefill first 6, then decode 4 one at a time
    logits, cache = model.forward_cached(params, tokens[:, :6], cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, :6]),
                               rtol=2e-4, atol=2e-4)
    for t in range(6, S):
        logits, cache = model.forward_cached(params, tokens[:, t:t + 1],
                                             cache)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, t]),
            rtol=2e-4, atol=2e-4, err_msg=f"position {t}")
    assert int(cache["pos"]) == S


def test_generate_greedy_deterministic():
    import jax
    import jax.numpy as jnp

    from accl_tpu.models import Llama, LlamaConfig

    config = LlamaConfig.tiny(dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                              ffn_dim=64, max_seq_len=64)
    model = Llama(config)
    params = model.init(jax.random.key(1))
    prompt = jnp.asarray([[5, 9, 3]], jnp.int32)
    a = model.generate(params, prompt, max_new=6)
    b = model.generate(params, prompt, max_new=6)
    assert a.shape == (1, 6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) >= 0).all() and \
        (np.asarray(a) < config.vocab_size).all()


@pytest.mark.parametrize("n_kv,shape", [(4, (2, 4)), (2, (2, 2))],
                         ids=["mha-tp4", "gqa-tp2"])
def test_sharded_flash_attention_matches_unsharded(tiny, n_kv, shape):
    """With a mesh passed, the GSPMD forward runs the fused flash kernel
    inside a shard_map over the tp head shards; in fp32 it must match
    the unsharded flash forward exactly (a wrong head/batch sharding —
    or a wrong per-shard GQA q-head-to-kv-head mapping in the gqa-tp2
    case — shifts every logit), and a train step through it must
    descend."""
    import dataclasses

    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg = dataclasses.replace(tiny[0], dtype=jnp.float32, n_heads=4,
                              n_kv_heads=n_kv)
    model = Llama(cfg)
    params_host = model.init(jax.random.key(0))
    tokens_host = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (4, 32)).astype(np.int32)
    ref = jax.jit(model.forward)(params_host, jnp.asarray(tokens_host))

    mesh = Mesh(np.array(jax.devices()[:shape[0] * shape[1]])
                .reshape(shape), ("dp", "tp"))
    with jax.set_mesh(mesh):
        params = model.shard_params(params_host, mesh)
        tokens = jax.device_put(tokens_host,
                                NamedSharding(mesh, P("dp", None)))
        fwd = jax.jit(lambda p, t: model.forward(p, t, dp="dp", mesh=mesh))
        out = fwd(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        opt = optax.adamw(1e-3)
        step = jax.jit(model.make_train_step(opt, dp="dp", mesh=mesh))
        st = opt.init(params)
        p, st, l0 = step(params, st, tokens)
        p, st, l1 = step(p, st, tokens)
        assert float(l1) < float(l0)


def test_tensor_parallel_train_rejects_indivisible_heads(tiny):
    """Training with mesh given fails LOUDLY when the tp axis size does
    not divide the head counts (forward_cached already raised here; a
    silent dense fallback would materialize the O(S^2) scores the fused
    path exists to avoid)."""
    import dataclasses

    from jax.sharding import Mesh

    cfg = dataclasses.replace(tiny[0], n_heads=4, n_kv_heads=2)
    model = Llama(cfg)
    params = model.init(jax.random.key(0))
    tokens = jnp.zeros((4, 16), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="must divide the head counts"):
            model.forward(params, tokens, dp="dp", mesh=mesh)
    # batch indivisible by dp: the dispatch raises a clear ValueError at
    # trace time instead of a cryptic shard_map divisibility error
    mesh2 = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    with jax.set_mesh(mesh2):
        with pytest.raises(ValueError, match="not divisible by dp"):
            jax.jit(lambda p, t: model.forward(p, t, dp="dp", mesh=mesh2)
                    ).trace(params, jnp.zeros((3, 16), jnp.int32))


def test_sequence_parallel_llama_via_ring_attention(tiny):
    """With mesh + sp given, the forward runs ring attention over the
    sequence shards (un-repeated GQA KV on every hop, no full-sequence
    gather): in fp32 it matches the unsharded flash forward exactly, the
    compiled program contains the ring's collective-permutes, and a
    train step through it descends."""
    import dataclasses

    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg = dataclasses.replace(tiny[0], dtype=jnp.float32, n_heads=4,
                              n_kv_heads=2)
    model = Llama(cfg)
    params_host = model.init(jax.random.key(0))
    tokens_host = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (4, 64)).astype(np.int32)
    ref = jax.jit(model.forward)(params_host, jnp.asarray(tokens_host))

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "sp"))
    with jax.set_mesh(mesh):
        params = jax.device_put(params_host, NamedSharding(mesh, P()))
        tokens = jax.device_put(tokens_host,
                                NamedSharding(mesh, P("dp", "sp")))
        fwd = jax.jit(lambda p, t: model.forward(p, t, dp="dp", sp="sp",
                                                 mesh=mesh))
        out = fwd(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        hlo = fwd.lower(params, tokens).compile().as_text()
        assert "collective-permute" in hlo
        opt = optax.adamw(1e-3)
        step = jax.jit(model.make_train_step(opt, dp="dp", sp="sp",
                                             mesh=mesh))
        st = opt.init(params)
        p, st, l0 = step(params, st, tokens)
        p, st, l1 = step(p, st, tokens)
        assert float(l1) < float(l0)


@pytest.mark.parametrize("n_kv,tp_size", [(4, 4), (2, 2)],
                         ids=["mha-tp4", "gqa-tp2"])
def test_tensor_parallel_generate_matches_unsharded(n_kv, tp_size):
    """generate() with mesh given decodes each tp shard's head group
    with the fused kernel over its own slice of the KV cache (no cache
    gather): greedy tokens are identical to the unsharded generate —
    including the GQA layout, whose q-head-shard -> kv-head-shard
    alignment is the subtle invariant of this path."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg = dataclasses.replace(
        LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=n_kv,
                         ffn_dim=128), dtype=jnp.float32)
    model = Llama(cfg)
    params_host = model.init(jax.random.key(0))
    prompt = np.array([[3, 7, 11, 2, 9], [1, 4, 1, 5, 9]], np.int32)
    ref = model.generate(params_host, jnp.asarray(prompt), max_new=6)

    mesh = Mesh(np.array(jax.devices()[:2 * tp_size]).reshape(2, tp_size),
                ("dp", "tp"))
    with jax.set_mesh(mesh):
        params = model.shard_params(params_host, mesh)
        p_sh = jax.device_put(prompt, NamedSharding(mesh, P("dp", None)))
        out = model.generate(params, p_sh, max_new=6, mesh=mesh, dp="dp")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_moe_llama_trains_and_decodes():
    """Mixtral-style variant: n_experts > 0 swaps every layer's SwiGLU
    for the routed expert block (models.moe math, Switch aux loss in
    loss()). Training descends, the cached forward matches the full
    forward exactly, and generation runs."""
    import dataclasses

    import optax

    cfg = dataclasses.replace(
        LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                         ffn_dim=96),
        n_experts=4, moe_top_k=2, dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 32)), jnp.int32)
    logits = jax.jit(model.forward)(params, tokens)
    assert np.isfinite(np.asarray(logits)).all()

    opt = optax.adam(1e-3)
    step = jax.jit(model.make_train_step(opt))
    st = opt.init(params)
    p, st, l0 = step(params, st, tokens)
    for _ in range(4):
        p, st, l = step(p, st, tokens)
    assert float(l) < float(l0)

    cache = model.init_kv_cache(2, 32)
    lc, _ = jax.jit(model.forward_cached,
                    static_argnames=("mesh", "dp", "tp"))(params, tokens,
                                                          cache)
    np.testing.assert_allclose(np.asarray(lc), np.asarray(logits),
                               rtol=2e-4, atol=2e-4)
    out = model.generate(params, tokens[:, :5], max_new=4)
    assert out.shape == (2, 4)

    # MoE + dp x tp sharding: the expert weights carry 4-D specs
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    with jax.set_mesh(mesh):
        sp = model.shard_params(params, mesh)
        tok = jax.device_put(np.asarray(tokens),
                             NamedSharding(mesh, P("dp", None)))
        out_sh = jax.jit(lambda p, t: model.forward(p, t, dp="dp"))(sp, tok)
        np.testing.assert_allclose(np.asarray(out_sh), np.asarray(logits),
                                   rtol=2e-4, atol=2e-4)
