"""Distribution-layer tests on 8 forced host devices.

Runs in a subprocess-isolated pytest module: conftest must NOT set
XLA_FLAGS globally, so this module re-execs itself with the flag when the
device count is 1 (see _ensure_devices).
"""
import os
import subprocess
import sys

import pytest

# re-exec under 8 host devices if needed (keeps other test modules on 1)
if "XLA_FLAGS" not in os.environ and __name__ != "__main__":
    _HERE = os.path.abspath(__file__)

    def _run_self():
        env = dict(os.environ)
        # forced host devices: the child runs on the CPU, never on a chip
        # its parent may hold
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
        r = subprocess.run([sys.executable, "-m", "pytest", _HERE, "-q",
                            "--no-header", "-p", "no:cacheprovider"],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if r.returncode != 0:
            raise AssertionError(
                f"subprocess sharding tests failed:\n{r.stdout[-4000:]}\n"
                f"{r.stderr[-2000:]}")

    def test_sharding_suite_subprocess():
        _run_self()

else:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType
    from repro.configs import SHAPES_BY_NAME, get_smoke_config
    from repro.kernels import ref
    from repro.models import build_model
    from repro.sharding.seq_attention import (make_seq_decode_attn,
                                              make_seq_mla_decode_attn)
    from repro.sharding.strategies import make_strategy

    def _mesh(shape=(2, 4)):
        return jax.make_mesh(shape, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

    def test_device_count():
        assert len(jax.devices()) == 8

    def test_seq_sharded_decode_matches_ref():
        mesh = _mesh()
        B, T, H, KV, D = 4, 64, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        ck = jax.random.normal(ks[1], (B, T, KV, D))
        cv = jax.random.normal(ks[2], (B, T, KV, D))
        lengths = jnp.array([5, 64, 33, 17], jnp.int32)
        fn = make_seq_decode_attn(mesh, ("model",), ("data",), D ** -0.5)
        with mesh:
            out = jax.jit(fn)(q, ck, cv, lengths)
        want = ref.decode_attention(q, ck, cv, lengths, D ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_seq_sharded_decode_whole_mesh_pool():
        """Batch-1 long-context: KV pooled over ALL mesh axes."""
        mesh = _mesh()
        B, T, H, KV, D = 1, 128, 4, 1, 32
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        ck = jax.random.normal(ks[1], (B, T, KV, D))
        cv = jax.random.normal(ks[2], (B, T, KV, D))
        lengths = jnp.array([100], jnp.int32)
        fn = make_seq_decode_attn(mesh, ("data", "model"), None, D ** -0.5)
        with mesh:
            out = jax.jit(fn)(q, ck, cv, lengths)
        want = ref.decode_attention(q, ck, cv, lengths, D ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_seq_sharded_mla_matches_dense():
        mesh = _mesh()
        B, T, H, R, Rp = 2, 32, 4, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        q_lat = jax.random.normal(ks[0], (B, 1, H, R))
        q_rope = jax.random.normal(ks[1], (B, 1, H, Rp))
        latent = jax.random.normal(ks[2], (B, T, R))
        rope = jax.random.normal(ks[3], (B, T, Rp))
        lengths = jnp.array([20, 32], jnp.int32)
        scale = (R + Rp) ** -0.5
        fn = make_seq_mla_decode_attn(mesh, ("model",), ("data",), scale)
        with mesh:
            out = jax.jit(fn)(q_lat, q_rope, latent, rope, lengths)
        # dense oracle
        s = (jnp.einsum("bshr,btr->bhst", q_lat, latent)
             + jnp.einsum("bshp,btp->bhst", q_rope, rope)) * scale
        mask = jnp.arange(T)[None, None, None, :] < lengths[:, None, None, None]
        s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        want = jnp.einsum("bhst,btr->bshr", w, latent)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("strategy", ["monolithic", "crosspool"])
    @pytest.mark.parametrize("arch", [
        "qwen3-moe-235b-a22b",
        "minicpm3-4b",
        "zamba2-1.2b",
    ])
    def test_decode_step_lowers_and_matches_single_device(arch, strategy):
        """Smoke-scale decode step under each strategy == unsharded decode."""
        mesh = _mesh()
        cfg = get_smoke_config(arch).replace(dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(3))
        B, seq, max_len = 8, 8, 16
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, seq)),
                             jnp.int32)
        cache = model.init_cache(B, max_len)
        _, cache = model.prefill(params, tokens, cache)
        next_tok = jnp.zeros((B,), jnp.int32)
        want, _ = model.decode_step(params, next_tok, cache, jnp.int32(seq))

        shp = SHAPES_BY_NAME["decode_32k"]
        from dataclasses import replace as dc_replace
        shp = dc_replace(shp, seq_len=max_len, global_batch=B)
        strat = make_strategy(strategy, mesh, cfg, shp)
        hooks = strat.hooks()

        def step(p, t, c, l):
            return model.decode_step(p, t, c, l, hooks=hooks)

        with mesh:
            p_sh = jax.device_put(params, strat.params_shardings(params))
            c_sh = jax.device_put(cache, strat.cache_shardings(cache))
            got, new_cache = jax.jit(step)(p_sh, next_tok, c_sh,
                                           jnp.int32(seq))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_elastic_reshard_across_meshes():
        """Checkpoint written under a (2,4) mesh restores onto a (4,2)
        mesh (the lose-a-pod / re-provision recovery path)."""
        import tempfile
        from repro.configs import get_smoke_config as _gsc
        from repro.models import build_model as _bm
        from repro.training import checkpoint as ckpt
        from repro.sharding.strategies import make_strategy as _ms
        from repro.configs import SHAPES_BY_NAME as _SBN

        cfg = _gsc("qwen3-14b").replace(dtype="float32")
        model = _bm(cfg)
        params = model.init(jax.random.PRNGKey(7))
        mesh_a = _mesh((2, 4))
        mesh_b = _mesh((4, 2))
        strat_a = _ms("train", mesh_a, cfg, _SBN["train_4k"])
        strat_b = _ms("train", mesh_b, cfg, _SBN["train_4k"])
        p_a = jax.device_put(params, strat_a.params_shardings(params))
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(p_a, 1, d)
            spec = jax.eval_shape(lambda: params)
            restored, step = ckpt.restore(
                d, target_tree=spec,
                shardings=strat_b.params_shardings(params))
            assert step == 1
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored leaves actually live under the NEW mesh's sharding
        leaf = jax.tree.leaves(restored)[0]
        assert leaf.sharding.mesh.shape["data"] == 4

    def test_moe_a2a_matches_capacity_path():
        """Explicit all-to-all dispatch == XLA-SPMD capacity dispatch."""
        from repro.models import moe as moe_mod
        from repro.models import build_model as _bm
        mesh = _mesh()
        cfg = get_smoke_config("qwen3-moe-235b-a22b").replace(
            dtype="float32", n_experts=8, experts_per_token=2,
            capacity_factor=8.0)   # high cf: no drops -> exact equality
        key = jax.random.PRNGKey(0)
        p = moe_mod.init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, cfg.d_model))
        want, aux_w = moe_mod.apply_moe(p, x, cfg)
        a2a = moe_mod.make_moe_a2a(mesh, cfg, expert_axis="data",
                                   tp_axis="model", batch_axes=("data",),
                                   capacity_mult=8.0)
        with mesh:
            got, aux_g = jax.jit(lambda p, x: a2a(p, x))(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(aux_g), float(aux_w), rtol=1e-5)

    def test_f8_kv_cache_decode_close_to_bf16():
        """fp8 KV cache decode stays within quantization error."""
        from repro.models import build_model as _bm
        cfg = get_smoke_config("qwen3-14b").replace(dtype="float32")
        model = _bm(cfg)
        params = model.init(jax.random.PRNGKey(3))
        B, seq = 2, 8
        tokens = jnp.zeros((B, seq), jnp.int32)
        outs = {}
        for kv_dtype in (None, "f8"):
            cache = model.init_cache(B, 16, kv_dtype=kv_dtype)
            _, cache = model.prefill(params, tokens, cache)
            logits, _ = model.decode_step(params, jnp.zeros((B,), jnp.int32),
                                          cache, jnp.int32(seq))
            outs[kv_dtype] = np.asarray(logits)
        assert np.isfinite(outs["f8"]).all()
        # fp8 quantization error is bounded, logits stay close
        err = np.abs(outs["f8"] - outs[None]).max()
        scale = np.abs(outs[None]).max()
        assert err < 0.1 * scale + 0.5, (err, scale)

    @pytest.mark.parametrize("arch", ["qwen3-moe-235b-a22b", "qwen3-14b"])
    def test_train_forward_matches_single_device(arch):
        mesh = _mesh()
        cfg = get_smoke_config(arch).replace(dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(4))
        B, seq = 8, 16
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, cfg.vocab_size, (B, seq)),
            jnp.int32)
        want, _ = model.forward(params, tokens)

        strat = make_strategy("train", mesh, cfg, SHAPES_BY_NAME["train_4k"])
        hooks = strat.hooks()
        with mesh:
            p_sh = jax.device_put(params, strat.params_shardings(params))
            got, _ = jax.jit(lambda p, t: model.forward(p, t, hooks=hooks))(
                p_sh, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    if __name__ == "__main__":
        sys.exit(subprocess.call(
            [sys.executable, "-m", "pytest", os.path.abspath(__file__), "-q"]))
