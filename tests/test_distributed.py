"""Multi-device integration tests.

These need >1 XLA device, so they run in a subprocess with
``--xla_force_host_platform_device_count`` (never set in the parent — the
rest of the suite must see one device)."""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(body: str, devices: int = 8, timeout: int = 900):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax, jax.numpy as jnp, numpy as np
        from dataclasses import replace
        from repro.config import reduced_config, ShapeConfig
        from repro.models import model as M
        from repro.sharding import make_plan, make_recipe
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.slow
def test_sharded_loss_matches_local():
    out = _run("""
        rng = np.random.default_rng(0)
        for name in ("gemma3-12b", "xlstm-125m", "hymba-1.5b"):
            cfg = replace(reduced_config(name), dtype="float32")
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            B, S = 8, 32
            batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B,S)), jnp.int32),
                     "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B,S)), jnp.int32)}
            loss_ref, _ = M.loss_fn(params, batch, cfg)
            plan = make_plan(mesh, cfg, fsdp=True)
            recipe = make_recipe(plan, cfg, ShapeConfig("t", S, B, "train"))
            with mesh:
                loss_sh, _ = jax.jit(lambda p, b: M.loss_fn(p, b, cfg, recipe))(params, batch)
            assert abs(float(loss_ref) - float(loss_sh)) < 2e-3, (name, float(loss_ref), float(loss_sh))
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_ep_moe_exact_at_full_capacity():
    out = _run("""
        rng = np.random.default_rng(0)
        for name in ("deepseek-v2-236b", "llama4-scout-17b-a16e"):
            cfg = replace(reduced_config(name), dtype="float32")
            cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            B, S = 8, 32
            batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B,S)), jnp.int32),
                     "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B,S)), jnp.int32)}
            _, m_ref = M.loss_fn(params, batch, cfg)
            plan = make_plan(mesh, cfg, fsdp=True)
            recipe = make_recipe(plan, cfg, ShapeConfig("t", S, B, "train"))
            with mesh:
                _, m_sh = jax.jit(lambda p, b: M.loss_fn(p, b, cfg, recipe))(params, batch)
            d = abs(float(m_ref["xent"]) - float(m_sh["xent"]))
            assert d < 2e-4, (name, d)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_isp_decode_matches_local_decode():
    out = _run("""
        rng = np.random.default_rng(0)
        for name in ("gemma3-12b", "yi-9b"):
            cfg = replace(reduced_config(name), dtype="float32")
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            B, S = 8, 32
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
            caches_l = M.init_caches(cfg, B, S)
            caches_s = M.init_caches(cfg, B, S)
            plan = make_plan(mesh, cfg, fsdp=False)
            recipe = make_recipe(plan, cfg, ShapeConfig("d", S, B, "decode"))
            dec_sh = jax.jit(lambda p, c, t, pos: M.decode_fn(p, c, t, pos, cfg, recipe))
            with mesh:
                for t in range(6):
                    nl, caches_l = M.decode_fn(params, caches_l, toks[:, t:t+1], jnp.int32(t), cfg)
                    ns, caches_s = dec_sh(params, caches_s, toks[:, t:t+1], jnp.int32(t))
                    assert (np.asarray(nl) == np.asarray(ns)).all(), (name, t)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_compiles_small_mesh_all_archs():
    """Every (arch × mode) lowers AND compiles on a 3-axis mesh."""
    out = _run("""
        from repro.launch import steps as S
        from repro.configs import ASSIGNED
        for name in ASSIGNED:
            cfg = reduced_config(name)
            plan = make_plan(mesh, cfg, fsdp=True)
            for shape in (ShapeConfig("t", 32, 8, "train"),
                          ShapeConfig("p", 32, 8, "prefill"),
                          ShapeConfig("d", 32, 8, "decode")):
                recipe = make_recipe(plan, cfg, shape)
                fn, args = S.jitted_step_for(cfg, shape, recipe)
                with mesh:
                    fn.lower(*args).compile()
        print("OK")
    """, timeout=2400)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_psum_matches_psum():
    out = _run("""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.optim import compressed_psum
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
        def f(x):
            key = jax.random.fold_in(jax.random.PRNGKey(0), jax.lax.axis_index("pod"))
            return compressed_psum(x, "pod", key)
        g = shard_map(f, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                      check_vma=False)
        got = g(x)
        # exact psum of the two pod shards
        want = x[:4] + x[4:]
        want = jnp.concatenate([want, want], axis=0)
        err = float(jnp.abs(got - want).max())
        amax = float(jnp.abs(x).max())
        assert err <= 2 * 2 * amax / 127.0 + 1e-6, err
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_cluster_drives_on_own_devices_match_one_drive():
    """Four drives behind data_local routing on an eight-device host: each
    has its own params copy, caches and decode state on its own device
    (drive d on device d), and they emit the tokens one drive emits for the
    same requests."""
    out = _run("""
        from repro.train.cluster_loop import ClusterEngine
        from repro.train.serve_loop import ServeEngine
        cfg = replace(reduced_config("yi-9b"), dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        reqs = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
                for n in rng.integers(4, 60, 12)]
        kw = dict(max_len=128, num_slots=4, k_block=4, chunk_prefill=32,
                  prewarm=True)
        devices = jax.devices()[:4]
        cluster = ClusterEngine(cfg, params, n_drives=4, routing="data_local",
                                **kw)
        for i, p in enumerate(reqs):
            cluster.submit(p, max_new=6, shard_id=i % 4)
        got = cluster.run_until_complete()
        for d in cluster.drives:
            e = d.engine
            on = {x.device for x in jax.tree.leaves(
                (e.params, e.caches, e._tok_dev, e._pages_dev))}
            assert on == {devices[d.drive_id]}, (d.drive_id, on)
            e.pager.check_balanced()
        assert sorted({r.drive for r in got}) == [0, 1, 2, 3]
        one = ServeEngine(cfg, params, **kw)
        want = one.generate(reqs, max_new=6)
        assert [r.tokens for r in got] == [r.tokens for r in want]
        print("OK")
    """)
    assert "OK" in out
