"""Launch-layer tests: sharding rules, mesh isolation, and a subprocess
dry-run smoke (small forced-device mesh so the main test process keeps its
single-device view)."""
import os
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sharding_policy_rules():
    # pure-python checks of the mapping logic (no devices needed)
    import jax
    from jax.sharding import PartitionSpec

    from repro.launch.sharding import ShardingPolicy

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    pol = ShardingPolicy.__new__(ShardingPolicy)
    object.__setattr__(pol, "mesh", FakeMesh())
    object.__setattr__(pol, "policy", "fsdp")
    object.__setattr__(pol, "context_parallel", False)
    object.__setattr__(pol, "opt_unembed_gather", False)

    # mlp kernel (embed, mlp): fsdp -> ('data', 'model')
    spec = pol.param_spec((2048, 6144), PartitionSpec("embed", "mlp"))
    assert tuple(spec) == (("pod", "data")[1:], "model") or \
        tuple(spec) == ("data", "model")
    # indivisible dims fall back to replication, never error
    spec = pol.param_spec((7, 13), PartitionSpec("embed", "mlp"))
    assert tuple(spec) == (None, None)
    # batch spec: 256 over data=16
    assert pol.batch_spec(256)[0] == "data"
    assert pol.batch_spec(1)[0] is None


def _run_snippet(code: str, device_count: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={device_count}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_dryrun_smoke_small_mesh():
    """Lower + compile a smoke-config train step on a 2x4 mesh with explicit
    shardings — the same code path dryrun.py uses at 16x16/2x16x16."""
    stdout = _run_snippet("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from repro import configs
        from repro.launch.sharding import ShardingPolicy
        from repro.models import lm
        from repro.optim import AdamWConfig, adamw_init
        from repro.optim.adamw import AdamWState

        from jax.sharding import AxisType
        mesh_kw = dict(axis_types=(AxisType.Auto,) * 2)
        cfg = configs.get_config("qwen3-1.7b", smoke=True)
        mesh = jax.make_mesh((2, 4), ("data", "model"), **mesh_kw)
        pol = ShardingPolicy(mesh, "fsdp")
        shapes, specs = lm.abstract_params(cfg)
        psh = pol.param_shardings(shapes, specs)
        opt_shapes = jax.eval_shape(adamw_init, shapes)
        opt_sh = AdamWState(step=NamedSharding(mesh, PartitionSpec()),
                            mu=psh, nu=psh)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 33), jnp.int32)}
        bsh = {"tokens": pol.data_sharding(8, 2)}
        step = lm.make_train_step(cfg, AdamWConfig(), remat="full",
                                  shard_fn=pol.shard_fn)
        with mesh:
            compiled = jax.jit(step, in_shardings=(psh, opt_sh, bsh)) \\
                .lower(shapes, opt_shapes, batch).compile()
        ma = compiled.memory_analysis()
        print("OK", ma.temp_size_in_bytes > 0)
    """)
    assert "OK True" in stdout


def test_dryrun_multipod_mesh_small():
    """The 3-axis (pod, data, model) mesh lowers a sharded decode step."""
    stdout = _run_snippet("""
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.launch.sharding import ShardingPolicy
        from repro.models import lm

        from jax.sharding import AxisType
        mesh_kw = dict(axis_types=(AxisType.Auto,) * 3)
        cfg = configs.get_config("qwen3-1.7b", smoke=True)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), **mesh_kw)
        pol = ShardingPolicy(mesh, "tp")
        shapes, specs = lm.abstract_params(cfg)
        psh = pol.param_shardings(shapes, specs)
        caches = jax.eval_shape(lambda: lm.init_caches(cfg, 8, 64,
                                                       dtype=jnp.float32))
        csh = pol.cache_sharding(caches, 8)
        tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        ln = jax.ShapeDtypeStruct((8,), jnp.int32)
        fn = lm.make_decode_step(cfg, pol.shard_fn)
        with mesh:
            compiled = jax.jit(fn, in_shardings=(
                psh, csh, pol.data_sharding(8, 2), pol.data_sharding(8, 1))) \\
                .lower(shapes, caches, tok, ln).compile()
        print("OK", compiled.cost_analysis() is not None)
    """, device_count=8)
    assert "OK True" in stdout


def test_elastic_restore_resharding(tmp_path):
    """Checkpoint written on one topology restores onto another (the
    elastic-rescale path): values must be identical after re-shard."""
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    tree = {"w": jnp.arange(64.0).reshape(8, 8)}
    save_checkpoint(str(tmp_path), 1, tree)
    # restore with an explicit (single-device) sharding tree
    shard = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    restored, _ = restore_checkpoint(
        str(tmp_path), tree, shardings={"w": shard})
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
