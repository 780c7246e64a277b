"""Multi-device tests (8 forced host devices via subprocess): gradient
compression collectives, sharded train step numerics vs single-device,
checkpoint resharding across mesh shapes, and the HLO analysis tooling."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.sharded_subprocess]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run8(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"       # children never touch a chip
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_int8_ef_allreduce_matches_psum():
    out = _run8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.train import compression as C
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 4096)) * 2.0
        e = jnp.zeros_like(g)
        fn = jax.jit(jax.shard_map(
            lambda g, e: C.ef_allreduce_mean(g, e, "dp"),
            mesh=mesh, in_specs=(P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp")), check_vma=False))
        mean, err = fn(g, e)
        true = jnp.mean(g, axis=0)
        rel = float(jnp.max(jnp.abs(mean[0] - true))
                    / jnp.max(jnp.abs(true)))
        assert rel < 0.03, rel                  # int8 single shot
        # All shards agree exactly (it IS an all-reduce).
        m = np.asarray(mean)
        assert np.all(m == m[0:1]), "shards disagree"
        # Error feedback: residual bounded by the quantization step.
        q_step = float(jnp.max(jnp.abs(g + 0))) / 127.0
        assert float(jnp.max(jnp.abs(err))) <= q_step + 1e-6
        print("OK")
    """)
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    out = _run8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs.base import get_config, reduced_config
        from repro.models.transformer import Model
        from repro.train.optimizer import get_optimizer
        from repro.train.trainer import make_train_step, batch_pspecs
        cfg = reduced_config(get_config("smollm_135m"), vocab=512)
        devs = np.array(jax.devices())
        mesh8 = Mesh(devs.reshape(4, 2), ("data", "model"))
        mesh1 = Mesh(devs[:1].reshape(1, 1), ("data", "model"))
        key = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, 512),
                 "labels": jax.random.randint(key, (8, 32), 0, 512)}
        losses = []
        for mesh in (mesh1, mesh8):
            model = Model(cfg, mesh, compute_dtype=jnp.float32)
            with jax.default_device(jax.devices()[0]):
                params = model.init(0)
            opt = get_optimizer("adamw", lr=1e-3)
            state = opt.init(params)
            step = jax.jit(make_train_step(model, opt, accum_steps=2))
            for _ in range(3):
                params, state, m = step(params, state, batch,
                                        jnp.float32(1e-3))
            losses.append(float(m["loss"]))
        assert abs(losses[0] - losses[1]) < 1e-3, losses
        print("OK", losses)
    """)
    assert "OK" in out


def test_checkpoint_elastic_reshard_across_meshes():
    out = _run8("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.train.checkpoint import Checkpointer
        devs = np.array(jax.devices())
        meshA = Mesh(devs.reshape(8, 1), ("data", "model"))
        meshB = Mesh(devs.reshape(2, 4), ("data", "model"))
        w = jnp.arange(64.0).reshape(8, 8)
        wA = jax.device_put(w, NamedSharding(meshA, P("data", "model")))
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(3, {"w": wA})
        out, _ = ck.restore(3, {"w": jnp.zeros((8, 8))}, mesh=meshB,
                            specs={"w": P("data", "model")})
        assert out["w"].sharding.mesh.shape == meshB.shape
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
        print("OK")
    """)
    assert "OK" in out


def test_collective_parser_on_sharded_module():
    out = _run8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import collective_bytes
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        def f(xs):
            def body(c, x):
                s = jax.lax.with_sharding_constraint(
                    x.sum(0), NamedSharding(mesh, P()))
                return c + jnp.sum(s) + jnp.sum(x @ x.T), None
            return jax.lax.scan(body, 0.0, xs)[0]
        xs = jax.ShapeDtypeStruct((13, 1024, 64), jnp.float32)
        comp = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P(None, "data", None)),)).lower(xs).compile()
        cb = collective_bytes(comp.as_text())
        # all-gather of f32[64,1024] inside a 13-trip loop.
        assert cb["all-gather"] == 64 * 1024 * 4 * 13, cb
        assert cb["_counts"]["all-gather"] == 13
        print("OK")
    """)
    assert "OK" in out


def test_moe_ep_sharded_matches_replicated():
    out = _run8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs.base import get_config, reduced_config
        from repro.models import mlp as F
        from repro.models.common import AxisSizes, KeyGen
        import repro.models.mlp as mlp_mod
        mlp_mod.CAPACITY_FACTOR = 64.0    # avoid drop divergence
        cfg = reduced_config(get_config("granite_moe_3b"), d_ff=64)
        devs = np.array(jax.devices())
        p = F.init_moe(KeyGen(jax.random.PRNGKey(0)), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        outs = []
        for shape, axes in (((1, 1), ("data", "model")),
                            ((2, 4), ("data", "model"))):
            n = shape[0] * shape[1]
            mesh = Mesh(devs[:n].reshape(shape), axes)
            ax = AxisSizes.from_mesh(mesh)
            outs.append(np.asarray(
                jax.jit(lambda p, x: F.moe_mlp(p, x, cfg, ax, mesh))(p, x)))
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=2e-4)
        print("OK")
    """)
    assert "OK" in out
