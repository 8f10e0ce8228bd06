"""Distributed behaviour on 8 fake host devices.

These run in SUBPROCESSES with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single CPU device (per the dry-run isolation rule).
Each scenario script asserts internally and exits 0.
"""

import os
import subprocess
import sys
import textwrap

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never a chip
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    script = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    return proc.stdout


class TestShardMapMoe:
    def test_sharded_equals_local(self):
        _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_host_mesh
            from repro.models.moe import MoeConfig, init_moe, moe_apply
            from repro.models.params import Maker
            mesh = make_host_mesh(4, 2)
            cfg_l = MoeConfig(d_model=16, d_ff=32, n_experts=8, top_k=2,
                              capacity_factor=8.0)
            cfg_s = MoeConfig(d_model=16, d_ff=32, n_experts=8, top_k=2,
                              capacity_factor=8.0, ep=2)
            p = init_moe(Maker("init", jax.random.PRNGKey(0)), cfg_l)
            x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 16))
            out_local, aux_l = moe_apply(p, cfg_l, x)
            out_shard, aux_s = jax.jit(
                lambda p, x: moe_apply(p, cfg_s, x, mesh=mesh))(p, x)
            np.testing.assert_allclose(np.asarray(out_shard),
                                       np.asarray(out_local),
                                       rtol=2e-4, atol=2e-4)
            # aux is a per-shard metric pmean'd across shards; it equals the
            # local value only approximately (nonlinear in the partition).
            np.testing.assert_allclose(float(aux_s), float(aux_l), rtol=0.25)
            print("moe sharded == local OK")
        """)


class TestDistributedTraining:
    def test_train_step_on_mesh_matches_single_device(self):
        _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro import configs
            from repro.configs.base import ShapeCell
            from repro.launch.mesh import make_host_mesh
            from repro.launch.steps import build_step
            from repro.models import lm
            from repro.models.params import Maker
            from repro.optim import AdamWConfig, init_opt_state

            cfg = configs.get_config("qwen3-1.7b", smoke=True)
            shape = ShapeCell("t", "train", 16, 8)
            opt = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
            params = lm.init_lm(Maker("init", jax.random.PRNGKey(0)), cfg)
            batch = {"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab)}

            results = []
            for (d, m) in [(1, 1), (4, 2)]:
                mesh = make_host_mesh(d, m)
                b = build_step(cfg, shape, mesh, opt_cfg=opt,
                               param_dtype=jnp.float32, donate=False)
                opt_state = init_opt_state(params, opt)
                with mesh:
                    new_p, _, metrics = b.fn(params, opt_state, batch)
                results.append((float(metrics["loss"]),
                                jax.tree.leaves(new_p)[0]))
            assert abs(results[0][0] - results[1][0]) < 1e-4, results
            np.testing.assert_allclose(np.asarray(results[0][1]),
                                       np.asarray(results[1][1]),
                                       rtol=1e-4, atol=1e-4)
            print("mesh train == single-device train OK")
        """)

    def test_decode_step_on_mesh(self):
        _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro import configs
            from repro.configs.base import ShapeCell
            from repro.launch.mesh import make_host_mesh
            from repro.launch.steps import build_step
            from repro.models import lm
            from repro.models.params import Maker

            cfg = configs.get_config("gemma2-27b", smoke=True)
            mesh = make_host_mesh(4, 2)
            shape = ShapeCell("d", "decode", 32, 8)
            b = build_step(cfg, shape, mesh, param_dtype=jnp.float32,
                           donate=False)
            params = lm.init_lm(Maker("init", jax.random.PRNGKey(0),
                                      jnp.float32), cfg)
            cache = lm.init_cache(None, cfg, 8, 32, dtype=jnp.bfloat16)
            tok = jax.random.randint(jax.random.PRNGKey(1), (8, 1), 0,
                                     cfg.vocab)
            pos = jnp.zeros((8,), jnp.int32)
            with mesh:
                logits, new_cache = b.fn(params, cache, tok, pos)
            assert np.isfinite(np.asarray(logits)).all()
            print("mesh decode OK")
        """)


class TestElasticRemesh:
    def test_checkpoint_8_to_4_devices(self, tmp_path):
        _run(f"""
            import jax, jax.numpy as jnp, numpy as np
            from repro import checkpoint as ckpt
            from repro import configs
            from repro.configs.base import ShapeCell
            from repro.launch.mesh import make_host_mesh
            from repro.launch.steps import build_step
            from repro.launch.sharding import sharding_rules
            from repro.models import lm
            from repro.models.params import (Maker, abstract_params,
                                             param_axes, tree_shardings)
            from repro.optim import AdamWConfig, init_opt_state

            cfg = configs.get_config("smollm-360m", smoke=True)
            shape = ShapeCell("t", "train", 16, 8)
            opt = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=4)
            params = lm.init_lm(Maker("init", jax.random.PRNGKey(0)), cfg)
            opt_state = init_opt_state(params, opt)
            batch = {{"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab)}}

            # train 2 steps on an 8-device mesh, checkpoint
            mesh8 = make_host_mesh(8, 1)
            b8 = build_step(cfg, shape, mesh8, opt_cfg=opt, donate=False,
                            param_dtype=jnp.float32)
            with mesh8:
                for _ in range(2):
                    params, opt_state, m = b8.fn(params, opt_state, batch)
            ckpt.save(r"{tmp_path}", 2, {{"params": params, "opt": opt_state}})

            # "pod failure": resume on HALF the devices (4-device mesh)
            mesh4 = make_host_mesh(4, 1)
            rules = sharding_rules(cfg, kind="train")
            axes = param_axes(lambda mk: lm.init_lm(mk, cfg))
            ab = abstract_params(lambda mk: lm.init_lm(mk, cfg),
                                 dtype=jnp.float32)
            pshard = tree_shardings(axes, ab, rules, mesh4)
            from jax.sharding import NamedSharding, PartitionSpec as P
            oshard = {{"step": NamedSharding(mesh4, P()),
                       "m": pshard, "v": pshard}}
            state = ckpt.restore(r"{tmp_path}", 2,
                                 {{"params": params, "opt": opt_state}},
                                 shardings={{"params": pshard,
                                             "opt": oshard}})
            b4 = build_step(cfg, shape, mesh4, opt_cfg=opt, donate=False,
                            param_dtype=jnp.float32)
            with mesh4:
                p2, o2, m2 = b4.fn(state["params"], state["opt"], batch)
            assert np.isfinite(float(m2["loss"]))
            print("elastic 8->4 resume OK, loss", float(m2["loss"]))
        """)


class TestGradientCompression:
    def test_compressed_psum_close_to_exact(self):
        _run("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.launch.mesh import make_host_mesh
            from repro.optim import compressed_psum_tree, init_error_state

            mesh = make_host_mesh(8, 1)
            g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

            def body(g):
                grads = {"w": g[0]}
                err = {"w": jnp.zeros_like(g[0])}
                summed, new_err = compressed_psum_tree(grads, err, ("data",))
                return summed["w"], new_err["w"][None]

            out, err = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P("data", None),
                out_specs=(P(), P("data", None))))(g_global)
            want = g_global.mean(0)  # decoded psum is the DP mean
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       atol=0.05)
            print("int8 compressed psum OK, max err",
                  float(jnp.abs(out - want).max()))
        """)
