"""Launch machinery on the host: HLO analysis, step builder, rules."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.base import ShapeCell, cell_supported, input_specs
from repro.launch.compile_cache import ENV_VAR as CACHE_ENV_VAR
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_host_mesh, mesh_chips
from repro.launch.sharding import sharding_rules
from repro.launch.steps import build_step
from repro.models.params import LogicalAxes, resolve_spec
from repro.optim import AdamWConfig

ROOT = Path(__file__).resolve().parents[1]


class TestHloAnalysis:
    def test_scan_trip_count_multiplies(self):
        mesh = make_host_mesh(1, 1)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)

        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, None, length=7)
            return y

        comp = jax.jit(f).lower(x, w).compile()
        a = analyze_hlo(comp.as_text())
        assert a["flops"] == pytest.approx(7 * 2 * 8 * 64 * 64, rel=0.01)

    def test_collectives_counted(self):
        # verified behaviourally in the dry-run artifacts; here: no
        # collectives on a single device
        comp = jax.jit(lambda x: x * 2).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
        a = analyze_hlo(comp.as_text())
        assert a["collective_bytes"] == 0


class TestShardingRules:
    def test_fsdp_auto_detection(self):
        big = sharding_rules(configs.get_config("gemma2-27b"))
        small = sharding_rules(configs.get_config("qwen3-1.7b"))
        assert big["embed"] == ("pod", "data")
        assert small["embed"] is None

    def test_decode_kv_rule(self):
        r = sharding_rules(configs.get_config("qwen3-1.7b"), kind="decode")
        assert r["kv_seq"] == "model"
        r = sharding_rules(configs.get_config("jamba-v0.1-52b"),
                           kind="decode", long_ctx=True)
        assert r["kv_seq"] == ("data", "model")

    @staticmethod
    def _mesh22():
        # resolve_spec only reads mesh.shape; a stub avoids needing 4
        # real devices in the main pytest process.
        import types
        return types.SimpleNamespace(shape={"data": 2, "model": 2})

    def test_resolver_drops_nondivisible(self):
        mesh = self._mesh22()
        spec = resolve_spec(LogicalAxes(("heads",)), (15,),
                            {"heads": "model"}, mesh)
        assert spec == P(None)
        spec = resolve_spec(LogicalAxes(("heads",)), (16,),
                            {"heads": "model"}, mesh)
        assert spec == P("model")

    def test_resolver_no_axis_reuse(self):
        mesh = self._mesh22()
        spec = resolve_spec(LogicalAxes(("embed", "mlp")), (8, 8),
                            {"embed": "model", "mlp": "model"}, mesh)
        assert spec == P("model", None)


class TestBuildStep:
    def test_train_lowers_on_host_mesh(self):
        cfg = configs.get_config("qwen3-1.7b", smoke=True)
        shape = ShapeCell("t", "train", 16, 4)
        mesh = make_host_mesh(1, 1)
        b = build_step(cfg, shape, mesh, opt_cfg=AdamWConfig(),
                       param_dtype=jnp.float32)
        with mesh:
            compiled = b.fn.lower(*b.args_abstract).compile()
        assert compiled.cost_analysis() is not None

    def test_decode_lowers_on_host_mesh(self):
        cfg = configs.get_config("xlstm-1.3b", smoke=True)
        shape = ShapeCell("d", "decode", 32, 2)
        mesh = make_host_mesh(1, 1)
        b = build_step(cfg, shape, mesh, param_dtype=jnp.float32)
        with mesh:
            compiled = b.fn.lower(*b.args_abstract).compile()
        assert compiled is not None

    def test_input_specs_cover_all_cells(self):
        for arch in configs.ARCHS:
            cfg = configs.get_config(arch)
            for shape in configs.SHAPES.values():
                ok, _ = cell_supported(cfg, shape)
                if not ok:
                    continue
                specs = input_specs(cfg, shape)
                assert jax.tree.leaves(specs), (arch, shape.name)

    def test_long_500k_only_subquadratic(self):
        shape = configs.SHAPES["long_500k"]
        supported = [a for a in configs.ARCHS
                     if cell_supported(configs.get_config(a), shape)[0]]
        assert sorted(supported) == ["jamba-v0.1-52b", "xlstm-1.3b"]

    def test_mesh_chips(self):
        assert mesh_chips(make_host_mesh(1, 1)) == 1


class TestHostMeshValidation:
    def test_too_many_devices_is_a_clear_error(self):
        """Over-asking must name the fix (XLA_FLAGS recipe), not
        surface as an opaque reshape failure."""
        have = jax.device_count()
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            make_host_mesh(have + 1, 1)
        with pytest.raises(ValueError,
                           match=rf"needs {2 * (have + 3)} devices"):
            make_host_mesh(have + 3, 2)

    def test_degenerate_axes_rejected(self):
        with pytest.raises(ValueError, match="axes must be >= 1"):
            make_host_mesh(0, 1)
        with pytest.raises(ValueError, match="axes must be >= 1"):
            make_host_mesh(1, -2)

    def test_full_device_count_is_valid(self):
        mesh = make_host_mesh(jax.device_count(), 1)
        assert dict(mesh.shape)["data"] == jax.device_count()

    def test_chip_host_error_names_no_cpu_flag(self, monkeypatch):
        """Forcing host devices cannot add chips: on a TPU host the
        error must not point at XLA_FLAGS."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="more chips") as e:
            make_host_mesh(jax.device_count() + 1, 1)
        assert "XLA_FLAGS" not in str(e.value)


class TestCompileCache:
    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(CACHE_ENV_VAR, str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_env_uses_the_checkout_dir(self, monkeypatch):
        monkeypatch.delenv(CACHE_ENV_VAR, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            got = enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == got
            assert enable_compile_cache() == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert Path(got) == ROOT / ".jax_cache"
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_a_tpu(tmp_path, alone):
    """Off a TPU, or copied away from the repo, chip_smoke.py exits
    nonzero and prints no result line."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout
    assert "FAILED" in proc.stderr
