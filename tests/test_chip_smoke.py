"""chip_smoke.py off the chip: its refusals, and its phases at a small width.

On the CPU the kernels run in interpret mode and the compiled program holds
no Mosaic call, so the phase tests stand in the kernel names the chip would
report; tests/test_chip_compile.py checks those names on compiled text.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

# qwen-7b's layout at a width whose every weight tiles the kernels (4
# contraction blocks: strategy2's sparse packing applies)
SMALL = dict(n_layers=1, d_model=512, d_ff=1024, n_heads=4, n_kv_heads=2,
             head_dim=128, vocab_size=256, dtype=jnp.bfloat16)


def _run(args, cwd, **env):
    return subprocess.run([sys.executable, *args], cwd=cwd, text=True,
                          capture_output=True, timeout=600,
                          env=dict(os.environ, **env))


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and '"ok"' in lines[-1])


def test_refuses_without_a_tpu():
    proc = _run([SCRIPT], ROOT, JAX_PLATFORMS="cpu")
    assert _no_result(proc), proc.stdout[-2000:]
    assert "no TPU" in proc.stderr


def test_fails_outside_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    proc = _run([str(tmp_path / "chip_smoke.py")], str(tmp_path),
                JAX_PLATFORMS="cpu", PYTHONPATH="")
    assert _no_result(proc), proc.stdout[-2000:]


@pytest.fixture
def smoke(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    from repro.configs import get_smoke_config

    def small(*, use_kernels, **overrides):
        return get_smoke_config("qwen-7b", kv_layout="paged",
                                use_kernels=use_kernels,
                                **{**SMALL, **overrides})
    monkeypatch.setattr(chip_smoke, "model_config", small)
    monkeypatch.setattr(chip_smoke, "mosaic_kernels", lambda text: {
        chip_smoke.ATTENTION_KERNEL, "ffn_fused_sparse"})
    return chip_smoke


def test_single_chip_phases(smoke, capsys):
    smoke.single_chip(jax.devices()[0], seed=0)
    out = capsys.readouterr().out
    counts = [json.loads(line.split(" ", 1)[1]) for line in out.splitlines()
              if line.startswith("phase2 {\"ticks\"")][0]
    # one mixed tick prefills every prompt, then one decode tick per token
    assert counts["mixed_ticks"] == 1
    assert counts["dispatches"] == counts["ticks"] == smoke.MAX_NEW_TOKENS
    assert counts["compile_keys"] == [["decode", 4], ["mixed", 16]]


def test_kernel_phase_fails_on_a_fallback(smoke, monkeypatch):
    """A kernel that gives way to its twin fails the kernel phase."""
    from repro.kernels import ops
    real = ops.mixed_attention

    def with_fallback(*a, **k):
        ops._fallback("mixed_attention", "forced by the test")
        return real(*a, **k)
    monkeypatch.setattr(ops, "mixed_attention", with_fallback)
    with pytest.raises(ops.KernelFallbackWarning):
        smoke.single_chip(jax.devices()[0], seed=0)


def test_kernel_phase_fails_without_its_kernels(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "mosaic_kernels", lambda text: set())
    with pytest.raises(smoke.PhaseFailed, match="mixed_flash_attention"):
        smoke.single_chip(jax.devices()[0], seed=0)


_MESH_WORKER = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {root!r})
    import jax
    import chip_smoke
    from repro.configs import get_smoke_config
    chip_smoke.model_config = lambda *, use_kernels, **o: get_smoke_config(
        "qwen-7b", kv_layout="paged", use_kernels=use_kernels,
        **{{**{small}, **o}})
    chip_smoke.mesh(jax.devices()[0], seed=0)
""")


def test_mesh_phase_on_four_host_devices():
    small = {k: v for k, v in SMALL.items() if k != "dtype"}
    proc = _run(["-c", _MESH_WORKER.format(root=ROOT, small=small)], ROOT,
                JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    sharded = [json.loads(line.split(" ", 1)[1])
               for line in proc.stdout.splitlines()
               if line.startswith("mesh-sharded {\"n_homes\"")][0]
    assert sharded["n_homes"] == 4
    per_dev = sharded["pool_bytes_by_device"]
    assert sorted(per_dev) == ["0", "1", "2", "3"]
    assert all(4 * b == sharded["pool_bytes_total"] for b in per_dev.values())


class TestCompileCache:
    """The entry points' persistent compile cache: where the environment
    says, else one fixed path in the checkout."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_environment_wins(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        was = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was  # JAX reads it

    def test_fixed_path_in_the_checkout(self, monkeypatch):
        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
