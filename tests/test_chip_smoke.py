"""The chip smoke's contract off the chip, and the compile-cache helper the
entry points share.

``chip_smoke.py`` must never report a result without a TPU: on the CPU it
exits non-zero with no ``"ok"`` line, and so does a copy of the script
standing alone without the package.  Its ``--tiny`` rehearsal drives every
phase end to end at toy sizes (interpret-mode Pallas on the CPU)."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch.common import CHECKOUT_CACHE_DIR, enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
PHASES = ("device", "fcn", "lm-train", "serve", "autotune", "health")


def _run(args, cwd, cache_dir, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env.pop("PYTHONPATH", None)  # the script finds its own package
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=timeout,
    )


class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_dir_is_left_to_jax(self, monkeypatch, restore_cache_dir):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_path_in_checkout(
        self, monkeypatch, restore_cache_dir
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == CHECKOUT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
        assert CHECKOUT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")

    def test_cache_dir_is_ignored_by_git(self):
        with open(os.path.join(ROOT, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()


class TestChipSmoke:
    def test_no_tpu_no_result(self, tmp_path):
        out = _run([SMOKE], ROOT, tmp_path, 300)
        assert out.returncode != 0
        assert '"ok"' not in out.stdout
        assert "not a TPU" in out.stderr

    def test_script_alone_fails(self, tmp_path):
        shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        out = _run(["chip_smoke.py"], tmp_path, tmp_path / "cache", 300)
        assert out.returncode != 0
        assert '"ok"' not in out.stdout

    def test_tiny_rehearsal_passes_every_phase(self, tmp_path):
        out = _run([SMOKE, "--tiny"], ROOT, tmp_path, 600)
        log = out.stdout + out.stderr
        for phase in PHASES:
            assert f"[phase] {phase}: PASS" in out.stdout, log
        assert out.returncode == 3, log  # a rehearsal never reports ok
        assert '"ok"' not in out.stdout
