"""CLI launcher smoke tests (the deployable entry points)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")


def _run(mod, args, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m", mod] + args,
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=ROOT)


@pytest.mark.slow
def test_train_launcher():
    p = _run("repro.launch.train",
             ["--arch", "qwen3-8b", "--reduced", "--layers", "2",
              "--d-model", "64", "--steps", "8", "--batch", "2",
              "--seq", "32", "--log-every", "4"])
    assert p.returncode == 0, p.stdout + p.stderr
    assert "loss" in p.stdout


@pytest.mark.slow
def test_serve_launcher():
    p = _run("repro.launch.serve",
             ["--arch", "granite-3-8b", "--reduced", "--layers", "2",
              "--d-model", "64", "--backend", "hetero",
              "--admission", "loadctl", "--requests", "6", "--batch", "4",
              "--prompt-len", "4", "--max-new", "6", "--cache-len", "32",
              "--interval", "3"])
    assert p.returncode == 0, p.stdout + p.stderr
    assert "served 6 requests" in p.stdout


@pytest.mark.slow
def test_dryrun_list():
    p = _run("repro.launch.dryrun", ["--list", "--mesh", "both",
                                     "--strategy", "both"])
    assert p.returncode == 0, p.stderr
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    # 39 pairs x 2 meshes x 2 strategies
    assert len(lines) == 39 * 4


@pytest.fixture
def _restore_cache_dir():
    import jax
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_compile_cache_dir(env_dir, monkeypatch, _restore_cache_dir):
    """An outside JAX_COMPILATION_CACHE_DIR wins and nothing else is set;
    without one the cache sits at a fixed path in the checkout."""
    import jax
    from repro.launch.cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", None)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert enable_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir is None
