"""What `import paddle_tpu` may and may not do to the process.

A process that has initialised a JAX backend holds the chip, and a child that
needs it then fails or hangs — so importing the package (as every launcher
parent does) must leave JAX uninitialised. The import also places the
persistent compilation cache, by one rule: JAX_COMPILATION_CACHE_DIR where
the environment sets it, `<checkout>/.jax_cache` otherwise.

Each case runs in a child `python -c` (the test process itself has long
since initialised a backend).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env)
    return subprocess.run([sys.executable, "-c", code], env=full, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("module", [
    "paddle_tpu",
    "paddle_tpu.distributed.launch",
    "paddle_tpu.text.models.gpt",
])
def test_import_initialises_no_backend(module):
    p = _child(f"import {module}, jax\n"
               "print('BACKENDS', sorted(jax._src.xla_bridge._backends))")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "BACKENDS []" in p.stdout, p.stdout


def test_default_generator_key_is_built_on_first_use():
    p = _child(
        "import paddle_tpu as paddle, jax\n"
        "from paddle_tpu.core import random as r\n"
        "assert r.default_generator._key_tensor is None\n"
        "paddle.seed(7)\n"
        "a = paddle.rand([4]).numpy()\n"
        "paddle.seed(7)\n"
        "b = paddle.rand([4]).numpy()\n"
        "assert (a == b).all() and r.default_generator._key.name == "
        "'generator_key'\n"
        "print('OK', sorted(jax._src.xla_bridge._backends))")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "OK ['cpu']" in p.stdout, p.stdout


_CACHE_DIR = ("import paddle_tpu, jax\n"
              "print('CACHE', jax.config.jax_compilation_cache_dir)")


def test_cache_dir_defaults_to_the_checkout():
    p = _child(_CACHE_DIR)
    assert p.returncode == 0, p.stderr[-2000:]
    assert f"CACHE {os.path.join(REPO, '.jax_cache')}\n" in p.stdout


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    want = str(tmp_path / "xla_cache")
    p = _child(_CACHE_DIR, JAX_COMPILATION_CACHE_DIR=want)
    assert p.returncode == 0, p.stderr[-2000:]
    assert f"CACHE {want}\n" in p.stdout
    assert os.path.isdir(want)


def test_unusable_cache_dir_from_the_environment_is_an_error(tmp_path):
    blocker = tmp_path / "a_file"
    blocker.write_text("")
    p = _child(_CACHE_DIR,
               JAX_COMPILATION_CACHE_DIR=str(blocker / "cache"))
    assert p.returncode != 0
    assert "JAX_COMPILATION_CACHE_DIR" in p.stderr
