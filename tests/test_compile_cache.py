"""The persistent compile cache lands at one fixed path per checkout."""
import os
import subprocess
import sys

import pytest

from repro.utils.compile_cache import CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROBE = ("import jax; from repro.utils.compile_cache import "
          "enable_compile_cache as e; r = e(); "
          "print(r, jax.config.jax_compilation_cache_dir)")


def _probe(cwd, env_dir=None):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_cache_dir_is_fixed_under_the_repo():
    assert CACHE_DIR == os.path.join(REPO, ".jax_cache")


@pytest.mark.parametrize("cwd", ["repo", "elsewhere"])
def test_unset_env_uses_repo_cache_in_every_process(cwd, tmp_path):
    # one process per case, started from different directories
    returned, configured = _probe(REPO if cwd == "repo" else str(tmp_path))
    assert returned == configured == CACHE_DIR


def test_set_env_is_left_to_jax(tmp_path):
    mine = str(tmp_path / "cache")
    returned, configured = _probe(REPO, env_dir=mine)
    # JAX took the directory from the environment; nothing else was set
    assert returned == configured == mine
