"""Where the entry points keep JAX's persistent compilation cache."""

import os
import subprocess
import sys
from pathlib import Path

from repro.launch.compile_cache import REPO_CACHE_DIR, compile_cache_dir

REPO = Path(__file__).resolve().parents[1]

_PROBE = (
    "import jax; from repro.launch.compile_cache import enable_compile_cache; "
    "d = enable_compile_cache(); print(d); "
    "print(jax.config.jax_compilation_cache_dir)"
)


def _probe(env_dir: str | None) -> list[str]:
    """Call the helper in a fresh process, so this one never enables a cache."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, capture_output=True,
        text=True, check=True,
    )
    return out.stdout.split()


def test_env_var_wins_and_helper_sets_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    # JAX read the variable itself; the helper left the setting alone
    assert _probe(str(tmp_path)) == [str(tmp_path), str(tmp_path)]


def test_default_is_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert REPO_CACHE_DIR == REPO / ".jax_cache"
    assert compile_cache_dir() == compile_cache_dir() == str(REPO_CACHE_DIR)
    # the same path in other processes, and it is the one JAX is given
    first, second = _probe(None), _probe(None)
    assert first == second == [str(REPO_CACHE_DIR)] * 2
