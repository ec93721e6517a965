"""The persistent compile cache's directory (common/compile_cache.py):
the operator's JAX_COMPILATION_CACHE_DIR wins and nothing else is set in
code; otherwise one fixed path inside the checkout, wherever the process
was started from."""

import os
import subprocess
import sys

import jax

from horaedb_tpu.common import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record_updates(monkeypatch) -> list:
    """Capture jax.config.update calls instead of applying them: the test
    process's own JAX must stay as conftest configured it."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_env_var_set_means_no_directory_set_in_code(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert calls == []


def test_unset_uses_the_fixed_path_inside_the_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_same_path_from_two_working_directories(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    code = (
        "import jax; from horaedb_tpu.common import compile_cache as c; "
        "c.enable(); print(jax.config.jax_compilation_cache_dir)"
    )
    seen = []
    for name in ("a", "b"):
        cwd = tmp_path / name
        cwd.mkdir()
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env, check=True,
            capture_output=True, text=True, timeout=120,
        )
        seen.append(out.stdout.strip().splitlines()[-1])
    assert seen == [os.path.join(REPO, ".jax_cache")] * 2
