"""Entry-point behaviour: where the compile cache goes, and how an
interrupted server run exits."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import compile_cache
from repro.launch import serve as serve_cli

REPO = Path(__file__).resolve().parents[1]

# Compiles one small function with the cache helper on. The repo default is
# pointed at argv[1] so the run never writes into the checkout.
_COMPILE_ONE = textwrap.dedent("""
    import sys
    from pathlib import Path
    from repro.launch import compile_cache
    compile_cache.REPO_CACHE_DIR = Path(sys.argv[1])
    print(compile_cache.enable_compile_cache())
    import jax, jax.numpy as jnp
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
""")


def _compile_one(tmp_path, placed):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=str(REPO / "src"),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
    )
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    out = subprocess.run(
        [sys.executable, "-c", _COMPILE_ONE, str(tmp_path / "default")],
        env=env, capture_output=True, text=True, timeout=300, cwd=tmp_path,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_goes_where_placed_else_the_default(tmp_path, placed):
    used = _compile_one(tmp_path, placed)
    want, other = (
        ("placed", "default") if placed else ("default", "placed")
    )
    assert used == str(tmp_path / want)
    assert any((tmp_path / want).iterdir())
    assert not (tmp_path / other).exists()


def test_repo_cache_dir_is_fixed_and_ignored():
    assert compile_cache.REPO_CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_interrupted_serve_run_exits_nonzero(monkeypatch, capsys):
    """A Ctrl-C mid-stream still flushes the run's output, then exits 130."""

    def interrupt(*args, **kwargs):
        raise KeyboardInterrupt

    monkeypatch.setattr(serve_cli, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(serve_cli, "run_paged_stream", interrupt)
    with pytest.raises(SystemExit) as exc:
        serve_cli.main(["--smoke", "--engine", "paged", "--requests", "1"])
    assert exc.value.code == 130
    assert "interrupted" in capsys.readouterr().out
