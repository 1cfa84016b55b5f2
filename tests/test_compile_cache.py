"""Where the persistent XLA compile cache lives
(tpuserve/utils/compile_cache.py): placed from outside by
JAX_COMPILATION_CACHE_DIR, else at ONE fixed path inside the checkout — the
path is part of what an entry is found by, so it must never move."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A fresh interpreter per case: jax.config is process-wide state, and the
# point is what a NEW process ends up with.
_PROBE = """
import jax
from tpuserve.utils import compile_cache
before = jax.config.jax_compilation_cache_dir
got = compile_cache.configure()
print(repr((before, got, jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)))
"""


def _probe(env_dir=None, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return eval(out.stdout.strip().splitlines()[-1])


def test_env_set_means_code_sets_nothing(tmp_path):
    """JAX reads the variable itself; configure() must not touch where the
    cache lives (what JAX has after it is what JAX had before it)."""
    before, got, after, _ = _probe(env_dir=str(tmp_path))
    assert got == str(tmp_path)
    assert before == after == str(tmp_path)


# Compiled twice in one fresh process, the second time after an edit.
_TWICE = """
import jax, jax.numpy as jnp
from tpuserve.utils import compile_cache
cache = compile_cache.configure()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
SRC = '''
@jax.jit
def trunk(x):
    with jax.named_scope("SCOPE"):
        return jnp.sin(x) @ x
'''
def build(src):
    space = {"jax": jax, "jnp": jnp}
    exec(compile(src, "trunk.py", "exec"), space)
    space["trunk"](jnp.ones((8, 8))).block_until_ready()
    return compile_cache.entries(cache)
print(build(SRC.replace("SCOPE", "mlp")), build(EDITED))
"""


@pytest.mark.parametrize("edited,compiles_again", [
    ('"\\n\\n" + SRC.replace("SCOPE", "mlp")', False),
    ('SRC.replace("SCOPE", "attn.out")', True)],
    ids=["a line moved", "a scope renamed"])
def test_an_entry_is_found_by_its_scopes_and_not_by_its_lines(
        edited, compiles_again, tmp_path):
    """A program compiled under another scope name must not be found again
    (its ``op_name``s would go into the trace); one whose source moved two
    lines down must be (or every edit compiles a whole cell again)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    out = subprocess.run(
        [sys.executable, "-c", _TWICE.replace("EDITED", edited)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    first, then = map(int, out.stdout.strip().splitlines()[-1].split())
    assert first > 0
    assert (then > first) == compiles_again


def test_unset_means_one_fixed_path_in_the_checkout():
    before, got, after, min_secs = _probe()
    assert before is None
    assert got == after == os.path.join(ROOT, ".jax_cache")
    assert min_secs == 1.0


def test_same_path_from_any_process_and_directory(tmp_path):
    """Two processes started from different directories agree: no cwd, pid,
    time, temp dir or platform in the path."""
    a = _probe(cwd=ROOT)[1]
    b = _probe(cwd=str(tmp_path))[1]
    assert a == b
    for part in ("/tmp", str(os.getpid()), "cpu", "tpu"):
        assert part not in a.replace(ROOT, "")


def test_cache_dir_is_ignored_by_git():
    out = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                         cwd=ROOT)
    if out.returncode == 128:
        pytest.skip("not a git checkout")
    assert out.returncode == 0, ".jax_cache/ must be in .gitignore"


def test_entries_counts_files(tmp_path):
    from tpuserve.utils import compile_cache
    assert compile_cache.entries(str(tmp_path / "absent")) == 0
    (tmp_path / "a").write_text("x")
    (tmp_path / "b").write_text("y")
    assert compile_cache.entries(str(tmp_path)) == 2
