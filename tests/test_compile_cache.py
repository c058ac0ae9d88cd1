"""Where repro.compile_cache puts JAX's persistent compilation cache.

Each case runs in a subprocess: the cache settings are process-global
and must not leak into the rest of the suite."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    print("PATH", path)
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    if COMPILE:
        jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)))
""")


def _run(env_dir, compile_: bool) -> dict:
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", f"COMPILE = {compile_}\n" + CODE],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return dict(
        line.split(" ", 1) for line in out.stdout.splitlines()
        if line.startswith(("PATH ", "CONFIG "))
    )


def test_env_dir_wins_and_receives_entries(tmp_path):
    cache = tmp_path / "x"
    got = _run(cache, compile_=True)
    assert got == {"PATH": str(cache), "CONFIG": str(cache)}
    assert any(p.name.endswith("-cache") for p in cache.iterdir())


def test_default_dir_is_fixed_under_the_checkout():
    got = _run(None, compile_=False)  # compiles nothing: writes nothing
    want = os.path.join(REPO, ".jax_cache")
    assert got == {"PATH": want, "CONFIG": want}


def test_nothing_is_set_at_import():
    code = (
        "import jax, repro.compile_cache\n"
        "print(jax.config.jax_compilation_cache_dir,"
        " jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.split() == ["None", "1.0"]
