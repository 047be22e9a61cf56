"""The entry points' persistent compilation cache: JAX_COMPILATION_CACHE_DIR
when it is set, else the fixed `<repo>/.jax_cache`.  Each case runs in a
subprocess, since JAX sets its cache up once per process."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if {compile!r}:
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
    print(path)
    print(jax.config.jax_compilation_cache_dir)
""")


def _run(env_dir, compile_):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c",
                        PROBE.format(compile=compile_)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()[-2:]


def test_cache_lands_in_env_dir(tmp_path):
    d = str(tmp_path / "cache")
    returned, configured = _run(d, compile_=True)
    assert returned == configured == d
    assert os.listdir(d), "no cache entry written"


def test_cache_defaults_to_repo_dir():
    returned, configured = _run(None, compile_=False)
    assert returned == configured == os.path.join(REPO, ".jax_cache")
