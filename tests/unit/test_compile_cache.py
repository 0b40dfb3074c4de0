"""runtime.enable_compile_cache: the cache directory can be placed from
outside, and is a fixed path otherwise.  Each case runs in its own
interpreter: JAX reads JAX_COMPILATION_CACHE_DIR when it is imported,
and the helper changes process-wide JAX configuration."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_PROBE = (
    "import jax\n"
    "from antidote_tpu.runtime import enable_compile_cache\n"
    "returned = enable_compile_cache()\n"
    "print(returned)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _cache_dirs(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    # a different working directory: the default must not depend on it
    r = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                       cwd=os.path.dirname(REPO) or "/",
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    returned, configured = r.stdout.split()
    return returned, configured


def test_environment_places_the_cache(tmp_path):
    placed = str(tmp_path / "placed")
    returned, configured = _cache_dirs(placed)
    assert returned == configured == placed


def test_default_is_a_fixed_path_in_the_checkout():
    """The path is part of what a later process must find again: two
    processes (two pids, two start times, a working directory that is
    not the checkout) name the same directory, a function of where the
    checkout is and of nothing else."""
    want = os.path.join(REPO, ".jax_cache")
    assert _cache_dirs(None) == (want, want)
    assert _cache_dirs(None) == (want, want)
