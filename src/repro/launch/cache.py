"""Where JAX keeps its persistent compilation cache. Called by the entry
points (launch/train.py, launch/serve.py, chip_smoke.py), never at import
time.

`JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own setting and is left to
it. Otherwise the cache lives at a fixed path in the checkout,
`<repo>/.jax_cache` (listed in .gitignore): the path is part of the cache
key, so it must not move between runs.
"""
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; -> its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
