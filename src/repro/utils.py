"""Small shared utilities."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# where the persistent compile cache lives when the environment names none:
# a fixed path inside the checkout (the path is part of the cache key, so a
# directory that moves between runs never hits)
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point (call
    it from ``main``, before the first compile — never at import).

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and is left alone (JAX
    reads it itself); otherwise the cache goes to :data:`REPO_CACHE_DIR`.
    → the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode, derived from the
    backend: compiled on a TPU, interpreted on the CPU (tests, rehearsals).
    Any other backend raises — the kernels are TPU kernels, and quietly
    interpreting them there would hide that the chip path never ran."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"Pallas TPU kernels cannot run on the {backend!r} backend; "
        "select a jnp impl instead")


def match_vma(x, like):
    """Make ``x``'s varying-manual-axes match ``like``'s (shard_map scan
    carries initialized from constants must be cast to varying — see the
    shard_map VMA docs). No-op outside shard_map."""
    vma = jax.typeof(like).vma
    if not vma:
        return x
    return jax.tree.map(
        lambda a: jax.lax.pcast(a, tuple(vma), to="varying"), x)
