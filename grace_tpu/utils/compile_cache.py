"""Where JAX's persistent compilation cache lives — the one place that says.

The cache key includes the directory's path, so a directory that moves
never hits. Two rules, both placed from outside the code that compiles:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code, so whoever launches the run owns the location.
* unset: ``<repo root>/.jax_cache`` — a fixed path inside the checkout
  (``.gitignore`` lists it), so every process of one run on one machine
  shares compiled programs and a second run from the same checkout starts
  warm. No process id, user id, time or temporary name in the path.

On an explicit CPU rehearsal with the variable unset the helper does
nothing: XLA:CPU caches machine code keyed loosely enough that an entry
compiled under different detected CPU features loads with a "could lead to
SIGILL" warning, and CPU compiles are cheap anyway.
"""

from __future__ import annotations

import os

from grace_tpu.telemetry import host

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


@host.spanned("place_compile_cache")
def place_compile_cache(platform: str = "tpu") -> str | None:
    """Apply the rule above; returns the directory in effect (None = off).

    Call after ``import jax`` and before the first compile. ``platform`` is
    the platform the caller is about to run on (``"cpu"`` = an explicitly
    named CPU rehearsal)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if platform == "cpu":
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
