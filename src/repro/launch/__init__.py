"""Launchers: mesh construction, axis-rule binding, dry-run lowering, and
the training/serving CLIs."""

import os
import pathlib

import jax

# Fixed, never temporary: a later run from this checkout then finds what
# an earlier one compiled.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that itself) or, when it
    is unset, in ``.jax_cache`` at the root of the checkout.  Called from
    the ``main`` of every entry point, before anything compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
