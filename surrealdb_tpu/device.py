"""The accelerator this process serves from, and where its compiled
kernels are kept.

Two facts every entry point needs before the first kernel compiles:

- `configure_compile_cache()` — the ONE place that decides where XLA's
  persistent compilation cache lives. `JAX_COMPILATION_CACHE_DIR` set in
  the environment: nothing is set in code, JAX reads the variable itself.
  Unset: `<checkout>/.jax_cache`, a fixed path (the path is part of the
  cache key, so a directory that moves never hits). `Datastore.__init__`
  calls it, which covers the server, the embedded library,
  `benchmarks/run.py` and `chip_smoke.py`; `__graft_entry__.py` compiles without a datastore and
  calls it itself.
- `describe()` — initialises the JAX backend and says what it is:
  platform, device kind, device count and the JAX/jaxlib/libtpu versions.
  `libtpu` is None on an install without that distribution. `Server` calls
  it and `surreal start` prints it, so a process that fell back to the CPU
  backend says so before it accepts a connection. A server process owns
  every chip it can see (`Datastore.mesh()` shards over all of them).
"""

from __future__ import annotations

import os
from typing import Optional

from surrealdb_tpu import cnf

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> Optional[str]:
    """The directory this module sets in code, or None when the
    environment already placed the cache."""
    if cnf.JAX_COMPILATION_CACHE_DIR:
        return None
    return os.path.join(CHECKOUT, ".jax_cache")


def configure_compile_cache() -> None:
    path = compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)


def describe() -> dict:
    """{platform, device_kind, device_count, jax, jaxlib, libtpu} of the
    backend JAX initialised (initialising it on first call)."""
    from importlib import metadata

    import jax
    import jaxlib

    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # an install without the TPU runtime
        libtpu = None
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }
