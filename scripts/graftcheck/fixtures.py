"""Seeded-violation kernels: the proof the gate can fail.

`python -m scripts.graftcheck --fixtures` audits THESE contracts instead
of the registered engine sites and must exit non-zero, one finding per
seeded contract breach:

- fixture_callback        GC001  host pure_callback inside the kernel
- fixture_debug_effect    GC001  jax.debug.callback (an effectful prim)
- fixture_f64             GC002  implicit float64 promotion
- fixture_out_dtype       GC002  output dtype drifting from the contract
- fixture_collective      GC003  undeclared all-reduce in a sharded kernel
- fixture_gather_slice    GC003  all-gather result re-sliced per shard
                                 (the SPMD reshard signature)

tests/test_graftcheck.py runs the CLI over these and asserts each rule
fires; the clean-twin direction is the real audit staying green.
"""

from __future__ import annotations

import numpy as np


def fixture_sites():
    import jax
    import jax.numpy as jnp

    dim, cap = 16, 64

    def _single(name, fn, out_dtypes=("float32",)):
        return {
            "subsystem": name,
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            "out_dtypes": out_dtypes,
            "shapes": [{"label": "seeded"}],
            "build": lambda shape: (
                fn,
                (jax.ShapeDtypeStruct((cap, dim), jnp.float32),),
            ),
        }

    def callback_kernel(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            x,
        )
        return y.sum(axis=1)

    def debug_effect_kernel(x):
        jax.debug.callback(lambda v: None, x)
        return x.sum(axis=1)

    def f64_kernel(x):
        # the classic silent promotion: a float64 numpy constant infects
        # the whole expression under x64
        scale = np.float64(0.5)
        return (x * scale).sum(axis=1)

    def out_dtype_kernel(x):
        return x.sum(axis=1)  # f32, but the contract below declares int32

    def sharded_builds():
        from jax import shard_map

        from surrealdb_tpu.parallel.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        import functools

        mesh = make_mesh(min(8, len(jax.devices())))
        n_dev = mesh.shape["data"]

        def build_collective(shape):
            @functools.partial(
                shard_map, mesh=mesh,
                in_specs=(P("data", None),), out_specs=P("data", None),
                check_vma=False,
            )
            def bad(x_local):
                # an undeclared whole-corpus reduction: O(N) over ICI
                s = jax.lax.psum(x_local.sum(), "data")
                return x_local + s

            return bad, (jax.ShapeDtypeStruct((cap, dim), jnp.float32),)

        def build_gather_slice(shape):
            rows = cap // n_dev

            @functools.partial(
                shard_map, mesh=mesh,
                in_specs=(P("data", None),), out_specs=P("data", None),
                check_vma=False,
            )
            def bad(x_local):
                # gather the WHOLE corpus to every chip, then slice this
                # shard back out — the partitioner reshard signature
                full = jax.lax.all_gather(x_local, "data", axis=0, tiled=True)
                i = jax.lax.axis_index("data")
                return jax.lax.dynamic_slice_in_dim(full, i * rows, rows, 0)

            return bad, (jax.ShapeDtypeStruct((cap, dim), jnp.float32),)

        return build_collective, build_gather_slice

    build_collective, build_gather_slice = sharded_builds()
    return [
        _single("fixture_callback", callback_kernel),
        _single("fixture_debug_effect", debug_effect_kernel),
        _single("fixture_f64", f64_kernel),
        _single("fixture_out_dtype", out_dtype_kernel, out_dtypes=("int32",)),
        {
            "subsystem": "fixture_collective",
            "module": __name__,
            "kind": "sharded",
            "mesh_devices": 8,
            "allowed_collectives": ("all-gather",),
            "out_dtypes": ("float32",),
            "shapes": [{"label": "seeded"}],
            "build": build_collective,
        },
        {
            "subsystem": "fixture_gather_slice",
            "module": __name__,
            "kind": "sharded",
            "mesh_devices": 8,
            "allowed_collectives": ("all-gather",),
            "out_dtypes": ("float32",),
            "shapes": [{"label": "seeded"}],
            "build": build_gather_slice,
        },
    ]
