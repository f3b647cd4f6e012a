"""KV page gather / scatter as Pallas TPU kernels — the device half of the
host-memory cache tier's copy path.

`page_gather` pulls N pages out of the pooled KV layout
(L, P, page, K, hd) into a dense (N, L, page, K, hd) stack: one
device->host transfer of that stack demotes the pages (the host pool keeps
the stacked layout, indexed by host page id). `page_scatter` is the
inverse: a staged stack (uploaded asynchronously while decode runs) lands
back in the pool at freshly-allocated page slots, updating the pool
IN PLACE via `input_output_aliases` so the load-back never copies the
untouched pages.

Both kernels walk a (N, L) grid with the page-id vector scalar-prefetched:
the ids drive the BlockSpec index maps directly, so each grid step DMAs
exactly one (page, K, hd) tile — no gather lands on the compute units at
all. Page ids must be unique within one call (each block is visited once).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(ids_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def _scatter_kernel(ids_ref, staged_ref, pool_ref, out_ref):
    # pool_ref is the aliased destination (untouched blocks keep their
    # contents); each grid step overwrites exactly one page tile
    out_ref[...] = staged_ref[...]


def _gather_one(pool, ids, *, interpret: bool):
    L, P, page, K, hd = pool.shape
    N = ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                          # ids
        grid=(N, L),
        in_specs=[
            pl.BlockSpec((1, 1, page, K, hd),
                         lambda n, l, ids: (l, ids[n], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, page, K, hd),
                               lambda n, l, ids: (n, l, 0, 0, 0)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, L, page, K, hd), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(ids, pool)


def page_gather(k_pages, v_pages, ids, *, interpret: bool = False):
    """k_pages/v_pages: (L, P, page, K, hd); ids: (N,) int32, unique.
    Returns (k_stack, v_stack), each (N, L, page, K, hd)."""
    return (_gather_one(k_pages, ids, interpret=interpret),
            _gather_one(v_pages, ids, interpret=interpret))


def _scatter_one(pool, staged, ids, *, interpret: bool):
    L, P, page, K, hd = pool.shape
    N = ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                          # ids
        grid=(N, L),
        in_specs=[
            pl.BlockSpec((1, 1, page, K, hd),
                         lambda n, l, ids: (n, l, 0, 0, 0)),   # staged
            pl.BlockSpec((1, 1, page, K, hd),
                         lambda n, l, ids: (l, ids[n], 0, 0, 0)),  # pool
        ],
        out_specs=pl.BlockSpec((1, 1, page, K, hd),
                               lambda n, l, ids: (l, ids[n], 0, 0, 0)),
    )
    # operand indices for aliasing count the scalar-prefetch args first:
    # 0 = ids, 1 = staged, 2 = pool  ->  pool aliases the single output
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(ids, staged, pool)


def page_scatter(k_pages, v_pages, k_stack, v_stack, ids, *,
                 interpret: bool = False):
    """Inverse of `page_gather`: write stacks (N, L, page, K, hd) into the
    pools at page slots `ids` (unique), in place. Returns the pools."""
    return (_scatter_one(k_pages, k_stack, ids, interpret=interpret),
            _scatter_one(v_pages, v_stack, ids, interpret=interpret))
