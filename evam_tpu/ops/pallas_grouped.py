"""Pallas TPU kernel: the products of rows SORTED BY GROUP with their
group's matrix, with work that follows the groups the rows reach.

``rows`` [m, k] hold ``sizes[g]`` rows of group ``g`` one group after the
other (the expert layer's assignments sorted by held expert,
models/lm/experts.py); what lies past ``sizes.sum()`` belongs to no group.
``w`` [groups, k, n] is one matrix a group, handed AS IT IS: the kernel
reads blocks of it where it lies and no slice or copy of it is made. A
model whose expert layers sit in ONE loop body hands the layers' tensors
STACKED, ``w`` [layers, groups, k, n], and the layer as a traced scalar
(``layer``): one more prefetched scalar, which the matrices' block index
reads, so the stack is addressed in place too.

The grid walks VISITS: the (row tile, group) pairs in which a tile of
``tile`` rows holds rows of the group, in order, ``tile_of`` and
``group_of`` and their number prefetched scalars computed from ``sizes``
inside the program (``visits``). A visit multiplies the tile by the
group's matrix (bfloat16 operands, float32 accumulation, one pass over
``k``), and writes the rows that are the group's; the tile's output block
stays in VMEM while visits of the same tile follow each other. So a group
with no row is never read, a group with rows is read once a tile that
holds some, and tiles past the last row cost nothing: one call over all
``m`` rows costs what the occupied tiles cost. ``n`` is cut into blocks of
whole lane tiles so that one block of a matrix is at most ``BLOCK_BYTES``,
and is the grid's OUTER axis (a tile's output block may be revisited only
by consecutive grid steps).

Three entry points, one kernel body: ``swiglu`` (two matrices a group, the
epilogue ``silu(g) * u`` on the two products rounded to bfloat16, written
once), ``relu2`` (one matrix, the epilogue ``max(t, 0)^2`` on the product
rounded to bfloat16: an expert of two matrices and no gate) and ``product``
(one matrix, rounded to bfloat16). Rows of no group
come back as whatever the memory held (a tile no visit names is never
written): the caller masks them. ``swiglu_xla``, ``relu2_xla`` and
``product_xla`` are the
same mathematics through ``jax.lax.ragged_dot`` (rows of no group zero);
the CPU tests run them, and check the kernel against them in the
interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16
F32 = jnp.float32
#: rows of a tile: the MXU's width; a visit costs a tile's products
#: whatever it keeps of them, and a matrix's bytes whatever the tile
ROW_TILE = 128
#: a bfloat16 tile's sublanes: fewer than ``ROW_TILE`` rows are one tile
#: of whole such tiles
SUBLANES = 16
#: the most one block of one matrix may hold (the pipeline keeps two of
#: each operand). Measured on a v5e (PR 39): a [2304, 1024] matrix whole
#: (4.7 MB) is 7 % faster than in halves, [5120, 1536] (15.7 MB) is as
#: fast in blocks of 3.9 MB as of 7.9 and 3 % slower whole in a decode
#: step (the first block's copy stands before the first product)
BLOCK_BYTES = 6 << 20


def row_tile(m: int) -> int:
    """The rows of a tile for ``m`` rows (``padded``)."""
    return min(ROW_TILE, m)


def padded(m: int) -> int:
    """The rows the kernel wants for ``m``: whole tiles."""
    unit = ROW_TILE if m > ROW_TILE else SUBLANES
    return -(-m // unit) * unit


def col_block(k: int, n: int, itemsize: int = 2) -> int:
    """Columns of one block of a [k, n] matrix: the most whole lane tiles
    that divide ``n`` and keep the block within ``BLOCK_BYTES`` (all of
    ``n`` where it is no multiple of 128: a block must then span it)."""
    if n % 128:
        return n
    lanes = n // 128
    fit = [d for d in range(1, lanes + 1)
           if lanes % d == 0 and k * d * 128 * itemsize <= BLOCK_BYTES]
    return 128 * max(fit, default=1)


def visits(sizes, m: int):
    """The (row tile, group) pairs that hold rows, in order, for ``m``
    rows in tiles of ``row_tile(m)``: ``(tile_of, group_of, starts, ends,
    n)``, the first two [m / tile + groups - 1] int32 of which the first
    ``n`` count (a pair past them repeats the last), ``starts``/``ends``
    each group's rows."""
    groups, tile = sizes.shape[0], row_tile(m)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile
    spans = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(spans)
    n = upto[-1]
    v = jnp.minimum(jnp.arange(m // tile + groups - 1, dtype=jnp.int32),
                    jnp.maximum(n - 1, 0))
    group_of = jnp.minimum((v[:, None] >= upto[None, :]).sum(1), groups - 1)
    group_of = group_of.astype(jnp.int32)
    tile_of = first[group_of] + v - (upto - spans)[group_of]
    return tile_of, group_of, starts, ends, n


def n_visits(sizes, m: int):
    """How many (row tile, group) pairs one product over ``m`` rows
    visits: the times a group's matrix is read."""
    return visits(sizes, m)[4]


def _kernel(tile: int, n_w: int, squared: bool = False):
    from jax.experimental import pallas as pl

    def kernel(tile_of, group_of, starts, ends, layer, rows_ref, *refs):
        w_refs, out_ref = refs[:n_w], refs[n_w]
        v = pl.program_id(1)
        g = group_of[v]
        x = rows_ref[...]
        acc = [jnp.dot(x, w[...], preferred_element_type=F32).astype(BF16)
               for w in w_refs]
        y = acc[0]
        if n_w == 2:
            # bfloat16 values, float32 arithmetic (Mosaic refuses a
            # bfloat16 logistic here), rounded where bfloat16 would be
            gate = y.astype(F32)
            act = (gate * jax.nn.sigmoid(gate)).astype(BF16).astype(F32)
            y = (act * acc[1].astype(F32)).astype(BF16)
        elif squared:
            up = jnp.maximum(y.astype(F32), 0.0)
            y = (up * up).astype(BF16)
        row = tile_of[v] * tile + jax.lax.broadcasted_iota(
            jnp.int32, y.shape, 0)
        mine = (row >= starts[g]) & (row < ends[g])
        out_ref[...] = jnp.where(mine, y, out_ref[...])

    return kernel


def _call(name: str, rows, ws, sizes, layer, interpret: bool,
          squared: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = ws[0].shape[-1]
    stacked = ws[0].ndim == 4
    if stacked != (layer is not None):
        raise ValueError("a stack of layers' matrices [layers, groups, k, "
                         "n] is addressed by ``layer``, and nothing else is")
    tile = row_tile(m)
    if m % tile or tile % SUBLANES:
        raise ValueError(f"{m} rows are not whole tiles of {tile} "
                         f"(whole tiles of {SUBLANES}): see padded()")
    cols = col_block(k, n, ws[0].dtype.itemsize)
    # two of each block, and room for the products in float32: no more,
    # because what a kernel may use of VMEM the rest of the program may
    # not (with 64 MiB here XLA kept DeepSeek's 59 MB cache slices in HBM
    # and a decode step lost what the kernel had gained)
    vmem = 2 * 2 * (len(ws) * k * cols + tile * k + tile * cols) + (8 << 20)
    tile_of, group_of, starts, ends, count = visits(sizes, m)
    if stacked:
        w_spec = pl.BlockSpec(
            (None, None, k, cols),
            lambda j, v, t, g, s, e, l: (l[0], g[v], 0, j))
    else:
        w_spec = pl.BlockSpec((None, k, cols),
                              lambda j, v, t, g, *_: (g[v], 0, j))
    return pl.pallas_call(
        _kernel(tile, len(ws), squared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // cols, count),
            in_specs=[pl.BlockSpec((tile, k),
                                   lambda j, v, t, *_: (t[v], 0)),
                      *[w_spec] * len(ws)],
            out_specs=pl.BlockSpec((tile, cols),
                                   lambda j, v, t, *_: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), BF16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        name=name,
        interpret=interpret,
    )(tile_of, group_of, starts, ends,
      jnp.reshape(0 if layer is None else layer, (1,)).astype(jnp.int32),
      rows.astype(BF16), *ws)


@functools.partial(jax.jit, static_argnames=("interpret",))
def swiglu(rows, gate, up, sizes, layer=None, *, interpret=False):
    """``silu(rows @ gate[g]) * (rows @ up[g])`` for the rows of each
    group ``g``, both products rounded to bfloat16 before the epilogue:
    ``rows`` [m, k] (``m`` = ``padded(m)``), ``gate``, ``up`` [groups, k,
    n] (or [layers, groups, k, n] with ``layer``), ``sizes`` [groups] ->
    [m, n] bfloat16."""
    return _call("expert_gate_up", rows, (gate, up), sizes, layer, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def relu2(rows, up, sizes, layer=None, *, interpret=False):
    """``max(rows @ up[g], 0)^2`` for the rows of each group ``g``, the
    product rounded to bfloat16 before the epilogue: ``rows`` [m, k], ``up``
    [groups, k, n] (or [layers, groups, k, n] with ``layer``) -> [m, n]
    bfloat16."""
    return _call("expert_up", rows, (up,), sizes, layer, interpret,
                 squared=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def product(rows, w, sizes, layer=None, *, interpret=False):
    """``rows @ w[g]`` for the rows of each group ``g``: ``rows`` [m, k],
    ``w`` [groups, k, n] (or [layers, groups, k, n] with ``layer``) -> [m,
    n] bfloat16."""
    return _call("expert_down", rows, (w,), sizes, layer, interpret)


# --------------------------------------------- the same through XLA


def product_xla(rows, w, sizes, layer=None):
    if layer is not None:
        w = w[layer]
    return jax.lax.ragged_dot(rows.astype(BF16), w, sizes.astype(jnp.int32),
                              preferred_element_type=F32).astype(BF16)


def relu2_xla(rows, up, sizes, layer=None):
    return jnp.square(jax.nn.relu(product_xla(rows, up, sizes, layer)))


def swiglu_xla(rows, gate, up, sizes, layer=None):
    return (jax.nn.silu(product_xla(rows, gate, sizes, layer))
            * product_xla(rows, up, sizes, layer))
