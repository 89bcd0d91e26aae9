"""How a decode step reaches the generate engine's per-slot state: one
addressing scheme for the Pallas kernels that move a step's rows IN
PLACE, and the plain row write of their twins.

A family keeps ``state`` [layers, rows, ...] per slot (engine/generate.py
``state_shapes``). A decode step of B rows names one state row a step row
by integer id (``slot`` [B]; ``live`` [B] false: the row carries no
sequence and names the null row, as several may in one step). Through XLA
a gather or scatter of such rows is a loop of one trip a row, and an
update of the whole array costs every row whatever the step names. Here
the layer ``l``, ``slot`` and ``live`` are PREFETCHED SCALARS of a kernel
whose grid is the step's rows, one grid step a row: the state's block at
grid step ``b`` is ``state[l, slot[b]]``, the array is aliased in and out,
and so a row is read from its slot, moved on and written back where it
was. No other row is touched. Five kernel bodies use it, one a kind of
recurrent layer: ``ssm_decode_rows`` (ops/pallas_selective_scan.py: a
float32 state and the convolution's taps), ``kda_decode_rows``
(ops/pallas_kda.py: a matrix state a head and three convolutions' taps)
``conv_decode_rows`` (ops/pallas_short_conv.py: taps alone, 8 KB a
row, the kernel's cost its grid steps and not its bytes),
``ssd_decode_rows`` (ops/pallas_ssd.py: 4 MB of float32 state a row) and
``pow_decode_rows`` (ops/pallas_power.py: 34 MB a row, more than a block
may hold, so its grid is (step row, key-value head) and a grid step moves
one head's 4.2 MB: ``call``'s ``parts``).

What the kernels rely on, and hold:

* two live rows never name one slot (the engine's invariant): a row's
  block is prefetched while the row before it computes, so a second write
  to one slot in a step could be lost. The twins check it (``put``);
* a row that is not live writes back what it read (``keep``), bit for
  bit: the null row comes back as it was however many rows name it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: a bfloat16 tile's rows: a slot's row of a bfloat16 state [layers, rows,
#: width] is kept as ``[TILE_ROWS, width / TILE_ROWS]`` (``tiled``), whole
#: tiles, because a block of a kernel cannot be one row of a tile
TILE_ROWS = 16
#: step rows in one block of a [B, width] operand (a float32 tile's
#: sublanes): grid step ``b`` finds its row at ``b % <the block's rows>``
GROUP = 8


def _zeros(n: int) -> tuple:
    return (0,) * n


def at_slot(*tail: int):
    """The block ``state[l, slot[b]]`` of a state array [layers, rows,
    *tail]."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec(
        (None, None) + tail,
        lambda b, l, slot, live: (l[0], slot[b]) + _zeros(len(tail)))


def per_row(*tail: int):
    """Step row ``b``'s block of an operand [B, *tail] (``tail`` two
    dimensions or more: whole tiles a row)."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None,) + tail,
                        lambda b, *_: (b,) + _zeros(len(tail)))


def grouped(rows: int, width: int):
    """An operand [B, width] in blocks of ``GROUP`` rows (all of them
    where B is no multiple of it): a block stays in VMEM while the grid
    walks its rows."""
    from jax.experimental import pallas as pl

    g = GROUP if rows % GROUP == 0 else rows
    return pl.BlockSpec((g, width), lambda b, *_: (b // g, 0))


def at_slot_part(*tail: int):
    """The block ``state[l, slot[b], p]`` of a state array [layers, rows,
    parts, *tail] under a grid of (step row, part) (``call``'s ``parts``).
    A row that is not live names part 0 in every one of its grid steps: its
    block is fetched once, goes back as it came (``keep``) and is written
    once, however many parts and dead rows follow one another."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec(
        (None, None, None) + tail,
        lambda b, p, l, slot, live: (
            l[0], slot[b], jnp.where(live[b] > 0, p, 0)) + _zeros(len(tail)))


def per_part(*tail: int):
    """Step row ``b``'s block of part ``p`` of an operand [B, parts,
    *tail] under a grid of (step row, part)."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None, None) + tail,
                        lambda b, p, *_: (b, p) + _zeros(len(tail)))


def shared(*shape: int):
    """An operand every row reads whole (fetched once)."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec(shape, lambda b, *_: _zeros(len(shape)))


def tiled(width: int) -> tuple[int, int]:
    """The two dimensions a slot's bfloat16 row of ``width`` values is
    kept in."""
    if width % TILE_ROWS:
        raise ValueError(f"a row of {width} values is not {TILE_ROWS} "
                         "equal parts")
    return TILE_ROWS, width // TILE_ROWS


def keep(*pairs) -> None:
    """A row that is not live: each state block goes back as it came."""
    for came, goes in pairs:
        goes[...] = came[...]


def call(kernel, name: str, l, slot, live, operands, specs, outs, out_specs,
         states, state_specs, *, parts: int | None = None, scratch=(),
         interpret: bool = False):
    """``kernel(l_ref, slot_ref, live_ref, *operands, *states, *outs,
    *states_out, *scratch)`` over a grid of the step's rows, or with
    ``parts`` of (step row, part of its state) under the ``*_part`` blocks
    (a row's state too large for one block). ``states`` are aliased to the
    last outputs; ``scratch``: shapes of float32 VMEM the body keeps.
    Returns ``(*outs, *states)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    first = 3 + len(operands)
    grid = (slot.shape[0],) + (() if parts is None else (parts,))
    more = ({"scratch_shapes": [pltpu.VMEM(s, jnp.float32) for s in scratch]}
            if scratch else {})
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[*specs, *state_specs],
            out_specs=[*out_specs, *state_specs], **more),
        out_shape=[*outs, *(jax.ShapeDtypeStruct(s.shape, s.dtype)
                            for s in states)],
        input_output_aliases={first + i: len(outs) + i
                              for i in range(len(states))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=64 * 1024 * 1024),
        name=name,
        interpret=interpret,
    )(jnp.reshape(l, (1,)).astype(jnp.int32), slot.astype(jnp.int32),
      live.astype(jnp.int32), *operands, *states)


# ------------------------------------------------- the twins' row write


def _distinct(slot, live) -> None:
    named = np.asarray(slot)[np.asarray(live)]
    if len(set(named.tolist())) != len(named):
        raise AssertionError(f"two live rows of a decode step name one "
                             f"slot: {sorted(named.tolist())}")


def put(state, l, slot, live, rows, *, check: bool = False):
    """``state`` with ``rows`` [B, ...] written to ``state[l, slot]``;
    rows that are not live are dropped, so the null row and every row no
    live row names come back bit for bit. ``check`` (the kernels' twins):
    a debug callback refuses two live rows that name one slot."""
    if __debug__ and check:
        jax.debug.callback(_distinct, slot, live)
    at = jnp.where(live, slot, state.shape[1])
    return state.at[l, at].set(rows.astype(state.dtype), mode="drop")
