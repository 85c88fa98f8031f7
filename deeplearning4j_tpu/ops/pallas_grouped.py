"""The served expert layer's grouped SwiGLU (``parallel.moe.moe_topk_share``):
sorted (token, pick) rows through their own group's expert, every shape
static. Op ``grouped_swiglu(rows, wg, wu, wd, sizes)``: ``rows`` (n, d) in the
weights' type, sorted by group; ``wg``/``wu`` (groups, d, w), ``wd`` (groups,
w, d); ``sizes`` (groups,) int32: group ``g`` is the ``sizes[g]`` rows after
those of the groups before it. Returns (n, d) float32; a row past the last
group holds anything. Two implementations of the one contract:

* **XLA** (every platform): ``jax.lax.ragged_dot`` over chunks of the sorted
  rows, each chunk with its own group sizes.
* **Pallas** (TPU): a grouped product that visits ``(group, row tile)`` pairs
  in the manner of ``jax.experimental.pallas.ops.tpu.megablox``: each visit
  streams one group's weight block against one tile of rows, so the time
  follows the held groups' bytes. The gate and up products share their
  visits and leave ``silu(g) * u`` in the rows' type; the down product is the
  same kernel with one matrix.

docs/KERNELS.md § The grouped expert product has the timings behind both
tile rules."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.registry import pallas_interpret


# ------------------------------------------------------------------ XLA


def grouped_row_tile(n: int) -> int:
    """The row tile of the grouped products over ``n`` sorted rows (the XLA
    path's chunk, the kernels' tile): a static shape in, a static tile out.
    64 rows, and no more than 16 tiles: XLA's product pays for the rows it is
    GIVEN a group that holds one and a kernel's visit for its tile's, so few
    rows are cheap; a chunk with no group still costs its turn of the loop,
    and a group across an edge is visited twice. Timed at 64 groups of 3584
    x 1024 and 16 of 6144 x 2048, where the groups and widths did not move
    the best tile (docs/KERNELS.md § The grouped expert product)."""
    return min(n, 64 * -(-n // (16 * 64)))


def _chunk_sizes(sizes, chunks: int, tile: int):
    """(chunks, groups): the rows of each group that lie in each chunk of
    ``tile`` sorted rows: the overlap of the group's ``[start, end)`` with
    the chunk's. A group that crosses a boundary is a group of both chunks;
    rows past the last group belong to no group of any chunk."""
    ends = jnp.cumsum(sizes)
    lo = jnp.arange(chunks, dtype=sizes.dtype)[:, None] * tile
    return jnp.clip(jnp.minimum(ends[None], lo + tile)
                    - jnp.maximum((ends - sizes)[None], lo), 0, None)


def grouped_swiglu_xla(rows, wg, wu, wd, sizes, *, tile: Optional[int] = None):
    """The generic path. XLA's ``ragged_dot`` on the TPU pays a whole tile of
    the rows it is given for every group that holds a row, so the rows go
    through it in chunks of ``tile`` (``grouped_row_tile`` of the shapes),
    each with its own group sizes: one ``ragged_dot`` a matrix in the body of
    a loop over the chunks. ``n <= tile`` is the one-call form."""
    def swiglu(rows, sizes):
        hidden = (jax.nn.silu(jax.lax.ragged_dot(
            rows, wg, sizes, preferred_element_type=jnp.float32))
            * jax.lax.ragged_dot(rows, wu, sizes,
                                 preferred_element_type=jnp.float32))
        return jax.lax.ragged_dot(hidden.astype(rows.dtype), wd, sizes,
                                  preferred_element_type=jnp.float32)

    n, d = rows.shape
    if tile is None:
        tile = grouped_row_tile(n)
    chunks = -(-n // tile)
    if chunks == 1:
        return swiglu(rows, sizes)
    rows = jnp.pad(rows, ((0, chunks * tile - n), (0, 0)))
    out = jax.lax.map(lambda chunk: swiglu(*chunk),
                      (rows.reshape(chunks, tile, d),
                       _chunk_sizes(sizes, chunks, tile)))
    return out.reshape(chunks * tile, d)[:n]


# --------------------------------------------------------------- Pallas

# A visit's weight block holds the WHOLE contraction (k, tn): a group whose
# rows lie across a tile's edge is visited twice in a row with the same
# block, which is then not fetched again, and no accumulator is kept. The
# block is as wide as 14 MiB allow (Xing4.0's (3584, 1024) and (1024, 3584)
# whole; LongCat's (6144, 1024) and (2048, 3072)); two matrices, two buffers
# each, stand in the v5e's 128 MiB of VMEM under this limit.
_BLOCK_BYTES = 14 << 20
_VMEM_LIMIT = 100 << 20


def _block_width(k: int, n_out: int) -> int:
    """The widest whole-lane divisor of ``n_out`` whose (k, tn) bfloat16
    block fits ``_BLOCK_BYTES`` (0: none does)."""
    return max((t for t in range(128, n_out + 1, 128)
                if n_out % t == 0 and k * t * 2 <= _BLOCK_BYTES), default=0)


def _visits(sizes, n: int, tm: int):
    """The (group, row tile) pairs the kernel visits, in group order: a group
    of ``[start, end)`` touches the tiles ``start // tm .. (end - 1) // tm``.
    Returns (offsets (groups + 1,), group of a visit, row tile of a visit,
    number of visits); the arrays are as long as the most visits there can
    be, ``n / tm + groups - 1``. Where no group holds a row one visit is
    made all the same (of an empty group: nothing is stored), so the grid is
    never empty."""
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, 0)
    visit_end = jnp.cumsum(tiles)
    v = jnp.arange(n // tm + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(visit_end, v, side="right"),
                        groups - 1).astype(jnp.int32)
    tile = first[group] + v - (visit_end - tiles)[group]
    tile = jnp.clip(tile, 0, n // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, jnp.maximum(visit_end[-1], 1)


def _grouped_kernel(offsets, group_of, tile_of, lhs, *refs):
    """One visit of (group, row tile) for one block of the output's columns:
    the tile's rows against the group's block (two blocks: ``silu(g) * u``),
    float32; the group's rows of the tile are stored and the others left as
    they are (a tile's visits are consecutive, so its block stays in VMEM)."""
    weights, out = refs[:-1], refs[-1]
    v = pl.program_id(1)
    rows = lhs[...]
    val = [jnp.dot(rows, w[...], preferred_element_type=jnp.float32)
           for w in weights]
    val = jax.nn.silu(val[0]) * val[1] if len(val) == 2 else val[0]
    g = group_of[v]
    row = tile_of[v] * out.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, out.shape, 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    out[...] = jnp.where(mine, val, out[...].astype(jnp.float32)).astype(
        out.dtype)


def _grouped_call(lhs, weights, visits, *, tm: int, out_dtype, name: str,
                  interpret: bool):
    """``lhs`` (n, k) against one (the down product) or two (gate and up:
    ``silu(g) * u``) stacks of (groups, k, n_out) weights."""
    offsets, group_of, tile_of, n_visits = visits
    n, k = lhs.shape
    n_out = weights[0].shape[2]
    tn = _block_width(k, n_out)
    weight = pl.BlockSpec((None, k, tn),
                          lambda j, v, off, grp, til: (grp[v], 0, j))
    return pl.pallas_call(
        _grouped_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_out // tn, n_visits),
            in_specs=[pl.BlockSpec(
                (tm, k), lambda j, v, off, grp, til: (til[v], 0))]
            + [weight] * len(weights),
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, off, grp, til: (til[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, n_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(offsets, group_of, tile_of, lhs, *weights)


def grouped_swiglu_pallas(rows, wg, wu, wd, sizes, *,
                          interpret: Optional[bool] = None):
    """Pallas grouped SwiGLU. Same contract as :func:`grouped_swiglu_xla`;
    the row tile is the chunk of the XLA path (``grouped_row_tile``: 64 rows
    are within half a percent of the best of 16-128 at both cells'
    geometries)."""
    n = rows.shape[0]
    tm = grouped_row_tile(n)
    interpret = pallas_interpret(interpret)
    with jax.named_scope("grouped_swiglu"):
        visits = _visits(sizes.astype(jnp.int32), n, tm)
        hidden = _grouped_call(rows, (wg, wu), visits, tm=tm,
                               out_dtype=rows.dtype, name="grouped_gate_up",
                               interpret=interpret)
        return _grouped_call(hidden, (wd,), visits, tm=tm,
                             out_dtype=jnp.float32, name="grouped_down",
                             interpret=interpret)


def _grouped_usable(rows, wg, wu, wd, sizes):
    """The Pallas path takes bfloat16 rows and weights whose widths are whole
    lanes (and narrow enough for one block to hold the contraction) and rows
    that fill whole tiles of sublanes; the tiny test models and float32
    weights take the generic path."""
    if getattr(rows, "ndim", 0) != 2 or getattr(wg, "ndim", 0) != 3:
        return False
    n, d = rows.shape
    w = wg.shape[2]
    if not (rows.dtype == wg.dtype == wu.dtype == wd.dtype == jnp.bfloat16):
        return False
    if not (_block_width(d, w) and _block_width(w, d)):
        return False
    tm = grouped_row_tile(n)
    return tm % 16 == 0 and n % tm == 0


def _check_grouped_swiglu():
    """Validation case: the generic path (one call and chunked) against a
    dense oracle a row, and the Pallas kernels (interpreted) against it, with
    empty groups, a group across a tile's edge and rows past the last."""
    import numpy as np

    rs = np.random.RandomState(5)
    n, d, w = 128, 128, 256               # two tiles of 64 rows
    sizes = np.asarray([9, 0, 41, 23, 0, 14], np.int32)
    bf = lambda a: jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)  # noqa: E731
    rows = bf(rs.randn(n, d))
    wg, wu = bf(rs.randn(6, d, w) * 0.1), bf(rs.randn(6, d, w) * 0.1)
    wd = bf(rs.randn(6, w, d) * 0.1)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    want = np.zeros((n, d), np.float32)
    for i, g in enumerate(np.repeat(np.arange(6), sizes)):
        h = f32(rows)[i] @ f32(wg)[g]
        h = h / (1.0 + np.exp(-h)) * (f32(rows)[i] @ f32(wu)[g])
        want[i] = f32(bf(h)) @ f32(wd)[g]
    held = int(sizes.sum())
    args = (rows, wg, wu, wd, jnp.asarray(sizes))
    for got in (grouped_swiglu_xla(*args), grouped_swiglu_xla(*args, tile=16),
                grouped_swiglu_pallas(*args)):
        np.testing.assert_allclose(np.asarray(got)[:held], want[:held],
                                   rtol=2e-2, atol=2e-2)
    assert _grouped_usable(*args)


def register_platform_grouped() -> None:
    """Register ``grouped_swiglu``: the chunked XLA path everywhere, the
    Pallas kernels as the TPU helper."""
    from deeplearning4j_tpu.ops.registry import registry
    from deeplearning4j_tpu.ops import validation as _validation

    reg = registry()
    if "grouped_swiglu" not in reg:
        reg.register(
            "grouped_swiglu", grouped_swiglu_xla,
            doc="sorted rows through their groups' SwiGLU experts (rows:"
                "[n,d], wg/wu:[G,d,w], wd:[G,w,d], sizes:[G] -> [n,d] f32)")
        reg.register_platform("grouped_swiglu", "tpu", grouped_swiglu_pallas,
                              _grouped_usable)
        _validation.add_case("grouped_swiglu", _check_grouped_swiglu)
