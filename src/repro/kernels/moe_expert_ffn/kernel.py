"""Grouped SwiGLU over the experts a layer holds — Pallas TPU.

The routed experts' half of a mixture-of-experts layer, without a
capacity: the token-choices routed to held experts arrive sorted into
one group of rows per expert, and every row is computed.  Row layout
(``group_offsets``): group ``e`` holds ``sizes[e]`` rows starting at
``offsets[e]``, a multiple of the row tile ``tm``; the rows between a
group's end and the next tile boundary are padding that nothing reads.

Grid (E, F / tf): step ``(g, j)`` serves the g-th expert that has rows
(``visit``), f-tile ``j`` of its hidden width.  Its weight tiles arrive
through BlockSpecs, so each touched expert's weights are read once per
call; the steps past the last touched expert keep the previous block
index, so untouched experts cost neither a copy nor compute.  At ``j ==
0`` the group's rows are copied from HBM into VMEM, ``tm`` at a time; at
every ``j`` each row tile adds ``silu(x Wg_j) * (x Wu_j) Wd_j`` to a
float32 accumulator; at the last ``j`` the rows go back to HBM.  A
group holds at most one row per token (a token chooses an expert at most
once), so the VMEM buffers hold ``T`` rows.

Output rows outside every group are left unwritten: callers read only
the rows of their own token-choices.  The weights may be stacks over
layers with the layer given: the kernel then reads that layer's experts in
place (a slice taken outside the call would be copied whole first).

Oracle: `ref.moe_expert_ffn_ref` (``jax.lax.ragged_dot``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one step's weight tiles (gate, up and down, double buffered)
# that the hidden-width tile may take
_WEIGHT_TILE_BYTES = 8 * 1024 * 1024


def group_offsets(sizes: jax.Array, tm: int) -> jax.Array:
    """Start row of each group: groups in expert order, each padded up to
    a multiple of ``tm`` rows."""
    padded = -(-sizes // tm) * tm
    return jnp.cumsum(padded) - padded


def rows_bound(tokens: int, k: int, experts: int, tm: int) -> int:
    """Rows the grouped layout may need for ``tokens`` tokens choosing
    ``k`` experts each, ``experts`` groups padded to ``tm``."""
    return -(-(tokens * k + experts * (tm - 1)) // tm) * tm


def _hidden_tile(d: int, f: int, itemsize: int) -> int:
    """The widest multiple of 128 dividing ``f`` whose three weight tiles,
    double buffered, fit ``_WEIGHT_TILE_BYTES`` (``f`` itself when it is
    no multiple of 128: the whole width is one tile)."""
    if f % 128:
        return f
    best = 128
    for tf in range(128, f + 1, 128):
        if f % tf == 0 and 6 * d * tf * itemsize <= _WEIGHT_TILE_BYTES:
            best = tf
    return best


def _kernel(layer_ref, sizes_ref, offsets_ref, visit_ref, nvis_ref, x_hbm,
            wg_ref,
            wu_ref, wd_ref, o_hbm, x_buf, acc, o_buf, sem, *, tm: int,
            nf: int):
    g, j = pl.program_id(0), pl.program_id(1)
    e = visit_ref[g]
    start = offsets_ref[e]
    tiles = pl.cdiv(sizes_ref[e], tm)
    active = g < nvis_ref[0]

    def rows(t):
        return pl.ds(pl.multiple_of(t * tm, tm), tm)

    def src(t):
        return pl.ds(pl.multiple_of(start + t * tm, tm), tm)

    def copies(make):
        def start_all(t, _):
            make(t).start()

        def wait_all(t, _):
            make(t).wait()
        lax.fori_loop(0, tiles, start_all, None)
        lax.fori_loop(0, tiles, wait_all, None)

    @pl.when(active & (j == 0))
    def _load():
        copies(lambda t: pltpu.make_async_copy(x_hbm.at[src(t)],
                                               x_buf.at[rows(t)], sem.at[0]))

        def zero(t, _):
            acc[rows(t), :] = jnp.zeros((tm, acc.shape[1]), jnp.float32)
        lax.fori_loop(0, tiles, zero, None)

    @pl.when(active)
    def _compute():
        wg, wu, wd = wg_ref[0], wu_ref[0], wd_ref[0]

        def tile(t, _):
            x = x_buf[rows(t), :]
            gate = jnp.dot(x, wg, preferred_element_type=jnp.float32)
            up = jnp.dot(x, wu, preferred_element_type=jnp.float32)
            h = (jax.nn.silu(gate) * up).astype(wd.dtype)
            acc[rows(t), :] += jnp.dot(h, wd,
                                       preferred_element_type=jnp.float32)
        lax.fori_loop(0, tiles, tile, None)

    @pl.when(active & (j == nf - 1))
    def _store():
        def cast(t, _):
            o_buf[rows(t), :] = acc[rows(t), :].astype(o_buf.dtype)
        lax.fori_loop(0, tiles, cast, None)
        copies(lambda t: pltpu.make_async_copy(o_buf.at[rows(t)],
                                               o_hbm.at[src(t)], sem.at[1]))


@functools.partial(jax.jit, static_argnames=("tm", "group_rows",
                                             "interpret"))
def moe_expert_ffn(xs: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   w_down: jax.Array, sizes: jax.Array, *,
                   layer: jax.Array | None = None, tm: int,
                   group_rows: int, interpret: bool = False) -> jax.Array:
    """xs: (M, d) token-choice rows grouped by expert as
    :func:`group_offsets` lays them out for ``sizes`` (E,) rows per
    group, none holding more than ``group_rows`` (the tokens); w_gate /
    w_up: (E, d, f); w_down: (E, f, d), or each stacked over layers,
    (L, E, ...), with ``layer`` the one to use.  Returns (M, d): row r of
    group e is ``SwiGLU_e(xs[r])``; rows outside the groups are
    unwritten."""
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    M, d = xs.shape
    E, _, f = w_gate.shape[1:]
    if M % tm:
        raise ValueError(f"{M} rows are no whole number of {tm}-row tiles")
    sizes = sizes.astype(jnp.int32)
    offsets = group_offsets(sizes, tm).astype(jnp.int32)
    has = sizes > 0
    nvis = jnp.sum(has).astype(jnp.int32)
    order = jnp.argsort(~has, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(nvis - 1, 0)]
    visit = jnp.where(jnp.arange(E) < nvis, order, last)
    tf = _hidden_tile(d, f, w_gate.dtype.itemsize)
    nf = f // tf
    t_max = min(M, -(-group_rows // tm) * tm)

    # steps past the last touched expert keep the previous block index
    def w_map(g, jf, layer_ref, sizes_ref, offsets_ref, visit_ref,
              nvis_ref):
        return (layer_ref[0], visit_ref[g], 0,
                jnp.where(g < nvis_ref[0], jf, nf - 1))

    def wd_map(g, jf, layer_ref, sizes_ref, offsets_ref, visit_ref,
               nvis_ref):
        return (layer_ref[0], visit_ref[g],
                jnp.where(g < nvis_ref[0], jf, nf - 1), 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(E, nf),
        in_specs=[hbm, pl.BlockSpec((None, 1, d, tf), w_map),
                  pl.BlockSpec((None, 1, d, tf), w_map),
                  pl.BlockSpec((None, 1, tf, d), wd_map)],
        out_specs=hbm,
        scratch_shapes=[pltpu.VMEM((t_max, d), xs.dtype),
                        pltpu.VMEM((t_max, d), jnp.float32),
                        pltpu.VMEM((t_max, d), xs.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, nf=nf),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_expert_ffn",
    )(jnp.asarray(layer, jnp.int32).reshape(1), sizes, offsets,
      visit.astype(jnp.int32), nvis.reshape(1), xs, w_gate, w_up, w_down)
