"""Causal grouped-query attention under a per-input key mask, as Pallas TPU
kernels: the flash form, an online softmax over tiles of keys whose scores
and weights stay in VMEM, forward and backward, tied by ``jax.custom_vjp``.

What it computes is ``models/trunk.py``'s ``_attend_block`` over a whole
swarm: ``q (S, kv, group, dk)``, ``k (S, kv, dk)``, ``v (S, kv, dv)`` and a
mask ``(S, S)`` of the keys each query selects (the trunk's indexer; ``None``
is the causal mask alone, the trunk's dense mixers) -> ``(S, kv * group *
dv)``, a softmax at scale ``dk ** -0.5`` over the selected keys alone. Every
query must select at least one key. Values may be narrower than keys
(latent attention's 128 against 192).

How:

- a grid step is one key head, a tile of ``block_q`` queries and a tile of
  ``block_k`` keys; the ``group`` query heads that share the key head are
  folded into the tile's rows (head-major), so a tile of keys, values and
  the mask is read once for all of them. ``block_q`` follows the group: about
  1,024 rows a tile whatever the group (``query_block``);
- tiles wholly above the diagonal are not computed, forward or backward,
  and their index maps repeat the last tile under it, so nothing is fetched
  for them either;
- a masked score is a finite large negative number, not ``-inf``: a row
  that selects nothing in a tile then adds weights that the first tile in
  which it selects a key scales to zero;
- every product is three bf16 passes with float32 sums (``a_hi b_hi +
  a_hi b_lo + a_lo b_hi``, what jax's ``high`` computes): Mosaic lowers
  only one pass or ``HIGHEST``. Keys and values are split once outside the
  kernels; queries and output gradients once a tile of queries; the
  weights and their gradients in every step;
- the backward pass is one kernel: with the query tile outer it sums dQ
  in a scratch, and dK, dV (as ``(dk, keys)`` and ``(dv, keys)``, so that no
  tile is transposed in the kernel) in blocks that stay in VMEM for the key
  head.

Where the chip does not run it, ``interpret=True`` runs the same kernels
through Pallas's interpreter (the CPU tests).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# Queries and keys of a grid step. 128 queries x 8 heads make 1,024 rows
# of scores over 512 keys: 2 MiB of float32 a score-sized temporary. A group
# of fewer heads takes more queries a tile, to the same rows (``query_block``).
BLOCK_Q = 128
BLOCK_K = 512
_ROWS = 1024
_LANES = 128
# A masked score: finite, so that exp(masked - masked) is 1 and not NaN.
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
# The backward kernel keeps dK and dV of a key head, 4 MiB each at 8,192
# keys of 128 (dK 6 MiB at keys of 192), twice over for the pipeline, beside
# its tiles.
_VMEM_LIMIT = 96 * 1024 * 1024
_ONE_PASS = jax.lax.Precision.DEFAULT
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def split(x: Array) -> Tuple[Array, Array]:
    """``x`` float32 as bf16 ``hi + lo``, the two parts of three passes.
    ``hi`` is ``x`` rounded to bf16 (to nearest, ties to even) on the bits:
    inside a kernel Mosaic takes ``x - float32(bf16(x))`` for nothing, and
    the products fall to one pass (on a TPU v5e the attention's output then
    lay 2.6e-3 of its largest entry from float32, against 2e-5)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _dot3(a, b, dims):
    """``a . b`` in three bf16 passes, float32 sums: ``a``, ``b`` are
    ``(hi, lo)`` pairs."""
    (a_hi, a_lo), (b_hi, b_lo) = a, b
    one = functools.partial(
        jax.lax.dot_general, dimension_numbers=dims, precision=_ONE_PASS,
        preferred_element_type=jnp.float32,
    )
    return one(a_hi, b_hi) + one(a_hi, b_lo) + one(a_lo, b_hi)


def _lanes(stat: Array, width: int) -> Array:
    """A row statistic held as ``(rows, 128)`` equal lanes, at ``width``."""
    if width > _LANES:
        stat = jnp.tile(stat, (1, -(-width // _LANES)))
    return stat if stat.shape[1] == width else stat[:, :width]


def query_block(group: int, s: int, block_k: int = BLOCK_K) -> int:
    """Queries a tile for ``group`` query heads a key head: ``_ROWS`` rows of
    scores (group 8 takes ``BLOCK_Q``, group 1 eight times that), at least
    ``BLOCK_Q`` and no more than the swarm padded to whole key tiles. Beside
    the backward's resident dK and dV a tile's temporaries then take what
    they take at group 8."""
    return max(BLOCK_Q, min(_ROWS // group, -(-s // block_k) * block_k))


def _last_tile(i, block_q: int, block_k: int):
    """The last key tile the causal mask leaves something of for query
    tile ``i``."""
    return ((i + 1) * block_q - 1) // block_k


def _keep(mask_ref, i, j, block_q: int, block_k: int):
    """``(block_q, block_k)``: the causal mask of tile ``(i, j)``, and the
    selection where there is one."""
    shape = (block_q, block_k)
    rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    keep = cols <= rows
    if mask_ref is not None:
        keep = keep & (mask_ref[...].astype(jnp.int32) != 0)
    return keep


def _scores(q, k, keep, scale: float, group: int):
    """The tile's scaled scores ``(group * block_q, block_k)``, masked."""
    s = _dot3(q, k, _NT) * scale
    rows, cols = s.shape
    s = jnp.where(keep[None], s.reshape(group, rows // group, cols), _MASKED)
    return s.reshape(rows, cols)


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------


def _forward_kernel(
    q_ref, k_hi_ref, k_lo_ref, v_hi_ref, v_lo_ref, *refs,
    scale: float, block_q: int, block_k: int, masked: bool,
):
    mask_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    o_ref, lse_ref, q_hi, q_lo, m_ref, l_ref, acc_ref = refs
    (group, _, dk), dv = q_ref.shape, o_ref.shape[-1]
    rows = group * block_q
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_tile(i, block_q, block_k)

    @pl.when(j == 0)
    def _start():
        q_hi[...], q_lo[...] = split(q_ref[...].reshape(rows, dk))
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j <= last)
    def _step():
        keep = _keep(mask_ref, i, j, block_q, block_k)
        s = _scores(
            (q_hi[...], q_lo[...]), (k_hi_ref[...], k_lo_ref[...]), keep, scale, group
        )
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))  # (rows, 128)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, block_k))
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_next
        pv = _dot3(split(p), (v_hi_ref[...], v_lo_ref[...]), _NN)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, dv) + pv

    @pl.when(j == last)
    def _end():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _lanes(l, dv)).reshape(group, block_q, dv)
        lse_ref[...] = (m_ref[...] + jnp.log(l)).reshape(group, block_q, _LANES)


def _kv_specs(block_q: int, block_k: int, widths):
    """Keys' and values' parts of the given ``widths`` and the mask of step
    ``(h, i, j)``; a step above the diagonal keeps the tile it had, so it
    fetches nothing."""

    def kv(h, i, j):
        return h, jnp.minimum(j, _last_tile(i, block_q, block_k)), 0

    def mask(h, i, j):
        return i, jnp.minimum(j, _last_tile(i, block_q, block_k))

    specs = [pl.BlockSpec((None, block_k, d), kv) for d in widths]
    return specs, pl.BlockSpec((block_q, block_k), mask)


def _rows_spec(group: int, block_q: int, d: int):
    """The tile of queries' rows ``(group, block_q, d)`` of step ``(h, i, j)``."""
    return pl.BlockSpec((None, group, block_q, d), lambda h, i, j: (h, 0, i, 0))


def _forward(q, kv_parts, mask, block_q: int, block_k: int, interpret: bool):
    """``q (kv, group, S, dk)`` float32, keys' parts ``(kv, S, dk)`` and
    values' ``(kv, S, dv)`` bf16 -> ``o (kv, group, S, dv)`` and the rows'
    log-sum-exp ``(kv, group, S, 128)``."""
    heads, group, s, dk = q.shape
    dv = kv_parts[2].shape[-1]
    rows = group * block_q
    kv_specs, mask_spec = _kv_specs(block_q, block_k, [p.shape[-1] for p in kv_parts])
    lse_spec = _rows_spec(group, block_q, _LANES)
    masked = mask is not None
    return pl.pallas_call(
        functools.partial(
            _forward_kernel, scale=dk**-0.5, block_q=block_q, block_k=block_k,
            masked=masked,
        ),
        grid=(heads, s // block_q, s // block_k),
        in_specs=[_rows_spec(group, block_q, dk)] + kv_specs
        + ([mask_spec] if masked else []),
        out_specs=[_rows_spec(group, block_q, dv), lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((heads, group, s, dv), jnp.float32),
            jax.ShapeDtypeStruct((heads, group, s, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, dk), jnp.bfloat16),
            pltpu.VMEM((rows, dk), jnp.bfloat16),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="sparse_flash_fwd",
    )(q, *kv_parts, *([mask] if masked else []))


# ----------------------------------------------------------------------
# Backward
# ----------------------------------------------------------------------


def _backward_kernel(
    q_ref, qt_ref, do_ref, dot_ref, o_ref, lse_ref,
    k_hi_ref, k_lo_ref, v_hi_ref, v_lo_ref, *refs,
    scale: float, block_q: int, block_k: int, masked: bool,
):
    mask_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    (dq_ref, dkt_ref, dvt_ref,
     q_hi, q_lo, qt_hi, qt_lo, do_hi, do_lo, dot_hi, dot_lo, di_ref, dq_acc) = refs
    (group, _, dk), dv = q_ref.shape, do_ref.shape[-1]
    rows = group * block_q
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_tile(i, block_q, block_k)

    @pl.when((i == 0) & (j == 0))
    def _new_head():
        dkt_ref[...] = jnp.zeros(dkt_ref.shape, jnp.float32)
        dvt_ref[...] = jnp.zeros(dvt_ref.shape, jnp.float32)

    @pl.when(j == 0)
    def _start():
        do = do_ref[...].reshape(rows, dv)
        q_hi[...], q_lo[...] = split(q_ref[...].reshape(rows, dk))
        do_hi[...], do_lo[...] = split(do)
        # (d, rows) from (group, d, block_q): the heads side by side
        qt_hi[...], qt_lo[...] = split(jnp.concatenate(list(qt_ref[...]), axis=1))
        dot_hi[...], dot_lo[...] = split(jnp.concatenate(list(dot_ref[...]), axis=1))
        di = (o_ref[...].reshape(rows, dv) * do).sum(axis=1, keepdims=True)
        di_ref[...] = jnp.broadcast_to(di, di_ref.shape)
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    @pl.when(j <= last)
    def _step():
        keep = _keep(mask_ref, i, j, block_q, block_k)
        k = (k_hi_ref[...], k_lo_ref[...])
        s = _scores((q_hi[...], q_lo[...]), k, keep, scale, group)
        p = jnp.exp(s - _lanes(lse_ref[...].reshape(rows, _LANES), block_k))
        dp = _dot3((do_hi[...], do_lo[...]), (v_hi_ref[...], v_lo_ref[...]), _NT)
        ds = p * (dp - _lanes(di_ref[...], block_k))
        p, ds = split(p), split(ds)
        dvt_ref[j] += _dot3((dot_hi[...], dot_lo[...]), p, _NN)
        dkt_ref[j] += _dot3((qt_hi[...], qt_lo[...]), ds, _NN)
        dq_acc[...] += _dot3(ds, k, _NN)

    @pl.when(j == last)
    def _end():
        dq_ref[...] = (scale * dq_acc[...]).reshape(group, block_q, dk)


def _backward(q, kv_parts, mask, o, lse, do, block_q: int, block_k: int, interpret: bool):
    """dQ ``(kv, group, S, dk)``; dK ``(kv, S, dk)``, dV ``(kv, S, dv)``."""
    heads, group, s, dk = q.shape
    dv = do.shape[-1]
    rows, tiles = group * block_q, s // block_k
    kv_specs, mask_spec = _kv_specs(block_q, block_k, [p.shape[-1] for p in kv_parts])

    def rows_spec(d):
        return _rows_spec(group, block_q, d)

    def cols_spec(d):
        return pl.BlockSpec((None, group, d, block_q), lambda h, i, j: (h, 0, 0, i))

    # dK and dV of the whole key head, as (tiles, d, block_k): in VMEM
    # across the head's steps, a tile at a time by its leading index
    def keys_spec(d):
        return pl.BlockSpec((None, tiles, d, block_k), lambda h, i, j: (h, 0, 0, 0))

    def keys_shape(d):
        return jax.ShapeDtypeStruct((heads, tiles, d, block_k), jnp.float32)

    masked = mask is not None
    bf16 = lambda shape: pltpu.VMEM(shape, jnp.bfloat16)  # noqa: E731
    dq, dkt, dvt = pl.pallas_call(
        functools.partial(
            _backward_kernel, scale=dk**-0.5, block_q=block_q, block_k=block_k,
            masked=masked,
        ),
        grid=(heads, s // block_q, tiles),
        in_specs=[
            rows_spec(dk), cols_spec(dk), rows_spec(dv), cols_spec(dv), rows_spec(dv),
            rows_spec(_LANES),
        ] + kv_specs + ([mask_spec] if masked else []),
        out_specs=[rows_spec(dk), keys_spec(dk), keys_spec(dv)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32), keys_shape(dk), keys_shape(dv)
        ],
        scratch_shapes=[
            bf16((rows, dk)), bf16((rows, dk)), bf16((dk, rows)), bf16((dk, rows)),
            bf16((rows, dv)), bf16((rows, dv)), bf16((dv, rows)), bf16((dv, rows)),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, dk), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="sparse_flash_bwd",
    )(
        q, jnp.swapaxes(q, 2, 3), do, jnp.swapaxes(do, 2, 3), o, lse,
        *kv_parts, *([mask] if masked else []),
    )

    def keys(t):  # (kv, tiles, d, block_k) -> (kv, S, d)
        return jnp.swapaxes(t, 2, 3).reshape(heads, s, t.shape[2])

    return dq, dk**-0.5 * keys(dkt), keys(dvt)


# ----------------------------------------------------------------------
# The differentiable call
# ----------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, mask, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, mask, block_q, block_k, interpret)[0]


def _flash_fwd(q, k, v, mask, block_q, block_k, interpret):
    kv_parts = (*split(k), *split(v))
    o, lse = _forward(q, kv_parts, mask, block_q, block_k, interpret)
    return o, (q, kv_parts, mask, o, lse)


def _flash_bwd(block_q, block_k, interpret, residuals, do):
    q, kv_parts, mask, o, lse = residuals
    dq, dk, dv = _backward(q, kv_parts, mask, o, lse, do, block_q, block_k, interpret)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def sparse_flash_attention(
    q: Array,
    k: Array,
    v: Array,
    mask: Optional[Array] = None,
    block_q: Optional[int] = None,
    block_k: int = BLOCK_K,
    interpret: bool = False,
) -> Array:
    """``q (S, kv, group, dk)``, ``k (S, kv, dk)``, ``v (S, kv, dv)``, ``mask
    (S, S)`` (bool, ``None``: causal) -> ``(S, kv * group * dv)``: each
    query's softmax over the keys it selects among those at or before it.
    ``block_q`` None takes ``query_block``'s. A swarm the tiles do not divide
    is padded to them: a padded query sees the keys causally (and is
    dropped), a padded key is after every real query."""
    s, heads, group, _ = q.shape
    block_q = block_q or query_block(group, s, block_k)
    tile = max(block_q, block_k)
    padded = -(-s // tile) * tile

    def rows(x):  # pad the sequence axis, then heads ahead of it
        x = jnp.pad(x, [(0, padded - s)] + [(0, 0)] * (x.ndim - 1))
        return jnp.moveaxis(x, 0, -2)

    if mask is not None:
        mask = jnp.pad(mask, ((0, padded - s), (0, padded - s)), constant_values=True)
        mask = mask.astype(jnp.int8)
    o = _flash(rows(q), rows(k), rows(v), mask, block_q, block_k, interpret)
    return jnp.moveaxis(o, -2, 0)[:s].reshape(s, -1)
