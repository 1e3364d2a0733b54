"""The sparse attention kernels (``ops/sparse_attention.py``) in Pallas's
interpreter, held to the trunk's blocked XLA path (``_attend_block``) at
float32 ``highest``: the output and the gradients with respect to q, k and
v, for head dim 128 in tiles of 128 as on the chip and at the tiny trunk's
shapes (padded to the tiles); group 8 and group 1; the causal mask alone
and the indexer's kind of mask; rows that select nothing in a tile; two and
four key tiles.

Tolerance: the kernel multiplies in three bf16 passes, so each product is
within about 2^-16 of its float32 value; outputs and gradients are compared
to 1e-4 of their largest entry (observed 4e-6 to 1.4e-5). The precision
pin shows the three passes are there: at one pass the same kernel is at
least ten times as far from float32.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from marl_distributedformation_tpu.models.trunk import _attend_block
from marl_distributedformation_tpu.ops import sparse_attention

TOLERANCE = 1e-4


def _inputs(s, heads, group, d, mask, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (s, heads, group, d))
    k = jax.random.normal(keys[1], (s, heads, d))
    v = jax.random.normal(keys[2], (s, heads, d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    t = jnp.arange(s)
    if mask == "indexer":  # a random share of the visible keys, the diagonal kept
        picked = jax.random.uniform(keys[3], (s, s)) < 0.3
        mask = causal & (picked | (t[:, None] == t[None, :]))
    elif mask == "empty_tiles":
        # even rows select their own key and the one before: the tiles before
        # the diagonal's select nothing; odd rows keys 0 and 1: the tiles
        # after the first nothing
        late = (t[None, :] <= t[:, None]) & (t[None, :] >= t[:, None] - 1)
        mask = jnp.where(t[:, None] % 2 == 0, late, t[None, :] <= 1)
    w = jax.random.normal(keys[4], (s, heads * group * d))
    return (q, k, v), mask, w


def _reference(q, k, v, mask):
    if mask is None:
        mask = jnp.tril(jnp.ones((q.shape[0],) * 2, bool))
    with jax.default_matmul_precision("highest"):
        return _attend_block(q, k, v, mask)


def _with_grads(attend, args, mask, w):
    loss = lambda q, k, v: (attend(q, k, v, mask) * w).sum()  # noqa: E731
    out = jax.jit(attend)(*args, mask)
    return out, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args)


def _gap(ours, theirs):
    return float(jnp.abs(ours - theirs).max() / jnp.abs(theirs).max())


CASES = {
    # s, kv heads, group, head dim, mask, block_q, block_k
    "group8_two_key_tiles": (256, 2, 8, 128, "indexer", 128, 128),
    "group8_four_key_tiles": (512, 1, 8, 128, "indexer", 128, 128),
    "group8_causal_only": (256, 1, 8, 128, None, 128, 128),
    "group1_masked": (512, 2, 1, 128, "indexer", 128, 128),
    "group1_causal_only": (256, 2, 1, 128, None, 128, 128),
    "rows_select_nothing_in_a_tile": (384, 1, 8, 128, "empty_tiles", 128, 128),
    "chip_tiles_two_query_tiles_a_key_tile": (512, 1, 8, 128, "indexer", 128, 256),
    "tiny_trunk_masked": (32, 2, 2, 16, "indexer", 128, 512),
    "tiny_trunk_causal_only": (32, 2, 2, 16, None, 128, 512),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_blocked_path(case):
    s, heads, group, d, mask, block_q, block_k = CASES[case]
    args, mask, w = _inputs(s, heads, group, d, mask)
    kernel = functools.partial(
        sparse_attention.sparse_flash_attention, block_q=block_q, block_k=block_k,
        interpret=True,
    )
    out, grads = _with_grads(kernel, args, mask, w)
    r_out, r_grads = _with_grads(_reference, args, mask, w)
    assert out.shape == (s, heads * group * d)
    assert _gap(out, r_out) < TOLERANCE
    for name, ours, theirs in zip("qkv", grads, r_grads):
        assert _gap(ours, theirs) < TOLERANCE, name


def test_three_passes_are_ten_times_nearer_float32_than_one(monkeypatch):
    """The same kernel with the low bf16 part of every operand dropped (one
    pass) is at least ten times as far from float32 ``highest``, in the
    output and in every gradient."""
    args, mask, w = _inputs(256, 1, 8, 128, "indexer", seed=1)
    kernel = functools.partial(
        sparse_attention.sparse_flash_attention, block_q=128, block_k=128, interpret=True
    )
    r_out, r_grads = _with_grads(_reference, args, mask, w)
    three = _with_grads(kernel, args, mask, w)
    split = sparse_attention.split

    def one_pass(x):
        hi, lo = split(x)
        return hi, jnp.zeros_like(lo)

    monkeypatch.setattr(sparse_attention, "split", one_pass)
    jax.clear_caches()
    one = _with_grads(lambda *a: kernel(*a), args, mask, w)  # traced anew
    for ours, single, theirs in zip(
        [three[0], *three[1]], [one[0], *one[1]], [r_out, *r_grads]
    ):
        assert 10 * _gap(ours, theirs) < _gap(single, theirs)
