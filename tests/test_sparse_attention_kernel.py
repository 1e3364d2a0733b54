"""The sparse attention kernels (``ops/sparse_attention.py``) in Pallas's
interpreter, held to the trunk's XLA paths at float32 ``highest`` (under a
mask the blocked ``_attend_block``; the causal mask alone the dense mixers'
pairs loop, ``_causal_pairs``): the output and the gradients with respect to
q, k and v, for head dim 128 in tiles of 128 as on the chip and at the tiny
trunk's shapes (padded to the tiles); group 8, 4 and 1; the causal mask
alone and the indexer's kind of mask; rows that select nothing in a tile;
two and four key tiles; keys of 192 against values of 128 (latent
attention), heads of 64, and the query tile ``query_block`` picks.

Tolerance: the kernel multiplies in three bf16 passes, so each product is
within about 2^-16 of its float32 value; outputs and gradients are compared
to 1e-4 of their largest entry (observed 4e-6 to 1.4e-5). The precision
pin shows the three passes are there: at one pass the same kernel is at
least ten times as far from float32.

Beside the interpreter, the kernels compile for a described TPU v5e, with
nothing run, at the trunk cells' shapes: Mosaic, not the interpreter,
decides whether it takes a block of keys 192 wide, a head of 64 and the
query tiles ``query_block`` picks. All in this file and inside fixtures:
only one process may load the TPU's library, and only the worker that is
given this file does.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from marl_distributedformation_tpu.models.trunk import _attend_block, _causal_pairs
from marl_distributedformation_tpu.ops import sparse_attention

TOLERANCE = 1e-4


def _inputs(s, heads, group, d, mask, seed=0, dv=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    dv = dv or d
    q = jax.random.normal(keys[0], (s, heads, group, d))
    k = jax.random.normal(keys[1], (s, heads, d))
    v = jax.random.normal(keys[2], (s, heads, dv))
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
    w = jax.random.normal(keys[4], (s, heads * group * dv))
    return (q, k, v), mask, w


def _reference(q, k, v, mask):
    with jax.default_matmul_precision("highest"):
        if mask is None:  # the dense mixers' XLA path, blocks of 128 queries
            return _causal_pairs(q, k, v, 128, whole_pairs=False)
        return _attend_block(q, k, v, mask)


def _with_grads(attend, args, mask, w):
    loss = lambda q, k, v: (attend(q, k, v, mask) * w).sum()  # noqa: E731
    out = jax.jit(attend)(*args, mask)
    return out, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args)


def _gap(ours, theirs):
    return float(jnp.abs(ours - theirs).max() / jnp.abs(theirs).max())


CASES = {
    # s, kv heads, group, keys' width, values' width, mask, block_q (None:
    # query_block's), block_k
    "group8_two_key_tiles": (256, 2, 8, 128, 128, "indexer", 128, 128),
    "group8_four_key_tiles": (512, 1, 8, 128, 128, "indexer", 128, 128),
    "group8_causal_only": (256, 1, 8, 128, 128, None, 128, 128),
    "group1_masked": (512, 2, 1, 128, 128, "indexer", 128, 128),
    "group1_causal_only": (256, 2, 1, 128, 128, None, 128, 128),
    "rows_select_nothing_in_a_tile": (384, 1, 8, 128, 128, "empty_tiles", 128, 128),
    "chip_tiles_two_query_tiles_a_key_tile": (512, 1, 8, 128, 128, "indexer", 128, 256),
    "tiny_trunk_masked": (32, 2, 2, 16, 16, "indexer", 128, 512),
    "tiny_trunk_causal_only": (32, 2, 2, 16, 16, None, 128, 512),
    # the dense mixers: one query tile of 512 over four key tiles; two query
    # tiles of 256 over two key tiles each; one of 1,024 over two of 512
    "mla_keys_192_values_128_group1_causal_only": (512, 2, 1, 192, 128, None, None, 128),
    "gqa_head64_group4_causal_only": (512, 2, 4, 64, 64, None, None, 128),
    "group1_query_block_1024_causal_only": (1024, 1, 1, 128, 128, None, None, 512),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_blocked_path(case):
    s, heads, group, dk, dv, mask, block_q, block_k = CASES[case]
    args, mask, w = _inputs(s, heads, group, dk, mask, dv=dv)
    kernel = functools.partial(
        sparse_attention.sparse_flash_attention, block_q=block_q, block_k=block_k,
        interpret=True,
    )
    out, grads = _with_grads(kernel, args, mask, w)
    r_out, r_grads = _with_grads(_reference, args, mask, w)
    assert out.shape == (s, heads * group * dv)
    assert _gap(out, r_out) < TOLERANCE
    for name, ours, theirs in zip("qkv", grads, r_grads):
        assert _gap(ours, theirs) < TOLERANCE, name


@pytest.mark.parametrize(
    "shape, block_q",
    [
        ((8, 8192), 128),  # Keye's and Solar's layers: the tile they had
        ((4, 8192), 256),  # LFM2's
        ((1, 8192), 1024),  # latent attention's
        ((16, 8192), 128),  # never under BLOCK_Q
        ((2, 32), 512),  # no more than the swarm padded to a key tile
    ],
)
def test_the_query_tile_holds_about_1024_rows(shape, block_q):
    group, s = shape
    assert sparse_attention.query_block(group, s) == block_q


def _three_against_one(monkeypatch, args, mask, w):
    """The kernel's gaps to float32 ``highest`` in three passes, and with the
    low bf16 part of every operand dropped (one pass): the output's, then
    the gradients'."""
    kernel = functools.partial(
        sparse_attention.sparse_flash_attention, block_k=128, interpret=True
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
    return [
        (_gap(ours, theirs), _gap(single, theirs))
        for ours, single, theirs in zip(
            [three[0], *three[1]], [one[0], *one[1]], [r_out, *r_grads]
        )
    ]


def test_three_passes_are_ten_times_nearer_float32_than_one(monkeypatch):
    """The same kernel with the low bf16 part of every operand dropped (one
    pass) is at least ten times as far from float32 ``highest``, in the
    output and in every gradient."""
    args, mask, w = _inputs(256, 1, 8, 128, "indexer", seed=1)
    for three, one in _three_against_one(monkeypatch, args, mask, w):
        assert 10 * three < one


def test_three_passes_are_ten_times_nearer_float32_than_one_keys_wider_than_values(
    monkeypatch,
):
    """The same pin where the keys are 192 wide and the values 128, with the
    causal mask alone (latent attention's call)."""
    args, mask, w = _inputs(256, 2, 1, 192, None, seed=1, dv=128)
    for three, one in _three_against_one(monkeypatch, args, mask, w):
        assert 10 * three < one


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


CELL_SHAPES = {
    # s, kv heads, group, keys' width, values' width, masked
    "keye_sparse_gqa_masked": (8192, 4, 8, 128, 128, True),
    "solar_gated_gqa": (8192, 1, 8, 128, 128, False),
    "lfm2_gqa_heads_of_64": (8192, 8, 4, 64, 64, False),
    "xing4_mla_keys_192_values_128": (8192, 16, 1, 192, 128, False),
}


@pytest.mark.parametrize("case", sorted(CELL_SHAPES))
def test_forward_and_backward_compile_for_a_v5e(case, one_chip, no_compile_cache):
    s, heads, group, dk, dv, masked = CELL_SHAPES[case]
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip
    )

    def loss(q, k, v, mask):
        return sparse_attention.sparse_flash_attention(q, k, v, mask if masked else None).sum()

    compiled = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        .lower(shape(s, heads, group, dk), shape(s, heads, dk), shape(s, heads, dv),
               shape(s, s, dtype=jnp.bool_))
        .compile()  # raises what Mosaic refuses, or more VMEM than the limit
    )
    assert compiled.as_text().count("tpu_custom_call") >= 2  # forward, backward
