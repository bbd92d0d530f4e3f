"""The experts' grouped-product kernel (ops/grouped_matmul.py) in interpret
mode on the CPU, against ``lax.ragged_dot`` and the same product in float32
(an einsum a group at ``highest``): groups of 0, 1, 16 and 40 rows, groups
that straddle two row tiles, a tail of rows behind the last group, an offset
into a stack of two layers' experts, the LFM2 cell's two products at small
widths, in float32 and bfloat16. Rows behind the last group are never
compared: the kernel leaves them as they were.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ddl25spring_tpu.ops import grouped_matmul as gm


def reference(x, w, sizes, offset):
    """Each group's rows times its expert's weights, in float32 at
    ``highest``; the rows behind the last group zero."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out = np.zeros((x.shape[0], w.shape[-1]), np.float32)
    start = 0
    for g, size in enumerate(sizes):
        out[start:start + size] = np.asarray(jnp.einsum(
            "md,dn->mn", x[start:start + size], w[offset + g],
            precision=lax.Precision.HIGHEST))
        start += size
    return out


# (sizes, rows, D, N, stack, offset, tiles): stack = groups in the weights
# handed over; None tiles = ``gm.tiling``'s
CASES = {
    "groups_of_0_1_16_40": ([0, 1, 16, 40, 0, 7], 64, 128, 256, 6, 0,
                            (16, 128)),
    "a_group_straddles_two_row_tiles": ([5, 20, 3], 32, 128, 128, 3, 0,
                                        (16, 128)),
    "a_tail_behind_the_last_group": ([3, 9], 48, 128, 256, 2, 0, (16, 256)),
    "rows_no_whole_tile": ([7, 0, 13], 24, 128, 128, 3, 0, (16, 128)),
    "offset_into_two_layers_stack": ([6, 0, 11, 9], 32, 128, 256, 8, 4,
                                     (16, 128)),
    "the_first_layer_of_the_stack": ([4, 12, 0, 8], 32, 128, 128, 8, 0,
                                     (16, 128)),
    "gate_up_product_shrunk": (None, 64, 256, 384, 16, 8, None),
    "down_product_shrunk": (None, 64, 384, 256, 16, 8, None),
    "one_visit_of_each_group": ([8, 8, 16, 0, 32], 64, 256, 256, 5, 0,
                                (64, 256)),
}


def routed(rng, rows, groups):
    """Group sizes of ``rows / 4`` tokens' top 4 of ``groups`` experts."""
    picks = np.stack([rng.choice(groups, 4, replace=False)
                      for _ in range(rows // 4)]).ravel()
    return np.bincount(picks, minlength=groups).astype(np.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot_and_the_float32_product(case, dtype):
    sizes, rows, d, n, stack, offset, tiles = CASES[case]
    rng = np.random.default_rng(len(case))
    if sizes is None:
        sizes = routed(rng, rows, stack - offset)
    sizes = np.asarray(sizes, np.int32)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.normal(size=(rows, d)), dt)
    w = jnp.asarray(rng.normal(size=(stack, d, n)) * d ** -0.5, dt)
    got = jax.jit(lambda x, w, s, o: gm.grouped_matmul(
        x, w, s, o, tiles=tiles, interpret=True))(
            x, w, jnp.asarray(sizes), jnp.int32(offset))
    assert got.shape == (rows, n) and got.dtype == dt
    groups = np.zeros(stack, np.int32)
    groups[offset:offset + len(sizes)] = sizes
    xla = lax.ragged_dot(x, w, jnp.asarray(groups))
    want = reference(x, w, sizes, offset)
    t = int(sizes.sum())
    got, xla = np.asarray(got, np.float32)[:t], np.asarray(xla, np.float32)[:t]
    # float32: the order of the sums alone; bfloat16: one rounding of the
    # float32 sum to 8 bits of mantissa, as ragged_dot's
    tol = 1e-5 if dtype == "float32" else 2.0 ** -8
    scale = np.abs(want[:t]).max()
    assert scale > 0.5
    assert np.abs(got - want[:t]).max() <= tol * scale
    assert np.abs(got - xla).max() <= 2 * tol * scale


def test_no_group_holds_a_row():
    """One visit, to no row: nothing is written and nothing fails."""
    out = gm.grouped_matmul(jnp.ones((32, 128)), jnp.ones((3, 128, 128)),
                            jnp.zeros(3, jnp.int32), interpret=True)
    assert out.shape == (32, 128)


def test_the_plan_visits_each_group_once_a_row_tile_it_touches():
    """Groups [0, 1, 16, 40, 0, 7] over 64 rows in tiles of 16: group 1
    (row 0) one visit, group 2 (rows 1-16) two, group 3 (rows 17-56) three,
    group 5 (rows 57-63) one; the empty groups none."""
    sizes = jnp.asarray([0, 1, 16, 40, 0, 7], jnp.int32)
    group, tile, visits, bounds = gm.plan(sizes, 64, 16)
    n = int(visits[0])
    assert n == 7
    assert np.asarray(group)[:n].tolist() == [1, 2, 2, 3, 3, 3, 5]
    assert np.asarray(tile)[:n].tolist() == [0, 0, 1, 1, 2, 3, 3]
    assert np.asarray(bounds).tolist() == [0, 0, 1, 17, 57, 57, 64]


@pytest.mark.parametrize("rows,d,n,want", [
    (1024, 2048, 3072, (128, 1024)),      # the LFM2 cell's gate | up
    (1024, 1536, 2048, (128, 1024)),      # its down
    (40, 256, 384, (48, 384)),
    (64, 128, 96, (64, 96)),              # no whole tile: n as it is
])
def test_the_tile_rule(rows, d, n, want):
    assert gm.tiling(rows, d, n, jnp.bfloat16) == want


def test_what_the_compiled_kernel_takes():
    assert gm.supported(2048, 3072, jnp.bfloat16)
    assert not gm.supported(2048, 3072, jnp.float32)
    assert not gm.supported(2048, 64, jnp.bfloat16)
    assert not gm.supported(7168 + 64, 4096, jnp.bfloat16)
