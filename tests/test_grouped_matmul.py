"""The grouped-matmul kernel (ops/pallas/grouped_matmul.py) in interpret
mode against ``jax.lax.ragged_dot``, its oracle, and the routed layer's
long buffer on the kernel against the same buffer on ``ragged_dot``."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn import routed
from bigdl_tpu.ops.pallas import grouped_matmul as gm
from bigdl_tpu.ops.pallas import report

def _tick_over_128():
    """A window-cell tick's 256 assignments over 128 experts: 17 get
    none, the other 111 at least one, about 2 each."""
    rng = np.random.default_rng(128)
    counts = np.zeros(128, np.int64)
    landed = rng.permutation(128)[:111]
    counts[landed] = 1
    np.add.at(counts, rng.choice(landed, 256 - 111), 1)
    return counts.tolist()


# case -> (rows in the buffer, rows a group)
GROUPS = {
    "empty_groups": (64, [10, 0, 23, 0, 31]),
    "fewer_than_m": (64, [5, 17, 0, 9, 1]),
    "one_group_all": (64, [0, 0, 64, 0, 0]),
    "nothing_landed": (64, [0, 0, 0, 0, 0]),
    "tile_aligned": (64, [16, 16, 0, 32, 0]),
    # a 32-row tick's buffer: over all 128 experts held ...
    "tick_128_groups": (256, _tick_over_128()),
    # ... over 16 held of 256, about 16 of the 256 rows landed ...
    "tick_16_groups": (256, [1, 2, 0, 1, 1, 0, 2, 1, 1, 1, 2, 0, 1, 1, 2,
                             0]),
    # ... and with no row landed
    "tick_nothing_landed": (256, [0] * 128),
}


@pytest.mark.parametrize("tiling", [(16, 128), (32, 256)],
                         ids=["tm16_tn128", "tm32_tn256"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_kernel_matches_ragged_dot(case, dtype, tiling):
    m, groups = GROUPS[case]
    k, n = 256, 256
    counts = jnp.asarray(groups, jnp.int32)
    rng = np.random.default_rng(sum(groups))
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(0.1 * rng.standard_normal((len(counts), k, n)), dtype)
    got = gm.grouped_matmul(x, w, counts, tiling=tiling, interpret=True)
    assert got.shape == (m, n) and got.dtype == dtype
    want = jax.lax.ragged_dot(x, w, counts,
                              preferred_element_type=jnp.float32)
    landed = int(counts.sum())
    # rows past the landed ones are undefined: only the landed compare
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got[:landed], np.float32),
                               want[:landed], rtol=tol, atol=tol)


def test_visits_only_the_tiles_that_landed():
    counts = jnp.asarray([10, 0, 23, 0, 31], jnp.int32)
    group, tile, place, after, offsets, sizes = gm.visits(counts, 128, 16)
    n = int(sizes[0])
    # rows 0-9 | 10-32 | 33-63: tiles 0 | 0, 1, 2 | 2, 3 -> six visits
    # of three groups
    assert sizes.tolist() == [6, 3]
    assert group[:n].tolist() == [0, 2, 2, 2, 4, 4]
    assert tile[:n].tolist() == [0, 0, 1, 2, 2, 3]
    assert place[:n].tolist() == [0, 1, 1, 1, 2, 2]
    assert after[:n].tolist() == [2, 4, 4, 4, 4, 4]
    assert offsets.tolist() == [0, 10, 10, 33, 33, 64]
    assert gm.visits(jnp.zeros((5,), jnp.int32), 128, 16)[-1].tolist() \
        == [0, 0]


def test_tile_rule_at_the_cells_shapes():
    """The row tile follows the rows an expert expects; whole K; the
    widest lane-dense N tile whose double buffer fits."""
    assert gm.tiling_for(64, 7168, 2048) == (64, 1024)
    assert gm.tiling_for(64, 2048, 7168) == (64, 3584)
    assert gm.tiling_for(128, 2048, 1024) == (128, 1024)
    assert gm.tiling_for(128, 1024, 2048) == (128, 2048)
    assert gm.tiling_for(4, 1024, 2048)[0] == 16         # a bf16 tile
    assert gm.tiling_for(4, 1024, 2048, jnp.float32)[0] == 8
    assert gm.tiling_for(4096, 1024, 2048)[0] == gm.MAX_TM
    # a 32-row tick: 1 row an expert expected in the latent cell, 2 in
    # the window cell, one bf16 sublane tile either way
    assert gm.tiling_for(1, 7168, 2048) == (16, 1024)
    assert gm.tiling_for(1, 2048, 7168) == (16, 3584)
    assert gm.tiling_for(2, 2048, 1024) == (16, 1024)
    assert gm.tiling_for(2, 1024, 2048) == (16, 2048)
    assert gm.tiling_for(64, 1024, 200) is None          # not whole lanes


@pytest.fixture
def kernel_route(monkeypatch):
    """Route every buffer as on the TPU, with the kernel run by the
    Pallas interpreter (this tier has no chip)."""
    monkeypatch.setenv("BIGDL_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, interpret=True))
    report.reset()
    yield
    report.reset()


def wide_layer(held, seed=0):
    """Lane-dense widths (the kernel's) and 8 experts, 3 a token."""
    layer = routed.RoutedExperts(128, 128, 8, 3, n_shared=0,
                                 experts_held=held)
    params = layer.init_params(jax.random.PRNGKey(seed))
    params["router"]["bias"] = jnp.asarray(
        [0.3, 0.3, 0, 0, 0, 0, 0, 0], jnp.float32)
    return layer, params


def tick_layer(n_routed, held=None, seed=0):
    """Lane-dense widths and a tick's routing: 8 experts a token, of
    ``n_routed``."""
    layer = routed.RoutedExperts(128, 128, n_routed, 8, n_shared=0,
                                 experts_held=held)
    return layer, layer.init_params(jax.random.PRNGKey(seed))


# a batch whose likely buffer (LIKELY_ROWS_PER_TOKEN * T = 768 rows) is
# over SHORT_BATCH_ROWS too, so both branches of the cond take the kernel
# (at tm 128: the 144 rows an expert expects, rounded down)
T = 384
# a decode tick's rows: 32 x 8 = 256 assignments, one buffer and no cond
# (at tm 16: the 1-2 rows an expert expects, rounded up to a bf16 tile)
TICK = 32

ROUTED_CASES = {
    # 2 of the 6 unfavoured experts held: about 128 of 1152 assignments
    # land, on the likely buffer of 768
    "held_subset_likely": (lambda: wide_layer([2, 3]), T, False),
    # 6 of 8 held: more than 2 a token land, the full buffer runs
    "full_buffer": (lambda: wide_layer([0, 1, 2, 3, 4, 5]), T, False),
    # all held, one token in eight masked out: the full buffer
    "all_held_rows_mask": (lambda: wide_layer(None), T, True),
    # a tick over 128 experts, all held: about 2 rows each, some none
    "tick_all_held": (lambda: tick_layer(128), TICK, False),
    # a tick over 16 held of 256: about 16 of the 256 rows land
    "tick_held_subset": (lambda: tick_layer(256, range(16)), TICK, False),
    # a tick with one slot in eight idle
    "tick_rows_mask": (lambda: tick_layer(128), TICK, True),
}


@pytest.mark.parametrize("case", sorted(ROUTED_CASES))
def test_long_buffer_on_the_kernel_matches_ragged_dot(case, kernel_route):
    make, t, mask = ROUTED_CASES[case]
    layer, params = make()
    x = jax.random.normal(jax.random.PRNGKey(5), (t, 128))
    rows = (jnp.arange(t) % 8 != 0) if mask else None
    ids, w = layer.route(params, x)
    on_kernel, counts = jax.jit(layer.routed_part)(params, x, ids, w, rows)
    landed = int(counts.sum())
    full, likely = t * layer.k, routed.LIKELY_ROWS_PER_TOKEN * t
    if t == TICK:
        # one buffer of all the assignments, its three products on the
        # kernel
        assert full <= routed.SHORT_BATCH_ROWS
        assert report.report()["grouped_matmul"] == {"pallas": 3, "xla": 0}
        if case == "tick_held_subset":
            # most of the buffer lies past the landed rows
            assert landed < full // 8
    else:
        assert likely > routed.SHORT_BATCH_ROWS
        # three products a branch, both branches on the kernel
        assert report.report()["grouped_matmul"] == {"pallas": 6, "xla": 0}
        assert (landed <= likely) == (case == "held_subset_likely")
        if case == "held_subset_likely":
            # the rows past the landed ones, which the kernel leaves
            # undefined, are most of the buffer
            assert landed < likely // 2
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("BIGDL_TPU_FORCE_PALLAS", "0")
        on_xla, _ = jax.jit(layer.routed_part)(params, x, ids, w, rows)
    np.testing.assert_allclose(on_kernel, on_xla, rtol=1e-5, atol=1e-6)
    if mask:
        assert not np.asarray(on_kernel)[~np.asarray(rows)].any()


def _same_token_in_two_batches(layer, params, t, seed):
    """The token counts of two batches that share every fifth token and
    differ elsewhere, after checking that the shared tokens' outputs
    agree."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, 128))
    other = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, 128))
    keep = jnp.arange(t) % 5 == 0
    x2 = jnp.where(keep[:, None], x, other)
    f = jax.jit(lambda p, x: layer.routed_part(p, x, *layer.route(p, x)))
    y1, c1 = f(params, x)
    y2, c2 = f(params, x2)
    assert c1.tolist() != c2.tolist()
    np.testing.assert_allclose(np.asarray(y1)[np.asarray(keep)],
                               np.asarray(y2)[np.asarray(keep)],
                               rtol=1e-6, atol=1e-7)
    return c1, c2


def test_a_tokens_output_ignores_the_rest_of_its_buffer(kernel_route):
    """The same token in two long batches whose other rows differ (so
    its rows sit elsewhere in the sorted buffer, in other tiles) gets
    the same output."""
    layer, params = wide_layer([1, 2, 3])
    c1, c2 = _same_token_in_two_batches(layer, params, T, 6)
    assert report.report()["grouped_matmul"] == {"pallas": 6, "xla": 0}
    # both batches run the likely buffer, on the kernel
    likely = routed.LIKELY_ROWS_PER_TOKEN * T
    assert max(int(c1.sum()), int(c2.sum())) <= likely


def test_a_tokens_output_ignores_the_rest_of_its_tick(kernel_route):
    """The same at a tick's size: 32 tokens over 128 experts, where a
    row tile holds the rows of several experts."""
    layer, params = tick_layer(128)
    _same_token_in_two_batches(layer, params, TICK, 6)
    assert report.report()["grouped_matmul"] == {"pallas": 3, "xla": 0}
