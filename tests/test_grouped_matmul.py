"""The experts' grouped matmul (ops/pallas/grouped_matmul.py) on the CPU:
the kernel in interpret mode against ``jax.lax.ragged_dot`` and against
a dense loop over the groups, float32, so the three differ by nothing
but the order of the same float32 sums (read: 0 to 2e-6 on values of
deviation 1).  Whether Mosaic takes the kernel at the published widths
is ``tests/test_chip_compile.py``'s to say; what it costs is the chip's
(tools/grouped_matmul_sweep.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas import grouped_matmul as gm

TOL = 2e-5
LAYERS, E, K, N = 3, 8, 64, 24
COUNTER = "serving.grouped_matmul_kernel_calls"

# name -> (group sizes, window rows)
CASES = {
    "even": ([4] * 8, 8),
    "skewed_empty_front_middle_end": ([0, 0, 9, 1, 0, 0, 14, 0], 8),
    "one_group_many_windows": ([0, 0, 0, 40, 0, 0, 0, 0], 8),
    "rows_no_multiple_of_the_window": ([5, 0, 3, 7, 1, 0, 2, 19], 16),
    "fewer_rows_than_a_window": ([1, 0, 0, 2, 0, 0, 0, 2], 16),
    "every_group_crosses_an_edge": ([6] * 8, 8),
    "the_default_window": ([3, 1, 0, 5, 2, 0, 4, 6], None),
}


def _operands(sizes, seed=0):
    rng = np.random.default_rng(seed)
    m = int(sum(sizes))
    xs = jnp.asarray(rng.normal(size=(m, K)), jnp.float32)
    stacks = [jnp.asarray(rng.normal(size=(LAYERS, E, K, N)) / np.sqrt(K),
                          jnp.float32) for _ in range(2)]
    return xs, jnp.asarray(sizes, jnp.int32), stacks


def _dense(xs, sizes, w, w_up=None):
    """A loop over the groups, numpy, float64 sums."""
    xs, w = np.asarray(xs, np.float64), np.asarray(w, np.float64)
    out, start = np.zeros((xs.shape[0], w.shape[-1])), 0
    for g, n in enumerate(np.asarray(sizes)):
        rows = xs[start:start + n]
        y = rows @ w[g]
        if w_up is not None:
            y = y / (1 + np.exp(-y)) * (rows @ np.asarray(w_up[g],
                                                           np.float64))
        out[start:start + n] = y
        start += n
    return out


@pytest.mark.parametrize("form", ["matmul", "gate_up"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot_and_a_dense_loop(case, form):
    sizes, tm = CASES[case]
    xs, sz, (gate, up) = _operands(sizes)
    layer = 1
    stacks = (gate, up) if form == "gate_up" else (gate,)
    got = gm._grouped_tpu(xs, sz, stacks, jnp.int32(layer), tm=tm,
                          gate_up=form == "gate_up", interpret=True)
    ragged = jax.lax.ragged_dot(xs, gate[layer], sz)
    dense = _dense(xs, sz, gate[layer])
    if form == "gate_up":
        ragged = jax.nn.silu(ragged) * jax.lax.ragged_dot(xs, up[layer], sz)
        dense = _dense(xs, sz, gate[layer], up[layer])
    assert got.shape == ragged.shape and got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - np.asarray(ragged)).max() < TOL
    assert np.abs(np.asarray(got) - dense).max() < TOL


@pytest.mark.parametrize("form", ["matmul", "gate_up"])
def test_the_stack_with_a_layer_index_is_that_layer_alone(form,
                                                          monkeypatch):
    """4-D with a traced layer index against the same layer 3-D, through
    the public functions with the kernel path forced."""
    monkeypatch.setattr(gm, "pallas_enabled", lambda: True)
    monkeypatch.setattr(gm, "_grouped_tpu", functools.partial(
        gm._grouped_tpu, interpret=True))
    xs, sz, (gate, up) = _operands([5, 0, 3, 7, 1, 0, 2, 19])
    if form == "gate_up":
        whole = jax.jit(lambda li: gm.grouped_gate_up(xs, sz, gate, up, li))
        one = lambda i: gm.grouped_gate_up(xs, sz, gate[i], up[i])
    else:
        whole = jax.jit(lambda li: gm.grouped_matmul(xs, sz, gate, li))
        one = lambda i: gm.grouped_matmul(xs, sz, gate[i])
    outs = [np.asarray(whole(jnp.int32(i))) for i in range(LAYERS)]
    for i in range(LAYERS):
        assert np.array_equal(outs[i], np.asarray(one(i)))
    assert not np.array_equal(outs[0], outs[1])


def test_column_tiles_of_an_expert():
    """Where VMEM asks for it an expert goes by columns: the window's
    steps run once a column tile."""
    rng = np.random.default_rng(3)
    sizes = [5, 0, 3, 7, 1, 0, 2, 19]
    xs = jnp.asarray(rng.normal(size=(sum(sizes), K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, E, K, 256)) / np.sqrt(K),
                    jnp.float32)
    sz = jnp.asarray(sizes, jnp.int32)
    got = gm._grouped_tpu(xs, sz, (w,), jnp.int32(1), tm=16, tn=128,
                          interpret=True)
    assert np.abs(np.asarray(got) - _dense(xs, sz, w[1])).max() < TOL


def test_gate_up_rounds_once_to_the_rows_dtype():
    """bf16 rows: float32 products and silu, one rounding of the result
    (what moe_ffn did around two ragged_dots)."""
    xs, sz, (gate, up) = _operands([4] * 8)
    xs, gate, up = (a.astype(jnp.bfloat16) for a in (xs, gate, up))
    got = gm._grouped_tpu(xs, sz, (gate, up), jnp.int32(0), tm=16,
                          gate_up=True, interpret=True)
    f32 = jnp.float32
    want = (jax.nn.silu(jax.lax.ragged_dot(xs, gate[0], sz,
                                           preferred_element_type=f32))
            * jax.lax.ragged_dot(xs, up[0], sz, preferred_element_type=f32)
            ).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    # one bf16 step of the largest value, where a sum's order moved it
    step = float(jnp.abs(want.astype(f32)).max()) * 2 ** -7
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() <= step


@pytest.mark.parametrize("case", sorted(CASES))
def test_work_list_covers_each_group_once_a_window_it_touches(case):
    sizes, tm = CASES[case]
    tm = tm or 8
    m = sum(sizes)
    steps = -(-m // tm) + len(sizes)
    group, window, offsets, live = (np.asarray(a) for a in gm._work_list(
        jnp.asarray(sizes, jnp.int32), tm, steps))
    want = [(g, w) for g, n in enumerate(sizes) if n
            for w in range(sum(sizes[:g]) // tm,
                           (sum(sizes[:g]) + n - 1) // tm + 1)]
    live = int(live[0])
    assert live == len(want) <= steps
    assert list(zip(group[:live], window[:live])) == want
    # the steps past the last re-name its blocks: nothing is fetched
    assert set(zip(group[live:], window[live:])) <= {want[-1]}
    assert list(offsets) == [sum(sizes[:g]) for g in range(len(sizes) + 1)]


def test_tiles_are_a_function_of_shapes():
    assert gm.window_rows(384, 2) == gm.WINDOW_ROWS == 128
    assert gm.window_rows(24576, 2) == 128
    assert gm.window_rows(48, 2) == 48 and gm.window_rows(5, 2) == 16
    assert gm.window_rows(5, 4) == 8
    # kanana2's experts whole, gate and up together: 12 MiB of blocks
    assert gm.column_tile(128, 2048, 768, 2, 2, 2) == 768
    assert gm.column_tile(128, 768, 2048, 2, 1, 4) == 2048
    # DeepSeek-V3's own [7168, 2048] experts go by columns
    tn = gm.column_tile(128, 7168, 2048, 2, 2, 2)
    assert tn < 2048 and 2048 % tn == 0 and tn % 128 == 0
    assert gm._step_vmem_bytes(128, 7168, tn, 2, 2, 2) <= gm._STEP_VMEM_BYTES
    with pytest.raises(ValueError, match="no column tile"):
        gm.column_tile(128, 1 << 20, 100, 2, 1, 4)


def test_off_the_chip_it_is_ragged_dot_and_counts_nothing():
    xs, sz, (gate, up) = _operands([5, 0, 3, 7, 1, 0, 2, 19])
    before = metrics.counter(COUNTER).value
    got = gm.grouped_matmul(xs, sz, gate, jnp.int32(2))
    mid = gm.grouped_gate_up(xs, sz, gate, up, jnp.int32(2))
    assert metrics.counter(COUNTER).value == before
    assert np.abs(np.asarray(got) - _dense(xs, sz, gate[2])).max() < TOL
    assert np.abs(np.asarray(mid)
                  - _dense(xs, sz, gate[2], up[2])).max() < TOL
    text = str(jax.make_jaxpr(gm.grouped_matmul)(xs, sz, gate, jnp.int32(2)))
    assert "ragged_dot" in text and "pallas_call" not in text
