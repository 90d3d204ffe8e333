"""ops/join.py:bucketize_planes against a plain loop, what its lowered
program may not hold, and the payload lane it carries where the index rode.

The reference is the definition: walk the points in index order and give
each the next free lane of its cell; a point past ``cap`` counts as
overflow, an invalid or out-of-grid point is neither stored nor counted.
Planes and overflow have to come out bit for bit — the join's pair order
and every test that pins it rest on the slot order.
"""

import re

import jax
import numpy as np
import pytest

from spatialflink_tpu.ops.join import bucketize_planes


def reference_planes(xy, valid, cells, grid_n, cap):
    num_cells = grid_n * grid_n
    bx = np.zeros((num_cells, cap), xy.dtype)
    by = np.zeros((num_cells, cap), xy.dtype)
    bidx = np.full((num_cells, cap), -1, np.int32)
    used = np.zeros(num_cells, np.int64)
    overflow = 0
    for i in range(len(cells)):
        c = int(cells[i])
        if not valid[i] or not 0 <= c < num_cells:
            continue
        if used[c] < cap:
            bx[c, used[c]], by[c, used[c]], bidx[c, used[c]] = xy[i, 0], xy[i, 1], i
            used[c] += 1
        else:
            overflow += 1
    shape = (grid_n, grid_n, cap)
    return bx.reshape(shape), by.reshape(shape), bidx.reshape(shape), overflow


def _uniform(rng, n, num_cells):
    return rng.integers(0, num_cells, n), np.ones(n, bool)


def _overflowing(rng, n, num_cells):
    # Half the points in cell 3: far past any cap here, the first `cap` by
    # original index are the ones kept.
    cells = np.where(rng.random(n) < 0.5, 3, rng.integers(0, num_cells, n))
    return cells, np.ones(n, bool)


def _invalid(rng, n, num_cells):
    return rng.integers(0, num_cells, n), rng.random(n) < 0.7


def _out_of_grid(rng, n, num_cells):
    # num_cells is the assembler's out-of-grid id; ids beyond it must fare
    # the same.
    return rng.integers(0, num_cells + 40, n), np.ones(n, bool)


def _last_cell(rng, n, num_cells):
    # The one window that runs past the end of the sorted lanes.
    return np.full(n, num_cells - 1), np.ones(n, bool)


def _mixed(rng, n, num_cells):
    cells = np.where(rng.random(n) < 0.3, num_cells - 1,
                     rng.integers(0, num_cells + 5, n))
    return cells, rng.random(n) < 0.85


#: name → (n, grid_n, cap, maker of (cells, valid))
CASES = {
    "overflowing_cell": (2000, 6, 64, _overflowing),
    "invalid_points": (1500, 6, 64, _invalid),
    "out_of_grid_points": (1500, 6, 64, _out_of_grid),
    "empty_side": (0, 5, 8, _uniform),
    "all_in_last_cell": (777, 4, 128, _last_cell),
    "n_not_a_multiple_of_128": (1001, 7, 64, _mixed),
    "one_point": (1, 3, 8, _uniform),
    "cap_8": (1024, 8, 8, _mixed),
    "cap_128": (4096, 5, 128, _mixed),
    "cap_256": (4096, 4, 256, _mixed),
    "more_cells_than_points": (300, 40, 8, _mixed),
}


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["float32", "float64"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_bucketize_planes_equals_the_loop(case, dtype):
    n, grid_n, cap, make = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    cells, valid = make(rng, n, grid_n * grid_n)
    cells = cells.astype(np.int32)
    xy = rng.uniform(-1.05, 1.05, (n, 2)).astype(dtype)
    want = reference_planes(xy, valid, cells, grid_n, cap)
    got = jax.jit(bucketize_planes, static_argnums=(3, 4))(
        xy, valid, cells, grid_n, cap)
    for name, g, w in zip(("x", "y", "index"), got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype, name
        assert g.shape == w.shape, name
        # Bit for bit: compare the lanes' bytes, not their values.
        assert g.tobytes() == w.tobytes(), name
    assert int(got[3]) == want[3]
    if case == "overflowing_cell":
        assert want[3] > 0
        kept = np.asarray(got[2]).reshape(-1, cap)[3]
        assert kept.tolist() == np.flatnonzero(cells == 3)[:cap].tolist()


@pytest.mark.parametrize("case", ["full_window", "in_grid_overflow"])
def test_payload_rides_where_the_index_rode(case):
    """A payload lane (the trajectory join's ids) comes out as the third
    plane: each slot holds the payload of the point whose index it held
    without one, -1 where empty; x, y and the overflow do not move."""
    n, grid_n, cap = 3000, 8, 128
    rng = np.random.default_rng(44)
    if case == "full_window":
        cells, valid = _uniform(rng, n, grid_n * grid_n)
    else:
        cells, valid = _overflowing(rng, n, grid_n * grid_n)
    cells = cells.astype(np.int32)
    xy = rng.uniform(-1.05, 1.05, (n, 2)).astype(np.float32)
    payload = rng.integers(0, 16_384, n).astype(np.int32)
    run = jax.jit(bucketize_planes, static_argnums=(3, 4))
    plain = [np.asarray(a) for a in run(xy, valid, cells, grid_n, cap)]
    got = [np.asarray(a) for a in run(xy, valid, cells, grid_n, cap, payload)]
    index = plain[2]
    want = np.where(index >= 0, payload[np.maximum(index, 0)], -1)
    assert got[2].dtype == np.int32 and np.array_equal(got[2], want)
    for name, g, w in zip(("x", "y"), got[:2], plain[:2]):
        assert g.tobytes() == w.tobytes(), name
    assert int(got[3]) == int(plain[3])
    assert (int(plain[3]) > 0) == (case == "in_grid_overflow")
    assert (index >= 0).sum() > n // 4  # the planes hold points


def _window_args(n):
    lanes = (jax.ShapeDtypeStruct((n, 2), np.float32),
             jax.ShapeDtypeStruct((n,), np.bool_),
             jax.ShapeDtypeStruct((n,), np.int32))
    return lanes + lanes, dict(grid_n=6, layers=1, radius=np.float32(0.1),
                               cap_left=16, cap_right=16, max_pairs=4096)


@pytest.mark.parametrize("program", ["pallas", "xla"])
def test_point_join_program_takes_no_payload_operand(program):
    """With no payload the window program's operands are the parent's: the
    six lanes and the radius — no seventh or eighth array; with one, the
    two payload lanes are the only ones added."""
    from spatialflink_tpu.ops.join import join_window_bucketed
    from spatialflink_tpu.ops.pallas_join import join_window_pallas

    if program == "pallas":
        fn, kw_program = join_window_pallas, {"interpret": True}
    else:
        fn, kw_program = jax.jit(join_window_bucketed, static_argnames=(
            "grid_n", "layers", "cap_left", "cap_right", "max_pairs")), {}
    n = 1024
    args, kw = _window_args(n)
    payload = jax.ShapeDtypeStruct((n,), np.int32)

    def operands(**extra):
        return [a.shape for a in jax.tree_util.tree_leaves(
            fn.lower(*args, **kw, **kw_program, **extra).args_info)]

    plain = operands()
    assert sorted(plain) == sorted([(n, 2), (n,), (n,)] * 2 + [()])
    assert operands(left_payload=None, right_payload=None) == plain
    loaded = operands(left_payload=payload, right_payload=payload)
    assert sorted(loaded) == sorted(plain + [(n,), (n,)])


def _gather_index_rows(text):
    """For every gather of a StableHLO module, how many slices it takes
    (the index tensor's elements over its index-vector length)."""
    rows = []
    for m in re.finditer(
            r'"stablehlo\.gather"\(.*?\)\s*<\{.*?\}>\s*:\s*'
            r"\(tensor<[^>]*>,\s*tensor<([^>]*)>\)", text, re.S):
        dims = [int(d) for d in m.group(1).split("x")[:-1]]
        vec = re.search(r"index_vector_dim\s*=\s*(\d+)", m.group(0))
        ivd = int(vec.group(1)) if vec else len(dims)
        rows.append(int(np.prod([d for k, d in enumerate(dims) if k != ivd])))
    return rows


def test_lowered_program_has_no_per_point_index_op():
    """The regression PR 40 removed, caught off the chip: a computed-index
    gather or scatter runs element by element on a v5e (7.5–8.2 ns an
    element), so twelve of them over 2¹⁹ lanes were ≈ 50 ms a window."""
    n, grid_n, cap = 1 << 15, 50, 128
    lowered = jax.jit(bucketize_planes, static_argnums=(3, 4)).lower(
        jax.ShapeDtypeStruct((n, 2), np.float32),
        jax.ShapeDtypeStruct((n,), np.bool_),
        jax.ShapeDtypeStruct((n,), np.int32), grid_n, cap)
    text = lowered.as_text()
    assert "stablehlo.sort" in text  # the text is the program's, not a stub's
    assert "stablehlo.scatter" not in text
    assert text.count('stablehlo.gather"(') == len(_gather_index_rows(text))
    # A gather a cell (its edges, its rows) is what the formulation is made
    # of; one a lane is what it replaced.
    for rows in _gather_index_rows(text):
        assert rows <= 4 * (grid_n * grid_n + 1) < n, rows
