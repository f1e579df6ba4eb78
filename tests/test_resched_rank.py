"""The queue rank of a window's re-queued pods (step._stable_queue_rank, PR 44):
ranked among themselves over R compacted slots, the sort of the whole pod axis
kept as the exact fallback for a window in which some cluster masks more than
R. Held here:

(a) on the masked rows the ranks equal the sort's, bit for bit, whichever
    branch runs: exact ties in every key (the slot decides), -0.0 against 0.0
    in the time key, masks of 0, 1, R - 1, R and R + 1 rows beside empty
    clusters, P < R, two keys (the CrashLoopBackOff caller), and the three
    sources of the node key (a thunk, a table, the slot itself);
(b) which branch RAN: the sort executes exactly where some cluster masks more
    than R rows, and the `over` flags the counters fold say the same;
(c) the compacted rank, lowered alone, holds no sort, gather or scatter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetriks_tpu.batched import step

C = 5
LAX_SORT = jax.lax.sort  # the reference's, whatever a test puts in its place


def sort_rank(keys, mask):
    """The rank as the parent computed it: unmasked rows keyed last, a stable
    sort of the whole pod axis, a second sort of its permutation."""
    P = mask.shape[1]
    ks = [
        jnp.where(mask, k, jnp.inf if k.dtype == jnp.float32 else 1 << 30).astype(k.dtype)
        for k in map(jnp.asarray, keys)
    ]
    iota = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None, :], mask.shape)
    out = LAX_SORT((*ks, iota), dimension=1, num_keys=len(ks), is_stable=True)
    return np.asarray(LAX_SORT((out[-1], iota), dimension=1, num_keys=1)[1])


def mask_of(counts, P, rng):
    mask = np.zeros((len(counts), P), bool)
    for c, n in enumerate(counts):
        mask[c, rng.choice(P, size=n, replace=False)] = True
    return mask


def tied_keys(P, rng, n_keys):
    """Keys with few distinct values each, so that whole groups of rows tie in
    every key, and both zeros in the time key."""
    time = rng.integers(0, 3, (C, P)).astype(np.float32) * np.float32(0.25)
    time[rng.random((C, P)) < 0.3] = np.float32(-0.0)
    ints = [rng.integers(0, 3, (C, P)).astype(np.int32) for _ in range(n_keys - 1)]
    return [time, *ints]


@pytest.fixture
def sorts_run(monkeypatch):
    """Count the executions of step's `lax.sort`: a callback inside a branch
    fires only when the branch is taken."""
    ran = []

    def counted(*args, **kwargs):
        jax.debug.callback(lambda: ran.append(1))
        return LAX_SORT(*args, **kwargs)

    monkeypatch.setattr(step.jax.lax, "sort", counted)
    yield ran


# (slots R, pod axis P, rows masked a cluster, keys, source of the node key)
CASES = {
    "ties-in-every-key": (8, 64, [5, 8, 3, 7, 0], 3, "plane"),
    "none-masked": (8, 64, [0, 0, 0, 0, 0], 3, "plane"),
    "one-row": (8, 64, [0, 1, 0, 0, 0], 3, "plane"),
    "r-minus-1": (8, 64, [0, 7, 0, 0, 0], 3, "plane"),
    "exactly-r": (8, 64, [0, 8, 0, 0, 2], 3, "plane"),
    "r-plus-1-sorts": (8, 64, [0, 9, 0, 0, 2], 3, "plane"),
    "every-row-masked-sorts": (8, 64, [64, 0, 64, 1, 0], 3, "plane"),
    "r-128": (128, 300, [128, 0, 17, 127, 1], 3, "table"),
    "r-128-plus-1-sorts": (128, 300, [129, 0, 17, 127, 1], 3, "table"),
    "pod-axis-under-r": (128, 16, [16, 0, 5, 1, 15], 3, "plane"),
    "two-keys": (8, 64, [6, 0, 8, 2, 0], 2, None),
    "two-keys-sorts": (8, 64, [6, 0, 30, 2, 0], 2, None),
    "node-key-thunk": (8, 64, [5, 8, 0, 7, 1], 3, "thunk"),
    "node-key-table": (8, 64, [5, 8, 0, 7, 1], 3, "table"),
    "node-key-table-sorts": (8, 64, [5, 12, 0, 7, 1], 3, "table"),
    "slot-order-key": (8, 64, [5, 8, 0, 7, 1], 3, "slot"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_rank_equals_the_sorts_on_the_masked_rows(case, monkeypatch, sorts_run):
    R, P, counts, n_keys, node_source = CASES[case]
    monkeypatch.setattr(step, "RANK_COMPACT_SLOTS", R)
    rng = np.random.default_rng(sorted(CASES).index(case))
    mask = mask_of(counts, P, rng)
    planes = tied_keys(P, rng, n_keys)
    keys, N = list(planes), 6
    if node_source in ("table", "thunk", "slot"):
        # The node key is a look-up: table[c, node[c, p]], with ties (two
        # nodes of one rank) and the pod's node drawn with repeats.
        node = rng.integers(0, N, (C, P)).astype(np.int32)
        table = rng.integers(0, 4, (C, N)).astype(np.int32)
        if node_source == "slot":
            planes[1] = keys[1] = node
        else:
            planes[1] = np.take_along_axis(table, node, axis=1)
            held = jnp.asarray(table)
            keys[1] = ((lambda: held) if node_source == "thunk" else held, jnp.asarray(node))
    keys = tuple(k if isinstance(k, tuple) else jnp.asarray(k) for k in keys)

    ranks, over = jax.jit(lambda m: step._stable_queue_rank(keys, m))(jnp.asarray(mask))
    jax.effects_barrier()
    ranks, over = np.asarray(ranks), np.asarray(over)

    want = sort_rank(planes, jnp.asarray(mask))
    assert ranks.dtype == np.int32 and ranks.shape == mask.shape
    np.testing.assert_array_equal(ranks[mask], want[mask])
    # A cluster's ranks are 0 .. n - 1, each once.
    for c, n in enumerate(counts):
        assert sorted(ranks[c, mask[c]]) == list(range(n))
    # The branch: the sort ran iff some cluster passed the slots, and the
    # flags the two counters are folded from say which clusters did.
    width = min(R, P)
    np.testing.assert_array_equal(over, np.asarray(counts) > width)
    assert bool(sorts_run) == (max(counts) > width) == case.endswith("sorts")


def test_the_zeros_of_the_time_key_tie():
    """-0.0 and 0.0 are one time to `lax.sort`'s comparator, so the next key
    decides between them: bits compared as integers would put -0.0 first."""
    time = jnp.asarray([[0.0, -0.0, 0.0, -0.0]], jnp.float32)
    name = jnp.asarray([[3, 2, 1, 0]], jnp.int32)
    mask = jnp.ones((1, 4), bool)
    ranks, _ = step._stable_queue_rank((time, name), mask)
    np.testing.assert_array_equal(np.asarray(ranks), [[3, 2, 1, 0]])
    np.testing.assert_array_equal(np.asarray(ranks), sort_rank((time, name), mask))


def test_the_compacted_rank_lowers_no_sort_gather_or_scatter():
    """At (8, 256) with a node table of 40: the keys come and the ranks go
    through dense compare-and-reduce forms only."""
    Cc, P, N, R = 8, 256, 40, 128
    f32, i32 = jnp.float32, jnp.int32

    def compacted(time, table, node, name, mask):
        n = mask.sum(axis=1, dtype=i32)
        pos = jnp.cumsum(mask, axis=1, dtype=i32) - 1
        return step._rank_compacted((time, (table, node), name), mask, n, pos, R)

    shapes = [
        jax.ShapeDtypeStruct(shape, dtype)
        for shape, dtype in (((Cc, P), f32), ((Cc, N), i32), ((Cc, P), i32), ((Cc, P), i32), ((Cc, P), jnp.bool_))
    ]
    text = jax.jit(compacted).lower(*shapes).as_text()
    for op in ("stablehlo.sort", "stablehlo.gather", "stablehlo.scatter", "top_k"):
        assert op not in text, op
    # ... and the whole rank holds its sorts in the fallback branch alone.
    whole = jax.jit(
        lambda time, table, node, name, mask: step._stable_queue_rank((time, (table, node), name), mask)
    ).lower(*shapes).as_text()
    assert whole.count("stablehlo.sort") == 2 and "stablehlo.case" in whole  # `cond` lowers to `case`
    assert "stablehlo.gather" not in whole and "stablehlo.scatter" not in whole
