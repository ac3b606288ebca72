"""The sorted form of the expert block (prompt rows and sparse decode
steps): only the routed pairs on held experts are computed, by a Pallas
grouped matmul (interpreted here). The op against the dense form and the
float64 reference. A section of ``tests/test_moe.py``, in a file of its
own since PR 59; the layer loop's and the engine's side of it is
``tests/test_moe_sorted_engine.py``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cake_tpu.ops import moe
from cake_tpu.ops.moe import (
    SORTED_MIN_ROWS, SORTED_MIN_ROWS_INT8, GroupRouting, _moe_dense,
    expert_form, moe_swiglu, router_topk,
)

from moe_kit import kernels  # noqa: F401


# ---------------------------------------------------------------------------
# The sorted form (prompt rows): only the routed pairs on held experts are
# computed, by a Pallas grouped matmul (interpreted here).
# ---------------------------------------------------------------------------

# name -> (held, scored, top_k, routing, first held, stacks' type)
SORTED_CASES = {
    "mixtral-bf16": (8, 8, 2, None, 0, "bf16"),
    "mixtral-int8": (8, 8, 2, None, 0, "int8"),
    "12-of-192-grouped": (12, 192, 8, GroupRouting(8, 4, True, 2.5), 24,
                          "f32"),
    "128-of-512-bias": (128, 512, 8, GroupRouting(8, 4, True, 2.5, "bias"),
                        128, "f32"),
}


def _sorted_case(name, rows, h=32, f=64, seed=0):
    """``(x [1, rows, h], router, (gate, up, down), kwargs, plain)`` of a
    case; ``plain``: the three stacks as float64 numpy, dequantised."""
    from cake_tpu.ops.quant import dequantize_linear, quantize_linear

    held, scored, top_k, routing, first, kind = SORTED_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jnp.bfloat16 if kind in ("bf16", "int8") else jnp.float32
    x = jax.random.normal(ks[0], (1, rows, h)).astype(dt)
    rw = jax.random.normal(ks[1], (h, scored)).astype(dt)
    stacks = [(jax.random.normal(k, shape) / d).astype(
        jnp.float32 if kind == "int8" else dt)
        for k, shape, d in ((ks[2], (held, h, f), 4), (ks[3], (held, h, f), 4),
                            (ks[4], (held, f, h), 6))]
    if kind == "int8":
        stacks = [jax.vmap(quantize_linear)(w) for w in stacks]
        plain = [np.asarray(dequantize_linear(w, jnp.float32), np.float64)
                 for w in stacks]
    else:
        plain = [np.asarray(w, np.float64) for w in stacks]
    if routing is not None and routing.bias is not None:
        routing = routing._replace(bias=jax.random.normal(ks[5], (scored,)))
    kw = dict(top_k=top_k, routing=routing,
              held=None if held == scored else (first, held))
    return x, rw, stacks, kw, plain


def _pairs_oracle(x, rw, plain, kw):
    """float64 loop over the (row, chosen expert) pairs the op's own
    router chose (the router has tests of its own), held experts only."""
    gate, up, down = plain
    first = (kw["held"] or (0, 0))[0]
    _, w, idx = router_topk(x[0], rw, kw["top_k"], kw["routing"])
    x64 = np.asarray(x[0], np.float64)
    out = np.zeros_like(x64)
    for n, (ws, es) in enumerate(zip(np.asarray(w, np.float64),
                                     np.asarray(idx) - first)):
        for wgt, e in zip(ws, es):
            if 0 <= e < gate.shape[0]:
                g = x64[n] @ gate[e]
                out[n] += wgt * ((g / (1 + np.exp(-g)) * (x64[n] @ up[e]))
                                 @ down[e])
    return out


def _over_ep(fn, ep, stacks):
    """``fn(stacks)`` with the expert axis sharded over ``ep`` devices."""
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:ep]), ("ep",))
    stacks = jax.device_put(stacks, NamedSharding(mesh, P("ep")))
    return shard_map(fn, mesh=mesh, in_specs=(P("ep"),), out_specs=P(),
                     check_vma=False)(stacks)


STEP_ROWS = 32  # the 32-slot cells' decode step: one row a slot


def _sorted_wanted(name, rows):
    """The rule, spelt out for the cases: from the threshold of the
    stacks' type on, and at a step's few rows where the pairs leave many
    of the router's experts without a row (a share of 512 or 192 scored
    experts; Mixtral's 64 pairs hit all 8)."""
    held, scored, _, _, _, kind = SORTED_CASES[name]
    least = SORTED_MIN_ROWS_INT8 if kind == "int8" else SORTED_MIN_ROWS
    return rows >= least or (rows == STEP_ROWS and held < scored)


@pytest.mark.parametrize("ep", [1, 2])
@pytest.mark.parametrize("rows", ["under", "threshold", 512, "step"])
@pytest.mark.parametrize("name", list(SORTED_CASES))
def test_sorted_form_is_the_dense_form_and_the_reference(
        name, rows, ep, kernels):
    """One rule on what a call's trace sees serves every caller: a call
    that leaves many of the router's experts without a row (a decode
    step's 32 rows x 8 over 192 or 512 scored) and a call from the
    threshold of its stacks' type on compute only the routed pairs on
    held experts, sorted by expert (pairs on experts that are not here,
    or on the other rank's under ``ep``, sort to the tail and are never
    computed); between the two a call runs every held expert over every
    row. Both are the float64 loop over the pairs, and ``count_local``
    counts the same: each row's pairs on held experts, and the held
    experts some row chose (a host count from the router's choice)."""
    held, scored, top_k, _, first, kind = SORTED_CASES[name]
    least = SORTED_MIN_ROWS_INT8 if kind == "int8" else SORTED_MIN_ROWS
    rows = {"under": least - 1, "threshold": least,
            "step": STEP_ROWS}.get(rows, rows)
    x, rw, stacks, kw, plain = _sorted_case(name, rows)
    tol = 3e-2 if x.dtype == jnp.bfloat16 else 3e-5

    def run(stacks):
        out, count = moe_swiglu(x, rw, *stacks, count_local=True,
                                ep_axis="ep" if ep > 1 else None, **kw)
        return out, jax.lax.psum(count, "ep") if ep > 1 else count

    def both():
        return run(stacks) if ep == 1 else _over_ep(run, ep, stacks)

    out, counted = both()
    assert moe.form_traced(rows) == (
        "sorted" if _sorted_wanted(name, rows) else "dense")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CAKE_PALLAS", "0")
        dense, dense_counted = both()
        assert moe.form_traced(rows) == "dense"
    want = _pairs_oracle(x, rw, plain, kw)
    scale = np.abs(want).max()
    for got in (out, dense):
        np.testing.assert_allclose(np.asarray(got[0], np.float64), want,
                                   atol=tol * scale, rtol=0)
    for a, b in zip(counted[:2], dense_counted[:2]):  # pairs, hit
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not int(dense_counted.sorted_rows) + int(dense_counted.live_rows)
    _, _, idx = router_topk(x[0], rw, top_k, kw["routing"])
    local = np.asarray(idx) - first
    local = local[(local >= 0) & (local < held)]
    assert int(counted.pairs[0]) == local.size
    assert int(counted.hit) == np.unique(local).size
    if kw["held"] is not None:  # a share: some pairs fell elsewhere
        assert 0 < local.size < rows * top_k
    if rows == STEP_ROWS and kw["held"] is not None:
        assert int(counted.hit) < held  # what the sorted form leaves unread


def test_sorted_step_where_no_row_has_a_held_choice_adds_exactly_zero(
        kernels):
    """A decode step none of whose 32 rows chose an expert held here: no
    group has a row, the kernel visits nothing, and the result is exactly
    zero (selected, not scaled: the rows were never written), with no
    pair and no expert counted."""
    x, rw, stacks, kw, _ = _sorted_case("12-of-192-grouped", STEP_ROWS)
    first, count = kw["held"]
    # the held experts' scores are the lowest of their group: never chosen
    x = jnp.abs(x)
    rw = rw.at[:, first:first + count].set(-4.0)
    _, _, idx = router_topk(x[0], rw, kw["top_k"], kw["routing"])
    idx = np.asarray(idx)
    assert not ((idx >= first) & (idx < first + count)).any()
    out, counted = moe_swiglu(x, rw, *stacks, count_local=True, **kw)
    assert moe.form_traced(STEP_ROWS) == "sorted"
    assert (np.asarray(out) == 0).all()
    assert int(counted.hit) == 0 and not np.asarray(counted.pairs).any()


def test_sorted_row_with_no_held_choice_adds_exactly_zero(kernels):
    """A row none of whose chosen experts is held here is never computed
    and adds exactly zero (the kernel leaves rows past the last held
    pair unwritten: they are selected away, not scaled), whatever lies in
    them; 37 rows x 8 pairs is no whole number of row tiles."""
    x, rw, stacks, kw, plain = _sorted_case("12-of-192-grouped", 37)
    _, _, idx = router_topk(x[0], rw, kw["top_k"], kw["routing"])
    first, count = kw["held"]
    idx = np.asarray(idx)
    away = ~((idx >= first) & (idx < first + count)).any(axis=1)
    assert 0 < away.sum() < 37
    out = np.asarray(moe_swiglu(x, rw, *stacks, **kw)[0])
    assert moe.form_traced(37) == "sorted"  # 296 pairs hit 0.79 of 192
    assert (out[away] == 0).all() and np.isfinite(out).all()
    want = _pairs_oracle(x, rw, plain, kw)
    np.testing.assert_allclose(out, want, atol=3e-5 * np.abs(want).max())


def test_sorted_every_row_on_one_expert(kernels):
    """The least balanced routing there is: every row chooses the same
    two experts, so two groups hold every pair and six hold none."""
    x, rw, stacks, kw, plain = _sorted_case("mixtral-int8", 256)
    rw = jnp.zeros_like(rw).at[:, 5].set(1.0).at[:, 2].set(0.5)
    x = jnp.abs(x)
    _, _, idx = router_topk(x[0], rw, 2)
    assert set(np.asarray(idx).ravel()) == {2, 5}
    out = moe_swiglu(x, rw, *stacks, **kw)
    assert moe.form_traced(256) == "sorted"
    want = _pairs_oracle(x, rw, plain, kw)
    np.testing.assert_allclose(np.asarray(out[0], np.float64), want,
                               atol=3e-2 * np.abs(want).max())


@pytest.mark.parametrize("case", ["boundary", "long-straddle"])
def test_sorted_form_under_a_traced_first_expert(case, kernels):
    """The sorted form where the first held expert is a traced value (an
    ``ep`` rank's), on choices made by hand: the held pairs fill exactly
    one row tile of 128 (nothing of the second tile is touched), or one
    expert's 160 rows span two tiles and share the second with the next
    expert's. Both are the dense form over the held experts and the
    float64 loop over the pairs; the rows of the live tiles are what the
    count says; a token with no held choice gets exactly zero."""
    from cake_tpu.ops.moe import _moe_sorted

    first, held, scored, k, h, f = 4, 4, 16, 4, 32, 64
    if case == "boundary":  # 32 tokens x 4 held choices = 128 pairs
        n, live = 64, 128
        idx = np.where(np.arange(n)[:, None] < 32, [[4, 5, 6, 7]],
                       [[0, 1, 2, 3]])
    else:  # expert 4: 160 rows; expert 5: 80 rows from row 160 on
        n, live = 160, 256
        idx = np.tile([[4, 12, 13, 14]], (n, 1))
        idx[::2, 1] = 5
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(ks[0], (n, h))
    w = jax.random.uniform(ks[1], (n, k), minval=0.1)
    stacks = [jax.random.normal(key, shape) / 4 for key, shape in (
        (ks[2], (held, h, f)), (ks[3], (held, h, f)), (ks[4], (held, f, h)))]
    idx = jnp.asarray(idx, jnp.int32)
    got, live_rows = jax.jit(lambda lo: _moe_sorted(
        x, w, idx, lo, *stacks, None, scored))(jnp.int32(first))
    assert int(live_rows) == live
    combine = jnp.einsum("nk,nke->ne", w, jax.nn.one_hot(idx, scored))
    dense = _moe_dense(x, combine[:, first:first + held], *stacks)
    want = np.zeros((n, h))
    x64, plain = np.asarray(x, np.float64), [np.asarray(a, np.float64)
                                             for a in stacks]
    for t in range(n):
        for wgt, e in zip(np.asarray(w[t], np.float64),
                          np.asarray(idx[t]) - first):
            if 0 <= e < held:
                g = x64[t] @ plain[0][e]
                want[t] += wgt * ((g / (1 + np.exp(-g))
                                   * (x64[t] @ plain[1][e])) @ plain[2][e])
    for out in (got, dense):
        np.testing.assert_allclose(np.asarray(out, np.float64), want,
                                   atol=3e-5 * np.abs(want).max(), rtol=0)
    if case == "boundary":
        assert (np.asarray(got)[32:] == 0).all()


@pytest.mark.parametrize("rows,top_k,int8,held,scored,form", [
    (1, 2, False, 8, 8, "gather"), (4, 2, True, 8, 8, "gather"),
    (8, 2, True, 8, 8, "dense"),  # the sparse cell's decode step: 0.88 hit
    (5, 2, True, 8, 8, "dense"),  # int8: 0.74 is over its 0.7 (0.95x)
    (8, 2, False, 8, 8, "dense"), (7, 2, False, 8, 8, "dense"),
    (6, 2, False, 8, 8, "sorted"),  # bf16: 0.7986
    # the 32-slot cells' decode step: 0.39 of 512 scored, 0.74 of 192
    (32, 8, False, 128, 512, "sorted"), (32, 8, False, 12, 192, "sorted"),
    # the rule's two sides at each router's width (the share hit is under
    # SORTED_MAX_HIT_SHARE up to 102 rows of 512 scored, 38 of 192)
    (64, 8, False, 128, 512, "sorted"), (102, 8, False, 128, 512, "sorted"),
    (103, 8, False, 128, 512, "dense"), (128, 8, False, 128, 512, "dense"),
    (38, 8, False, 12, 192, "sorted"), (39, 8, False, 12, 192, "dense"),
    (64, 8, False, 12, 192, "dense"), (256, 8, False, 12, 192, "dense"),
    (1, 8, False, 12, 192, "sorted"),  # a told share never gathers
    (1, 2, False, 4, 8, "sorted"),  # nor a rank's slice under ep
    (2, 2, True, 4, 8, "sorted"), (4, 2, True, 4, 8, "sorted"),  # 0.66
    (SORTED_MIN_ROWS_INT8 - 1, 2, True, 8, 8, "dense"),
    (SORTED_MIN_ROWS_INT8, 2, True, 8, 8, "sorted"),
    (SORTED_MIN_ROWS - 1, 8, False, 128, 512, "dense"),
    (SORTED_MIN_ROWS, 8, False, 128, 512, "sorted"),
    (2048, 2, False, 8, 8, "sorted"),
])
def test_decode_shaped_calls_keep_their_form(rows, top_k, int8, held, scored,
                                             form, kernels, monkeypatch):
    """One strategy a program, from the call's rows, ``top_k``, the
    stacks' type, the experts held and the router's width: a call whose
    pairs leave many of the scored experts without a row is sorted, one
    that hits nearly all of them runs every held expert, and without
    kernels (the CPU's default) every call takes what it took before
    there was a sorted form."""
    assert expert_form(rows, top_k, int8, held, scored) == form
    monkeypatch.setenv("CAKE_PALLAS", "0")
    assert expert_form(rows, top_k, int8, held, scored) == (
        "dense" if form == "sorted" else form)


# name -> (held, scored, top_k, first held): every scored expert here (XLA
# gathers and sums, :func:`cake_tpu.ops.moe.compacts` False) or a share of
# them (the live tiles' kernels do)
VALID_SHAPES = {"all-held": (8, 8, 2, 0), "compact": (4, 16, 4, 4)}


@pytest.mark.parametrize("launch", ["one-row", "two-rows"])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("shape", list(VALID_SHAPES))
def test_a_buckets_padding_is_nothing_of_the_sorted_forms(
        shape, kind, launch, kernels):
    """``valid [B]``: a token at or past its row's true length counts as a
    pair on an absent expert does. Every true row's result is what it is
    without ``valid``, bit for bit; every padding row's is exactly zero,
    and finite where the padding rows of ``x`` are NaN (their routing
    weights are NaN then, and what the all-held form reads for their
    pairs was never written: selected away, not multiplied); the live row
    tiles hold the true pairs on held experts alone; and a bucket that is
    all true (``valid == T``) is the call without ``valid``."""
    from cake_tpu.ops.quant import quantize_linear

    held, scored, top_k, first = VALID_SHAPES[shape]
    b, t, valid = {"one-row": (1, 512, [300]),
                   "two-rows": (2, 256, [70, 201])}[launch]
    h, f = 32, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(ks[0], (b, t, h)).astype(jnp.bfloat16)
    rw = jax.random.normal(ks[1], (h, scored)).astype(jnp.bfloat16)
    stacks = [jax.random.normal(k, shp) / d for k, shp, d in (
        (ks[2], (held, h, f), 4), (ks[3], (held, h, f), 4),
        (ks[4], (held, f, h), 6))]
    stacks = [jax.vmap(quantize_linear)(w) if kind == "int8"
              else w.astype(jnp.bfloat16) for w in stacks]
    kw = dict(top_k=top_k, count_local=True,
              held=None if held == scored else (first, held))

    def run(x, valid):
        out, count = moe_swiglu(
            x, rw, *stacks, **kw,
            valid=None if valid is None else jnp.asarray(valid, jnp.int32))
        assert moe.form_traced(b * t) == "sorted"
        return np.asarray(out, np.float32), int(count.live_rows)

    whole, whole_live = run(x, None)
    got, live = run(x, valid)
    true = np.arange(t)[None] < np.asarray(valid)[:, None]  # [B, T]
    np.testing.assert_array_equal(got[true], whole[true])
    assert (got[~true] == 0).all()
    _, _, idx = router_topk(x.reshape(b * t, h), rw, top_k)
    local = np.asarray(idx).reshape(b, t, top_k) - first
    on_held = (local >= 0) & (local < held)
    assert whole_live == -(-on_held.sum() // 128) * 128
    assert live == -(-on_held[true].sum() // 128) * 128 < whole_live
    # NaN where nobody reads
    poisoned, poisoned_live = run(
        jnp.where(jnp.asarray(true)[..., None], x, jnp.nan), valid)
    assert poisoned_live == live
    assert (poisoned[~true] == 0).all()
    if not moe.compacts(held, scored):
        # (the kernel that gathers the live tiles' rows picks them by a
        # one-hot product over all of ``x``: 0 x NaN)
        np.testing.assert_array_equal(poisoned[true], whole[true])
    # a bucket with no padding: the call it was
    full, full_live = run(x, [t] * b)
    np.testing.assert_array_equal(full, whole)
    assert full_live == whole_live


# the compacting cells' widths (``tools/moe_sweep.py`` ``SHAPES``): hidden
GATHER_WIDTHS = {"axk1-ep16": 7168, "ling3flash-ep4": 2560,
                 "kexaone-ep8": 6144, "qwen3next-ep4": 2048,
                 "glm5-ep16": 6144}


@pytest.mark.parametrize("name,rows,form", [
    # a decode step's rows and the 64- to 2048-row buckets: the one-hot
    # product, at every compacting cell's width
    *[(name, rows, "onehot") for name in GATHER_WIDTHS
      for rows in (16, 32, 64, 512, 2048)],
    # the rule's two sides where a row is whole tiles of words
    ("qwen3next-ep4", moe.GATHER_FETCH_MIN_ROWS - 64, "onehot"),
    ("qwen3next-ep4", moe.GATHER_FETCH_MIN_ROWS, "fetch"),
    ("qwen3next-ep4", 8192, "fetch"), ("glm5-ep16", 8192, "fetch"),
    ("glm5-ep16", 16384, "fetch"), ("kexaone-ep8", 8192, "fetch"),
    # 28 and 10 rows of words a token: no whole number of tiles
    ("axk1-ep16", 8192, "onehot"), ("ling3flash-ep4", 16384, "onehot"),
])
def test_gather_form_by_the_rows_and_the_width(name, rows, form):
    """ONE function of what a trace sees picks how a compacting sorted
    call gathers its live rows: by a one-hot product under
    ``GATHER_FETCH_MIN_ROWS`` rows (every step, and every bucket of
    ``axk1-ep16-cut``, Ling and ``kexaone-ep8-cut``), by address from
    there on (``qwen3next-ep4-cut``'s 4096- and 8192-row buckets,
    ``glm5-ep16-cut``'s to 16,384) where a row is a whole number of the
    chip's tiles, and no model's name enters it."""
    from cake_tpu.tools.moe_sweep import SHAPES

    hidden = GATHER_WIDTHS[name]
    assert SHAPES[name][3] == hidden and SHAPES[name][0] < SHAPES[name][1]
    assert moe.gather_form(rows, hidden, jnp.bfloat16) == form
    assert 2048 < moe.GATHER_FETCH_MIN_ROWS <= 8192


@pytest.mark.parametrize("fill", [1.0, 0.67])
def test_sorted_form_by_fetch_is_the_one_hot_forms_bit_for_bit(
        fill, kernels, monkeypatch):
    """The expert block over rows gathered by address is the block over
    rows picked by the one-hot product, bit for bit: a gather is exact
    either way, and nothing else of the call differs. A bucket told that
    0.67 of its rows are true included, the live rows counted alike; and
    where its padding rows are NaN the fetch never reads them (the
    one-hot product multiplies them by zero, into NaN); the trace records
    which bucket fetched (``fetch_traced``, ``moe.gather_fetch_min_rows``)."""
    from cake_tpu.obs import metrics as obs_metrics

    held, scored, top_k, first, rows, h, f = 4, 16, 4, 4, 512, 2048, 128
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    x = jax.random.normal(ks[0], (1, rows, h)).astype(jnp.bfloat16)
    true = round(rows * fill)
    rw = jax.random.normal(ks[1], (h, scored)).astype(jnp.bfloat16) / 16
    stacks = [(jax.random.normal(k, shp) / d).astype(jnp.bfloat16)
              for k, shp, d in ((ks[2], (held, h, f), 32),
                                (ks[3], (held, h, f), 32),
                                (ks[4], (held, f, h), 8))]

    def run(x=x):
        out, count = moe_swiglu(
            x, rw, *stacks, top_k=top_k, held=(first, held),
            count_local=True, valid=jnp.asarray([true], jnp.int32))
        assert moe.form_traced(rows) == "sorted"
        return np.asarray(out, np.float32), int(count.live_rows)

    monkeypatch.setattr(moe, "_fetched", set())
    gauge = obs_metrics.gauge("moe.gather_fetch_min_rows")
    gauge.set(0)
    picked, picked_live = run()
    assert not moe.fetch_traced(rows) and not gauge.value
    monkeypatch.setattr(moe, "GATHER_FETCH_MIN_ROWS", rows)
    fetched, fetched_live = run()
    assert moe.fetch_traced(rows) and gauge.value == rows
    np.testing.assert_array_equal(fetched, picked)
    assert fetched_live == picked_live > 0
    assert (fetched[0, true:] == 0).all() and np.abs(fetched).max() > 0
    poisoned, _ = run(x.at[:, true:].set(jnp.nan))
    np.testing.assert_array_equal(poisoned, fetched)
