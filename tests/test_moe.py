"""MoE routing/compute unit tests (no torch oracle needed — f64 numpy loop
is the reference math; HF golden parity lives in test_families.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.ops import moe
from cake_tpu.ops.moe import (
    GATHER_MAX_ROWS,
    SORTED_MIN_ROWS,
    SORTED_MIN_ROWS_INT8,
    GroupRouting,
    _moe_dense,
    _moe_gather,
    compacts,
    expert_form,
    moe_swiglu,
    router_topk,
)


def _fixtures(n=3, h=16, f=32, e=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, h))
    rw = jax.random.normal(ks[1], (h, e))
    wg = jax.random.normal(ks[2], (e, h, f)) / 4
    wu = jax.random.normal(ks[3], (e, h, f)) / 4
    wd = jax.random.normal(ks[4], (e, f, h)) / 6
    return x, rw, wg, wu, wd


def _oracle(x, rw, wg, wu, wd, k):
    """Per-token f64 loop: top-k by logit, softmax over selected, routed
    SwiGLU sum."""
    x64 = np.asarray(x, np.float64)
    logits = x64 @ np.asarray(rw, np.float64)
    out = np.zeros_like(x64)

    def silu(v):
        return v / (1 + np.exp(-v))

    for n in range(x64.shape[0]):
        top = np.argsort(-logits[n], kind="stable")[:k]
        w = np.exp(logits[n][top] - logits[n][top].max())
        w /= w.sum()
        for wgt, e in zip(w, top):
            hidden = silu(x64[n] @ np.asarray(wg[e], np.float64)) * (
                x64[n] @ np.asarray(wu[e], np.float64)
            )
            out[n] += wgt * (hidden @ np.asarray(wd[e], np.float64))
    return out


def test_router_combine_weights_normalized():
    x, rw, *_ = _fixtures()
    combine, w, idx = router_topk(x, rw, 2)
    np.testing.assert_allclose(np.asarray(combine.sum(-1)), 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    # combine's nonzeros sit exactly at the top-k indices
    nz = np.asarray(combine) > 0
    for n in range(x.shape[0]):
        assert set(np.nonzero(nz[n])[0]) == set(np.asarray(idx[n]))


def test_dense_and_gather_agree_with_oracle():
    x, rw, wg, wu, wd = _fixtures()
    combine, w, idx = router_topk(x, rw, 2)
    dense = _moe_dense(x, combine, wg, wu, wd)
    gather = _moe_gather(x, w, idx, wg, wu, wd)
    oracle = _oracle(x, rw, wg, wu, wd, 2)
    np.testing.assert_allclose(np.asarray(dense), oracle, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gather), oracle, rtol=2e-5, atol=2e-5)


def test_auto_strategy_crossover_consistency():
    """The same inputs produce the same outputs whichever side of the
    gather/dense crossover N lands on (pad the batch to push it across)."""
    x, rw, wg, wu, wd = _fixtures(n=2)
    small = moe_swiglu(x[None], rw, wg, wu, wd, 2)  # N*k=4 -> gather
    big_n = GATHER_MAX_ROWS  # N*k = 2*GATHER_MAX_ROWS -> dense
    xb = jnp.concatenate([x, jnp.zeros((big_n - 2, x.shape[1]), x.dtype)])
    big = moe_swiglu(xb[None], rw, wg, wu, wd, 2)
    np.testing.assert_allclose(np.asarray(small[0]), np.asarray(big[0, :2]),
                               rtol=2e-5, atol=2e-5)


def test_moe_swiglu_shapes_and_finite():
    x, rw, wg, wu, wd = _fixtures(n=12)  # N*k=24 -> dense path
    out = moe_swiglu(x.reshape(3, 4, -1), rw, wg, wu, wd, 2)
    assert out.shape == (3, 4, x.shape[-1])
    assert bool(jnp.isfinite(out).all())


def test_top1_routing():
    """Switch-style top-1: softmax over one logit = weight 1.0 on the
    argmax expert."""
    x, rw, wg, wu, wd = _fixtures()
    out = moe_swiglu(x[None], rw, wg, wu, wd, 1)
    oracle = _oracle(x, rw, wg, wu, wd, 1)
    np.testing.assert_allclose(np.asarray(out[0]), oracle, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("ep", [2, 4])
def test_expert_parallel_matches_single_device(ep):
    """Experts sharded over an ep mesh axis via shard_map: the psum'd
    combine must equal the unsharded op bit-for-bit in structure (same
    routing) and numerically."""
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    x, rw, wg, wu, wd = _fixtures(n=4, e=4)
    devs = jax.devices()[:ep]
    mesh = Mesh(np.array(devs), ("ep",))
    spec_w = P("ep")  # expert axis sharded
    repl = P()

    def f(x, rw, wg, wu, wd):
        return moe_swiglu(x, rw, wg, wu, wd, 2, ep_axis="ep", ep_size=ep)

    sharded = shard_map(
        f, mesh=mesh,
        in_specs=(repl, repl, spec_w, spec_w, spec_w),
        out_specs=repl,
    )
    got = sharded(x[None], rw,
                  jax.device_put(wg, NamedSharding(mesh, spec_w)),
                  jax.device_put(wu, NamedSharding(mesh, spec_w)),
                  jax.device_put(wd, NamedSharding(mesh, spec_w)))
    want = moe_swiglu(x[None], rw, wg, wu, wd, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The sorted form (prompt rows): only the routed pairs on held experts are
# computed, by a Pallas grouped matmul (interpreted here).
# ---------------------------------------------------------------------------

@pytest.fixture
def kernels(monkeypatch):
    """Off the chip the expert block stays dense unless kernels are
    forced (interpreted), as every Pallas path of the repo."""
    monkeypatch.setenv("CAKE_PALLAS", "1")


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


def _family(name):
    from cake_tpu.models.config import tiny_kda_hybrid, tiny_mla_moe
    from cake_tpu.ops.quant import quantize_params

    cfg = {"mixtral": lambda: tiny_moe(max_seq_len=512),
           "mixtral-int8": lambda: tiny_moe(max_seq_len=512),
           "latent": lambda: tiny_mla_moe(max_seq_len=512),
           # K K (M K K) x 2 M K: a repeated period's stacks lead [2, n]
           "hybrid": lambda: tiny_kda_hybrid(num_hidden_layers=10,
                                             max_seq_len=512)}[name]()
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    if name == "mixtral-int8":
        params = quantize_params(params)
    return cfg, params


@pytest.mark.parametrize("shape", ["prefill", "step"])
@pytest.mark.parametrize("name", ["mixtral", "mixtral-int8", "latent",
                                  "hybrid"])
def test_layer_loop_hands_the_sorted_form_whole_stacks(name, shape,
                                                       monkeypatch):
    """Through the layer loop of each family, a prefill of the
    threshold's rows (128 int8, 512 else) and a step of 3 rows (one token
    each: Mixtral's 6 pairs gather, the 12 pairs over 16 scored experts
    hit 0.54 of them and are sorted): where the expert block takes the
    sorted form the scan slices everything of a layer but its expert
    matrices, which stay whole beside a layer index (a repeated period's
    index runs over its repetitions too), and where it does not the scan
    slices them too: the loop and the block ask ONE rule. Logits as
    without kernels."""
    from cake_tpu.ops.kvcache import init_cache

    cfg, params = _family(name)
    if shape == "prefill":
        rows = (SORTED_MIN_ROWS_INT8 if name == "mixtral-int8"
                else SORTED_MIN_ROWS)
        batch, forms = 1, ("dense", "sorted")
    else:
        rows = batch = 3
        forms = (("gather", "gather") if name.startswith("mixtral")
                 else ("dense", "sorted"))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (batch, rows // batch),
                                0, cfg.vocab_size)

    def logits(force):
        monkeypatch.setenv("CAKE_PALLAS", force)
        moe._traced.clear()
        out, _ = jax.jit(lambda p, t: llama.forward(
            p, t, init_cache(cfg, batch=batch, max_seq=512), 0, cfg))(
            params, tokens)
        return np.asarray(out), moe.form_traced(rows)

    want, form = logits("0")
    assert form == forms[0]
    got, form = logits("1")
    assert form == forms[1]
    np.testing.assert_allclose(got, want, atol=2e-3 * np.abs(want).max())


def test_engine_counts_admitted_rows_by_form(monkeypatch):
    """Per admission dispatch the engine adds the bucket's rows to
    ``moe.admit_rows`` and, where that bucket's program took the sorted
    form when it was traced, to ``moe.admit_rows_sorted``; the gauge
    ``moe.sorted_from_rows`` holds the smallest such bucket."""
    from cake_tpu.obs import metrics
    from cake_tpu.runtime.batch_generator import BatchGenerator

    monkeypatch.setenv("CAKE_PALLAS", "1")
    cfg = tiny_moe(max_seq_len=512, eos_token_id=-1)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    reg = metrics.registry()
    rows, ordered = (reg.counter(f"moe.admit_rows{s}") for s in ("", "_sorted"))
    reg.gauge("moe.sorted_from_rows").set(0)
    before = rows.value, ordered.value
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY))
    bg.set_prompts([[3, 5, 7], [2, 4]], stream_ids=[0, 1])
    for sid, prompt in ((2, range(1, 21)),  # bucket 32: dense
                        (3, range(1, 301))):  # bucket 512: sorted
        assert bg.finish(sid - 2)
        bg.admit([t % 250 + 1 for t in prompt], stream_id=sid)
    assert rows.value - before[0] == 32 + 512
    assert ordered.value - before[1] == 512
    assert reg.gauge("moe.sorted_from_rows").value == 512
    assert all(row is None or row.id >= 0 for row in bg.step())


def test_engine_counts_the_pair_rows_the_sorted_form_touches(monkeypatch):
    """An expert model told its share (4 held of 16 scored, top-4) counts
    on the device, a sorted-form call and expert layer, the pair rows the
    call was handed (``rows x top_k``) and those of the row tiles it
    touched; the engine brings both home with the counts it already
    fetches: an admission's once its program has run, a decode step's
    with its block. A 512-row program (the bucket's, and the two-row
    ones the engine warms behind it) hands 2048 pair rows a layer to the
    sorted form, of which a quarter or so are held: 6 tiles of 16 at
    most."""
    from cake_tpu.models.config import tiny_mla_moe
    from cake_tpu.obs import metrics
    from cake_tpu.runtime.batch_generator import BatchGenerator

    monkeypatch.setenv("CAKE_PALLAS", "1")
    cfg = tiny_mla_moe(max_seq_len=512, eos_token_id=-1, n_routed_experts=4,
                       router_experts=16, first_expert=4)
    layers = sum(ffn == "moe" for _, ffn in cfg.layer_kinds)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    reg = metrics.registry()
    handed, live = (reg.counter(f"moe.sorted_pair_rows{s}")
                    for s in ("", "_live"))
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY))
    bg.set_prompts([[3, 5, 7], [2, 4]], stream_ids=[0, 1])
    bg.drain()
    before = handed.value, live.value
    assert bg.finish(1)
    bg.admit([t % 250 + 1 for t in range(1, 301)], stream_id=3)
    bg.drain()
    assert moe.form_traced(512) == "sorted"
    programs = (handed.value - before[0]) / (512 * 4 * layers)
    assert programs >= 1 and programs == int(programs)
    touched = live.value - before[1]
    assert touched % 128 == 0
    assert 128 <= touched / (programs * layers) <= 6 * 128
    # a step of 2 rows x 4 of 16 scored hits 0.4 of them: sorted, one
    # tile of 128 for its 8 pair rows where a pair is held, else none
    before = handed.value, live.value
    for _ in range(4):
        bg.step()
    bg.drain()
    steps = (handed.value - before[0]) / (2 * 4 * layers)
    assert steps >= 1 and steps == int(steps)
    assert 0 <= live.value - before[1] <= steps * layers * 128


def test_moe_sweep_rows_at_tiny_shapes(monkeypatch, kernels):
    """tools/moe_sweep.py's machinery on the CPU (interpreted kernel, no
    device time): a row per shape and row count, each form timed through
    ``moe_swiglu`` as the layer loop calls it (``compact``: the live
    tiles' gather and sum kernels where every expert is held too), the
    bytes each moves beside the weights by its shapes, and the program's
    own choices restored afterwards."""
    from cake_tpu.tools import moe_sweep

    monkeypatch.setattr(moe_sweep, "SHAPES", {
        "tiny": (4, 4, 2, 32, 128, False, None),
        "tiny-int8-share": (4, 16, 2, 32, 128, True, (4, 2))})
    out = list(moe_sweep.sweep(["tiny", "tiny-int8-share"], [16, 128],
                               ["dense", "sorted", "compact"], [128]))
    assert [(r["shape"], r["rows"]) for r in out] == [
        ("tiny", 16), ("tiny", 128), ("tiny-int8-share", 16),
        ("tiny-int8-share", 128)]
    for r in out:
        assert r["dense_us_per_layer"] > 0 and r["sorted_us_per_layer"] > 0
        assert r["compact_us_per_layer"] > 0
        # a quarter of the pairs are held: the sorted form moves less
        assert (r["sorted_moved_mb"] == r["compact_moved_mb"]) == (
            r["shape"] == "tiny-int8-share")
    assert out[3]["sorted_moved_mb"] < out[3]["dense_moved_mb"]
    assert moe.expert_form is expert_form and moe.compacts is compacts


@pytest.mark.parametrize("form,hit,want", [
    (0, 5000, 100.0),  # the dense form reads every held expert
    (1, 6144, 40.0),  # 6144 of 128 held x 6 layers x 20 steps
    (1, None, None),  # the parent's program: no such counter
    (None, 6144, None),  # nor the gauge
], ids=["dense", "sorted", "no-counter", "no-gauge"])
def test_reader_of_the_experts_a_decode_step_reads(form, hit, want):
    """``benchmark/layer_metrics/moe.decode_experts_read_share.py``, loaded
    by path as the benchmark loads it: 100 where the gauge
    ``moe.decode_sorted`` is 0; else the growth of ``moe.experts_hit``
    over held experts x expert layers x the growth of
    ``moe.decode_steps``; nothing (the line leaves the metric out, no
    error) from a program without the counter or the gauge."""
    import importlib.util
    import sys
    import types
    from pathlib import Path

    bench = Path(__file__).resolve().parent.parent / "benchmark"

    def counter(value):
        return {"type": "counter", "value": value}

    before = {"moe.decode_steps": counter(100)}
    after = {"moe.decode_steps": counter(120)}
    if hit is not None:
        before["moe.experts_hit"] = counter(1000)
        after["moe.experts_hit"] = counter(1000 + hit)
    if form is not None:
        after["moe.decode_sorted"] = {"type": "gauge", "value": form}
    arch = types.SimpleNamespace(held_experts=lambda cfg: range(128, 256),
                                 expert_layers=lambda cfg: 6)
    ctx = {"before": {"status": {"metrics": before}},
           "after": {"status": {"metrics": after}}, "arch": arch, "cfg": {}}
    path = list(sys.path)  # the readers import their helpers by bare name
    sys.path.insert(0, str(bench))
    try:
        spec = importlib.util.spec_from_file_location(
            "reader_for_tests",
            bench / "layer_metrics" / "moe.decode_experts_read_share.py")
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        got = reader.read(ctx)
    finally:
        sys.path[:] = path
        sys.modules.pop("counters", None)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_benchmark_declares_the_read_share_for_the_two_cells():
    import json
    from pathlib import Path

    bench = json.loads((Path(__file__).resolve().parent.parent
                        / "BENCHMARK.json").read_text())
    # appended by PR 35: nothing before it moved, later PRs append after
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "moe.decode_experts_read_share")
    # ... and a later configuration's cell is appended to its list (PR 40)
    cells = metric.pop("workloads")
    assert cells[:2] == ["axk1-ep16-cut.decode-full",
                         "ling3flash-ep4-cut.decode-full"]
    assert metric == {
        "name": "moe.decode_experts_read_share", "unit": "%",
        "better": "lower", "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms"}


# ---------------------------------------------------------------------------
# MoE over the mesh pipeline: the full generator surface with the expert
# axis sharded (stage x ep x tp), token-identical to the all-local stream.
# ---------------------------------------------------------------------------

from cake_tpu.models import llama  # noqa: E402
from cake_tpu.models.config import tiny_moe  # noqa: E402
from cake_tpu.ops.sampling import SamplerSettings  # noqa: E402
from cake_tpu.runtime.generator import LlamaGenerator  # noqa: E402
from cake_tpu.runtime.mesh_generator import MeshGenerator  # noqa: E402

MOE_CFG = tiny_moe(max_seq_len=64)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)


@pytest.fixture(scope="module")
def moe_params():
    return llama.init_params(MOE_CFG, jax.random.PRNGKey(5))


@pytest.mark.parametrize(
    "axes",
    [
        dict(ep=2),
        dict(ep=4),
        dict(num_stages=2, ep=2),
        dict(num_stages=2, ep=2, tp=2),
    ],
    ids=lambda a: "-".join(f"{k}{v}" for k, v in a.items()),
)
def test_moe_mesh_greedy_parity_with_local(moe_params, axes):
    settings = SamplerSettings(**GREEDY)
    ref = LlamaGenerator(MOE_CFG, moe_params, settings=settings)
    ref.set_prompt([5, 9, 2, 11])
    want = [ref.next_token(i).id for i in range(6)]

    g = MeshGenerator(MOE_CFG, moe_params, settings=settings, **axes)
    g.set_prompt([5, 9, 2, 11])
    assert [g.next_token(i).id for i in range(6)] == want


@pytest.mark.parametrize(
    "axes", [dict(num_stages=2, ep=2), dict(ep=2, tp=2)],
    ids=lambda a: "-".join(f"{k}{v}" for k, v in a.items()))
def test_sorted_form_on_the_mesh_matches_local(axes, monkeypatch):
    """A prompt of the int8 threshold's bucket through the mesh programs
    (stages x ep, ep x tp): each rank's layer loop closes over ITS slice
    of the expert stacks whole, the sorted form computes the pairs that
    fall on its experts (and, under ``tp``, its slice of their width),
    and the one ``psum`` sums the parts: the all-local dense stream."""
    from cake_tpu.ops.quant import quantize_params

    cfg = tiny_moe(max_seq_len=256)
    params = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(3)))
    prompt = [t % 250 + 1 for t in range(SORTED_MIN_ROWS_INT8 + 2)]
    settings = SamplerSettings(**GREEDY)
    ref = LlamaGenerator(cfg, params, settings=settings)
    ref.set_prompt(prompt)
    want = [ref.next_token(i).id for i in range(4)]
    assert moe.form_traced(2 * SORTED_MIN_ROWS_INT8) == "dense"

    monkeypatch.setenv("CAKE_PALLAS", "1")
    g = MeshGenerator(cfg, params, settings=settings, **axes)
    g.set_prompt(prompt)
    assert [g.next_token(i).id for i in range(4)] == want
    assert moe.form_traced(2 * SORTED_MIN_ROWS_INT8) == "sorted"


def test_ep_requires_moe_config():
    from cake_tpu.models.config import tiny
    from cake_tpu.parallel.mesh import MeshPlan

    with pytest.raises(ValueError, match="num_local_experts"):
        MeshPlan.build(tiny(), ep=2)
    with pytest.raises(ValueError, match="divisible"):
        MeshPlan.build(tiny_moe(), ep=3)


def test_moe_serving_batch_generator_parity(moe_params):
    """MoE serves multi-stream on an ep x stage mesh: every stream must
    reproduce its solo all-local run token-for-token (the BatchGenerator
    bar, test_batch_generator.py, now with routed experts under ep)."""
    from cake_tpu.runtime.batch_generator import BatchGenerator

    settings = SamplerSettings(**GREEDY)
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1, 5], [7, 7, 2]]

    solo = []
    for p in prompts:
        g = LlamaGenerator(MOE_CFG, moe_params, settings=settings)
        g.set_prompt(p)
        solo.append([g.next_token(i).id for i in range(6)])

    bg = BatchGenerator(MOE_CFG, moe_params, settings=settings,
                        num_stages=2, ep=2, block_size=2)
    bg.set_prompts(prompts)
    outs = bg.generate(6)
    assert [list(o) for o in outs] == solo


def test_moe_int8_experts_match_dequantized_oracle():
    """moe_swiglu over int8 expert stacks equals the same op over the
    explicitly dequantized arrays bit-for-bit (both strategies)."""
    from cake_tpu.ops.quant import dequantize_linear, quantize_linear

    x, rw, wg, wu, wd = _fixtures(n=2)
    qg, qu, qd = (quantize_linear(w) for w in (wg, wu, wd))
    dg, du, dd = (dequantize_linear(q, jnp.float32) for q in (qg, qu, qd))
    got_g = moe_swiglu(x[None], rw, qg, qu, qd, 2)  # gather path (N*k=4)
    want_g = moe_swiglu(x[None], rw, dg, du, dd, 2)
    np.testing.assert_array_equal(np.asarray(got_g), np.asarray(want_g))
    xb = jnp.concatenate([x, jnp.zeros((8, x.shape[1]), x.dtype)])
    got_d = moe_swiglu(xb[None], rw, qg, qu, qd, 2)  # dense path
    want_d = moe_swiglu(xb[None], rw, dg, du, dd, 2)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


def test_moe_int8_mesh_parity_with_local():
    """int8 expert stacks shard over ep (q takes the weight spec, scale
    [L, E, F] drops the in axis) and the mesh stream matches all-local."""
    from cake_tpu.ops.quant import quantize_params

    qparams = quantize_params(
        llama.init_params(MOE_CFG, jax.random.PRNGKey(5)), bits=8
    )
    settings = SamplerSettings(**GREEDY)
    ref = LlamaGenerator(MOE_CFG, qparams, settings=settings)
    ref.set_prompt([5, 9, 2, 11])
    want = [ref.next_token(i).id for i in range(6)]

    g = MeshGenerator(MOE_CFG, qparams, settings=settings, num_stages=2,
                      ep=2)
    g.set_prompt([5, 9, 2, 11])
    assert [g.next_token(i).id for i in range(6)] == want


def test_moe_int8_init_params():
    from cake_tpu.models import llama as L
    from cake_tpu.ops.quant import QuantizedLinear

    p = L.init_params_int8(MOE_CFG, jax.random.PRNGKey(0))
    assert isinstance(p["layers"]["w_gate"], QuantizedLinear)
    assert p["layers"]["w_gate"].q.ndim == 4  # [L, E, H, F]
    assert p["layers"]["router"].dtype == MOE_CFG.jax_dtype
    with pytest.raises(NotImplementedError, match="int4"):
        L.init_params_int4(MOE_CFG, jax.random.PRNGKey(0))


def test_mixtral_hbm_budget():
    """Budget arithmetic prices MoE expert stacks (x num_experts / ep) —
    the planning plane behind serving Mixtral-8x7B on a v5e-16."""
    from cake_tpu.models.config import mixtral_8x7b
    from cake_tpu.utils.memory import hbm_budget

    g = 1 << 30
    m = mixtral_8x7b(max_seq_len=4096)
    one = hbm_budget(m, quant="int8")
    sharded = hbm_budget(m, num_stages=4, ep=4, quant="int8")
    # experts dominate: 16-way expert-bytes split must shrink the total
    # close to 1/16 of the expert bytes (+ replicated embed/router floor)
    assert one["total"] / g > 40  # ~45 GB of int8 experts on one chip
    assert sharded["total"] / g < 4
    # ep shards ONLY the expert bytes: the ep=1 vs ep=4 layer-byte delta
    # must equal exactly (1 - 1/ep) of the expert bytes — a regression
    # that divided attention/norm bytes by ep would break this equality
    b = hbm_budget(m, num_stages=4, ep=1, quant="int8")
    e = m.num_local_experts
    expert_bytes = (
        m.num_hidden_layers / 4  # layers per stage
        * e
        * (3 * m.hidden_size * m.intermediate_size * 1  # int8 q bytes
           + (2 * m.intermediate_size + m.hidden_size) * 4)  # f32 scales
    )
    assert b["layers"] - sharded["layers"] == pytest.approx(
        expert_bytes * (1 - 1 / 4), rel=1e-6
    )


def test_moe_distributed_worker_parity(moe_params):
    """The cross-host master/worker runtime serves MoE layers unchanged —
    expert stacks slice by layer range like any stacked weight, and the
    TCP-shipped activations reproduce the all-local stream exactly."""
    from cake_tpu.parallel.topology import Topology
    from cake_tpu.runtime.master import DistributedGenerator, build_runners
    from cake_tpu.runtime.worker import Worker

    def loader(lo, hi):
        return jax.tree.map(lambda a: a[lo:hi], moe_params["layers"])

    w = Worker(
        "w", MOE_CFG,
        Topology.from_dict({"w": {"layers": ["model.layers.2-3"]}}),
        loader, address="127.0.0.1:0", max_seq=MOE_CFG.max_seq_len,
    )
    w.serve_in_background()
    try:
        topo = Topology.from_dict({
            "w": {"host": f"127.0.0.1:{w.port}",
                  "layers": ["model.layers.2-3"]},
        })
        settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
        runners = build_runners(MOE_CFG, topo, loader)
        head = {k: moe_params[k] for k in ("embed", "norm_f", "lm_head")}
        g = DistributedGenerator(MOE_CFG, head, runners, settings=settings)
        g.set_prompt([5, 9, 2])
        got = [g.next_token(i).id for i in range(6)]
        ref = LlamaGenerator(MOE_CFG, moe_params, settings=settings)
        ref.set_prompt([5, 9, 2])
        assert got == [ref.next_token(i).id for i in range(6)]
        g.close()
    finally:
        w.shutdown()


def test_moe_mesh_speculation_parity(moe_params):
    """Speculation over the ep mesh: the verification program (one pass
    over stage x ep) must reproduce the plain MoE stream bit for bit —
    the greedy exactness contract of speculative decoding."""
    from cake_tpu.runtime.speculative import MeshSpeculativeGenerator

    settings = SamplerSettings(**GREEDY)
    # repetitive prompt: n-gram proposals actually fire
    prompt = [5, 9, 2, 5, 9, 2, 5, 9, 2]
    ref = LlamaGenerator(MOE_CFG, moe_params, settings=settings)
    ref.set_prompt(prompt)
    want = [ref.next_token(i).id for i in range(8)]

    g = MeshSpeculativeGenerator(MOE_CFG, moe_params, settings=settings,
                                 num_stages=2, ep=2, spec_k=4)
    g.set_prompt(prompt)
    assert [g.next_token(i).id for i in range(8)] == want
    assert g.dispatches < 8  # speculation actually engaged


def test_moe_serving_int8kv_interleaved_parity(moe_params):
    """MoE x int8 KV cache x interleaved-microbatch decode (batch divides
    stages, so BatchGenerator auto-selects the GPipe-streamed schedule):
    every stream still reproduces its solo bf16-KV-free run... rather,
    its solo int8-KV oracle, token for token."""
    from cake_tpu.runtime.batch_generator import BatchGenerator

    settings = SamplerSettings(**GREEDY)
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1], [7, 7, 2], [9, 8, 7, 6]]

    solo = []
    for p in prompts:
        g = LlamaGenerator(MOE_CFG, moe_params, settings=settings,
                          kv_quant="int8")
        g.set_prompt(p)
        solo.append([g.next_token(i).id for i in range(6)])

    bg = BatchGenerator(MOE_CFG, moe_params, settings=settings,
                        num_stages=2, ep=2, block_size=2, kv_quant="int8")
    bg.set_prompts(prompts)
    assert bg._interleave  # 4 streams over 2 stages: GPipe schedule on
    outs = bg.generate(6)
    assert [list(o) for o in outs] == solo
