"""Mixture-of-Experts SwiGLU with expert parallelism.

The reference has no MoE at all (SURVEY.md §2 "expert parallelism (no MoE)"
under *Not present*) — this is a capability extension that completes the
mesh's parallelism alphabet (dp / stage / sp / tp / **ep**) and serves the
Mixtral model family (HF ``model_type: "mixtral"``: 8 experts, top-2
routing, softmax over the selected gate logits).

TPU-first design:

- **Static shapes only.** Routing never gathers a data-dependent *number* of
  tokens. Three fixed-shape forms of one algorithm, ONE a program, picked
  at trace time from the call's rows, ``top_k``, the router's width and
  the stacks' type (:func:`expert_form`; which is better depends on how
  much of the stacks a call touches and how many rows share a weight
  read):

  * ``gather`` — a handful of pairs (tiny N, every expert here): gather
    the top-k experts' weight rows with ``jnp.take`` (static output shape
    ``[N, k, H, F]``) and run only those. At N=1/k=2 this reads 2 experts'
    bytes instead of E — the decode path is weights-bandwidth-bound, so
    the gather is the difference between top-k and all-E HBM traffic per
    token.
  * ``dense`` — a batch whose pairs hit nearly every expert (8 rows x
    top-2 of 8: 0.88 of them): every (local) expert runs over every token
    via batched einsums (``[E, N, F]`` activations) and the per-token
    combine weights zero out the non-selected experts. Each matrix is read
    once and at a few rows an expert the arithmetic hides under the read.
    At prompt rows it does not: E/top_k x the routed arithmetic (4x at
    top-2 of 8, 64x at 128 held of 512), more than the experts' own bytes
    from ~240 rows on in bf16 and ~120 in int8; and over a scanned int8
    stack the chip's compiler writes each layer's stack out for the
    dequantised batched product (6.7 ms a layer at Mixtral's widths, my
    chip run, PR 33).
  * ``sorted`` — a call that leaves many experts without a row (a decode
    step's 32 rows x top-8 of 512 scored hit 0.39 of them, of 192 scored
    0.74: :func:`hit_share`) and prompt rows (an admission's bucket, a
    verify chunk, a prefill): the ``N x k`` (row, chosen expert) pairs
    sorted by local expert id, pairs on experts that are not here at the
    tail and with them the pairs of a bucket's padding (``valid``: the
    rows' true lengths, where the caller knows them); from there on only
    the LIVE row tiles (those that hold a true pair on a held expert) are
    read or written: their rows gathered (:func:`gather_form`: picked by
    a one-hot product, or fetched by address out of a long bucket); gate,
    up and the SwiGLU one grouped call and down another over the
    contiguous groups
    (:func:`cake_tpu.ops.pallas.grouped_swiglu`,
    :func:`~cake_tpu.ops.pallas.grouped_matmul`: a group without a row is
    never visited, so its matrices are never read; an int8 stack streams
    as int8 and is converted a block at a time); every row's results
    summed under its routing weights in float32 over the live tiles' rows
    (:func:`compacts`: by kernels where a share of the scored experts is
    held, by XLA where all are and every tile but padding's is live).
    Exact with no capacity, no fallback and no control flow. Its kernels
    read a layer's matrices out of the WHOLE stacks the layer loop closes
    over (``layer=``), so no layer's slice is written out for them
    (:func:`reads_whole_stacks`).

- **Expert parallelism** shards the expert axis over the mesh's ``ep`` axis
  (:mod:`cake_tpu.parallel.mesh`): each rank holds ``E/ep`` experts' weights,
  computes the dense or sorted form restricted to its local experts (tokens are
  replicated over ep — at inference scale activations are tiny next to
  expert weights), and the combine is a single ``psum`` over ``ep``. This
  composes with tensor parallelism: the expert intermediate axis shards over
  ``tp`` exactly like the dense MLP, and the down-projection partial sums
  reduce over ``(ep, tp)`` in one fused psum.

- **A router wider than the experts** (``zero_experts``: the router's last
  outputs are zero-compute experts, each of which returns its input). A
  pair on such an output is, to the sorted form, a pair on an expert that
  is not here: the tail of the sort, no tile, no read. To the dense form it
  is a zero weight (the combine's columns stop at the experts). The gather
  form never indexes a stack by such an id (the id is clamped and its
  weight zeroed). :func:`expert_form` and :func:`hit_share` reckon with
  every output the router scores. The identity part ``z(h) h`` (``z`` the
  sum of the chosen zero outputs' weights) is every rank's alike and is
  added ONCE, after the ``psum``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops import pallas as pk
from cake_tpu.ops.quant import QuantizedLinear, dequantize_linear

# Decode/prefill strategy crossover: gather materializes [N*k, H, F] weight
# rows, so it only pays off while N*k is well under E (single-digit serving
# batches at decode). Above it the dense path's E-batched einsum wins.
GATHER_MAX_ROWS = 8
# Rows of a call from which the sorted form is taken, by the stacks' type:
# where tools/moe_sweep.py measured it at 1.10x the dense form or better at
# EVERY cell's shape (my chip runs, PR 33 and PR 35, and PR 56's over the
# live-tile form at all seven shapes, which moved neither constant;
# PERF.md section 6 keeps the table; at these rows the live tiles' gather
# is the one-hot product, which PR 63's fetch by address replaces from
# GATHER_FETCH_MIN_ROWS rows on: its sweep left the points here where
# they were). int8: 2.99x from 128 rows on (the
# dense form's dequantised product writes a layer's stack out first) and
# 0.71x at 64, where that form has no slab and runs at 89% of the bytes'
# roofline. bf16 at 512 rows: 1.44x (64 held of 64, 2304 wide) to 1.98x
# (128 of 512), 1.75x at 12 held of 192; at 256 rows 0.97x-1.14x (1.06x
# and 1.14x at those two): under the bar at five shapes of six.
SORTED_MIN_ROWS_INT8 = 128
SORTED_MIN_ROWS = 512
# ... and the share of the experts hit (:func:`hit_share`) up to which a
# call of fewer rows takes it all the same: it reads the hit experts'
# matrices alone, at 1.04-1.23x their bytes' time in bf16 and ~1.4x in int8
# (a block's conversion), where the dense form reads every held one. The
# same sweeps, 8-256 rows. bf16 (PR 56's table, 32-128 rows): 2.10x at a
# share of 0.39 (32 rows x 8 of 512 scored), 1.47x at 0.63, 1.24x at 0.74
# (32 x 8 of 192), 1.06-1.10x and 1.08x at 0.87 and 0.86 (32 x 8 of 128;
# 128 x 8 of 512), 0.96x at 0.93. int8 (2-8 rows x 2 of 8, PR 35): 1.51x
# at 0.41, 1.29x at 0.66, 0.95x at 0.74, 0.82x at 0.88.
SORTED_MAX_HIT_SHARE_INT8 = 0.7
SORTED_MAX_HIT_SHARE = 0.8
# Rows of a compacting sorted call from which its live tiles' rows are
# fetched by address and not picked by a one-hot product
# (:func:`gather_form`): where tools/moe_sweep.py --forms onehot,fetch
# measured the block 1.10x the one-hot form's or better at every shape
# whose row is whole tiles of words (my chip runs, PR 63; us a layer,
# one-hot -> fetch; PERF.md section 6 keeps the table). qwen3next-ep4
# (H 2048): 512 rows 1315 -> 1325, 1024 1526 -> 1500, 2048 2111 -> 1964
# (1.075x), 4096 3723 -> 2951 (1.26x), 8192 9102 -> 5586 (1.63x); glm5-ep16
# (H 6144): 2048 2955 -> 2834 (1.04x), 4096 4514 -> 3996 (1.13x), 8192
# 8552 -> 6422, 16,384 22,269 -> 13,479 (1.65x); kexaone-ep8 (H 6144): 512
# 2089 -> 2093, 2048 3562 -> 3353 (1.06x). Under the bar at 2048 rows at
# all three, over it from 4096 on.
GATHER_FETCH_MIN_ROWS = 4096

# rows of a call -> the form its trace took (what the engine's admission
# counters ask: the form is a function of the shapes, so one entry a shape)
_traced: dict[int, str] = {}
# ... and the rows of the sorted calls whose gather was traced as a fetch
_fetched: set[int] = set()


class ExpertCount(NamedTuple):
    """What ``count_local`` counts of a call (summed over expert layers
    and steps by the callers): ``pairs [B]`` each batch row's (token,
    chosen expert) pairs that fell on the experts held here; ``hit []``
    the distinct held experts that some row chose (what the sorted form
    reads of the stacks); ``sorted_rows []`` the pair rows (``rows x
    top_k``, a bucket's padding included) of a call that took the sorted
    form, and ``live_rows []`` those of them that lie in a row tile the
    call touched (the sorted form moves the tiles that hold a true
    token's pair on a held expert and no others: ``ceil(such pairs / row
    tile)`` tiles); both 0 of a call in another form. ``zero [B]`` each
    batch row's pairs that fell on zero-compute outputs of the router,
    or None (no leaf: the programs of a model whose router scores experts
    alone return what they returned before there was such a count)."""

    pairs: jax.Array
    hit: jax.Array
    sorted_rows: jax.Array
    live_rows: jax.Array
    zero: jax.Array | None = None

    @classmethod
    def zeros(cls, batch: int, zero: bool = False) -> "ExpertCount":
        """``zero``: the router scores zero-compute outputs too."""
        nothing = jnp.zeros((), jnp.int32)
        rows = jnp.zeros((batch,), jnp.int32)
        return cls(rows, nothing, nothing, nothing, rows if zero else None)

    def __add__(self, other: "ExpertCount") -> "ExpertCount":
        # field by field (a tuple's own ``+`` would concatenate): what the
        # layer loop and the step loop carry and add up
        return ExpertCount(*(None if a is None else a + b
                             for a, b in zip(self, other)))


class GroupRouting(NamedTuple):
    """DeepSeek-V3's routing (config keys ``scoring_func: "sigmoid"``,
    ``n_group``, ``topk_group``, ``norm_topk_prob``,
    ``routed_scaling_factor``): sigmoid scores over all experts; a
    group's score is the sum of its 2 highest; the ``topk_group`` best
    groups stay; top-k of the scores inside them; weights are those
    scores, normalised over the chosen (``+ norm_eps``) and scaled.
    ``bias [E]`` (``topk_method: "noaux_tc"``): a per-expert correction
    that enters the CHOICE (groups and top-k are taken on ``score +
    bias``) and not the weights (the chosen experts' own scores).
    ``scoring`` "softmax" (LongCat-Flash's routing): the scores are the
    softmax shares over ALL the router's outputs in place of sigmoids,
    everything else as above (that model: one group, a bias, the chosen
    shares not renormalised, a scale of 6)."""

    n_group: int = 1
    topk_group: int = 1
    norm_topk: bool = True
    scale: float = 1.0
    bias: jax.Array | None = None
    norm_eps: float = 1e-20
    scoring: str = "sigmoid"


def _deq(w, dt):
    """Trace-level dequant of an int8 expert stack ``[E, in, out]``
    (scale ``[E, out]``): XLA fuses the convert+mul into the downstream
    einsum's operand read, so HBM streams the int8 bytes — the same
    contract as the int8 KV cache's XLA path (ops/attention.py)."""
    if isinstance(w, QuantizedLinear):
        return dequantize_linear(w, dt)
    return w


def _take(w, flat):
    """Expert-row gather that works for plain and int8 stacks (gathering
    q and scale separately keeps the gathered bytes int8-sized)."""
    if isinstance(w, QuantizedLinear):
        return QuantizedLinear(q=jnp.take(w.q, flat, axis=0),
                               scale=jnp.take(w.scale, flat, axis=0))
    return jnp.take(w, flat, axis=0)


def router_topk(
    x2d: jax.Array,  # [N, H]
    router_w: jax.Array,  # [H, E] (global expert count)
    top_k: int,
    routing: GroupRouting | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing in f32. ``routing`` None is Mixtral's convention:
    top-k of the logits, softmax over the *selected* logits. A
    :class:`GroupRouting` is DeepSeek-V3's sigmoid, group-limited choice,
    or (``scoring`` "softmax") the same choice on softmax shares over all
    the outputs.
    Ties go to the lower index (``lax.top_k``). Returns ``(combine [N, E]
    f32, weights [N, k] f32, idx [N, k] int32)`` where ``combine`` is zero
    off the top-k."""
    logits = jnp.einsum(
        "nh,he->ne", x2d, router_w, preferred_element_type=jnp.float32
    )
    if routing is None:
        vals, idx = jax.lax.top_k(logits, top_k)  # [N, k]
        w = jax.nn.softmax(vals, axis=-1)
    else:
        n, e = logits.shape
        scores = (jax.nn.softmax(logits, axis=-1)
                  if routing.scoring == "softmax"
                  else jax.nn.sigmoid(logits))
        choice = scores
        if routing.bias is not None:
            choice = scores + routing.bias.astype(jnp.float32)
        if routing.n_group > 1:
            grouped = choice.reshape(n, routing.n_group, -1)
            group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)  # [N, G]
            _, kept = jax.lax.top_k(group_score, routing.topk_group)
            keep = jax.nn.one_hot(kept, routing.n_group,
                                  dtype=jnp.bool_).any(axis=1)  # [N, G]
            # below every score (and every corrected one): an expert
            # outside the kept groups is never chosen
            choice = jnp.where(keep[..., None], grouped,
                               -jnp.inf if routing.bias is not None
                               else -1.0).reshape(n, e)
        _, idx = jax.lax.top_k(choice, top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if routing.norm_topk and top_k > 1:
            w = w / (w.sum(-1, keepdims=True) + routing.norm_eps)
        w = w * routing.scale
    onehot = jax.nn.one_hot(idx, logits.shape[-1], dtype=w.dtype)  # [N,k,E]
    combine = jnp.einsum("nk,nke->ne", w, onehot)
    return combine, w, idx


def _moe_dense(
    x2d: jax.Array,  # [N, H]
    combine: jax.Array,  # [N, E_local] f32 combine weights (zeros off top-k)
    w_gate,  # [E_local, H, F] array or int8 QuantizedLinear
    w_up,
    w_down,  # [E_local, F, H]
) -> jax.Array:
    dt = x2d.dtype
    g = jnp.einsum("nh,ehf->enf", x2d, _deq(w_gate, dt))
    u = jnp.einsum("nh,ehf->enf", x2d, _deq(w_up, dt))
    y = jnp.einsum("enf,efh->enh", jax.nn.silu(g) * u, _deq(w_down, dt))
    return jnp.einsum("ne,enh->nh", combine.astype(y.dtype), y)


def _moe_gather(
    x2d: jax.Array,  # [N, H]
    w_topk: jax.Array,  # [N, k] f32
    idx: jax.Array,  # [N, k] int32 (global expert ids)
    w_gate,  # [E, H, F] array or int8 QuantizedLinear
    w_up,
    w_down,  # [E, F, H]
    clamp: bool = False,  # some ids name zero-compute outputs, no expert
) -> jax.Array:
    n, k = idx.shape
    dt = x2d.dtype
    if clamp:
        # a pair on a zero-compute output names no row of the stacks: its
        # id is clamped to the last expert's and its weight is zero
        experts = _stack(w_gate).shape[-3]
        w_topk = jnp.where(idx < experts, w_topk, 0.0)
        idx = jnp.minimum(idx, experts - 1)
    flat = idx.reshape(-1)
    gg = _deq(_take(w_gate, flat), dt)  # [N*k, H, F]
    gu = _deq(_take(w_up, flat), dt)
    gd = _deq(_take(w_down, flat), dt)  # [N*k, F, H]
    xr = jnp.repeat(x2d, k, axis=0)  # [N*k, H]
    g = jnp.einsum("nh,nhf->nf", xr, gg)
    u = jnp.einsum("nh,nhf->nf", xr, gu)
    y = jnp.einsum("nf,nfh->nh", jax.nn.silu(g) * u, gd)  # [N*k, H]
    y = y.reshape(n, k, -1)
    return jnp.einsum("nk,nkh->nh", w_topk.astype(y.dtype), y)


def _stack(w):
    """The array that carries an expert stack's shape."""
    return w.q if isinstance(w, QuantizedLinear) else w


def _moe_sorted(
    x2d: jax.Array,  # [N, H]
    w_topk: jax.Array,  # [N, k] f32
    idx: jax.Array,  # [N, k] int32 (global expert ids)
    lo,  # the first global expert held here (an int, or traced under ep)
    w_gate,  # [E_local, H, F] or, with ``layer``, the whole [L, E_local, ..]
    w_up,
    w_down,
    layer,
    scored: int,  # the experts the router chose among
    true: jax.Array | None = None,  # [N] bool: the row is no padding
) -> tuple[jax.Array, jax.Array]:
    """Only the (row, chosen expert) pairs that fall on experts held here:
    the ``N x k`` pairs sorted by local expert (a pair on an expert that
    is not here sorts to the tail, and so does a pair of a bucket's
    padding: a row that ``true`` does not name), and from there on only
    the LIVE row tiles, those that hold a true pair on a held expert, are
    read or written: their rows gathered (picked out of the bucket by a
    one-hot product at a step's rows and a short bucket's, fetched by
    address from a long bucket on, where the product would cost more than
    the experts' own: :func:`gather_form`), gate, up and the SwiGLU one
    grouped call, down another, and every row's results summed under its
    routing weights in float32 over the live tiles' rows. Where every
    scored expert is held every tile is live but padding's, and the rows
    are gathered and summed by XLA (:func:`compacts`). Exact whatever the
    routing: no capacity, no fallback and no control flow. A padding
    row's result is exactly zero whatever lies in the tiles no kernel
    wrote. Returns the block's result and the rows of the live tiles
    (int32 ``[]``: what of ``N x k`` was touched)."""
    n, k = idx.shape
    e_local = _stack(w_gate).shape[-3]
    tm = pk.MOE_ROW_TILE
    local = idx - lo
    held = (local >= 0) & (local < e_local)  # [N, k]
    if true is not None:
        held &= true[:, None]
    key = jnp.where(held, local, e_local).reshape(-1)
    m = -(-n * k // tm) * tm
    pad = (0, m - n * k)
    key = jnp.pad(key, pad, constant_values=e_local)
    sizes = jnp.sum(key[:, None] == jnp.arange(e_local, dtype=key.dtype),
                    axis=0, dtype=jnp.int32)
    tiles = pk.group_tiles(sizes, m, tm)
    compact = compacts(e_local, scored)
    # ONE sort carries a pair's place and, for the kernel that sums, its
    # routing weight along (a gather of 4096 scalars costs the chip more
    # than the sort does): sorted place -> pair, and each sorted row's
    # weight ([M] each: the pairs' own width, as the router's); a padding
    # pair names the last token
    carried = (jnp.pad(w_topk.reshape(-1), pad),) if compact else ()
    _, order, *weight = jax.lax.sort(
        (key, jnp.arange(m, dtype=jnp.int32), *carried), num_keys=1,
        is_stable=True)
    token = jnp.minimum(order, n * k - 1) // k

    def split(w):
        return (w.q, w.scale) if isinstance(w, QuantizedLinear) else (w, None)

    (gate, gate_scale), (up, up_scale) = split(w_gate), split(w_up)
    down, down_scale = split(w_down)
    if compact:  # [M, H], the live tiles': by address from a long bucket on
        fetch = gather_form(n, x2d.shape[1], x2d.dtype) == "fetch"
        if fetch:
            _fetched.add(n)
            gauge = obs_metrics.gauge("moe.gather_fetch_min_rows")
            gauge.set(min(n, gauge.value or n))
        xs = pk.gather_rows(x2d, token, tiles, fetch=fetch, tm=tm)
    else:
        xs = jnp.take(x2d, token, axis=0)
    act = pk.grouped_swiglu(xs, gate, up, tiles, layer=layer,
                            gate_scale=gate_scale, up_scale=up_scale, tm=tm)
    y = pk.grouped_matmul(act, down, tiles, layer=layer, scale=down_scale,
                          tm=tm, out_dtype=jnp.float32)  # [M, H]
    if compact:
        out = pk.combine_rows(y, token, weight[0], tiles, n,
                              out_dtype=x2d.dtype, tm=tm)
    else:
        place = jnp.argsort(order)[: n * k]  # pair -> sorted place
        y = jnp.take(y, place, axis=0).reshape(n, k, -1)
        if true is not None:
            # a padding pair's place is past the groups' rows, which no
            # kernel wrote: selected away, not multiplied (0 x NaN)
            y = jnp.where(true[:, None, None], y, 0.0)
            w_topk = jnp.where(true[:, None], w_topk, 0.0)
        out = jnp.einsum("nk,nkh->nh", w_topk, y).astype(x2d.dtype)
    return out, tiles.live[0] * tm


def compacts(held: int, scored: int) -> bool:
    """Does the sorted form gather its rows and sum its results with the
    kernels that run over the live row tiles alone? Yes where the stacks
    hold a share of the scored experts: most pairs then lie on experts
    that are elsewhere, and moving all ``N x k`` rows is moving mostly
    nothing (the gather by a one-hot product or, from a long bucket on,
    by address, :func:`gather_form`; the sum a row at a time into a
    float32 block held in VMEM). Where every scored expert is held every
    pair is live: XLA's
    gather and sum then move exactly the live rows, at the memory's rate,
    which a row at a time in a kernel does not reach
    (``tools/moe_sweep.py --forms compact``; PERF.md section 6, PR 56)."""
    return held < scored


def gather_form(rows: int, hidden: int, dtype) -> str:
    """``"fetch"`` or ``"onehot"``: how a compacting sorted call of
    ``rows`` rows gathers its live tiles' rows
    (:func:`cake_tpu.ops.pallas.gather_rows`), from what its trace sees.
    One gather, whose cheaper form depends on the rows: the one-hot
    product costs ``live rows x rows x hidden`` operations, all but free
    at a step's rows and a short bucket's and the larger part of the
    block at 8192; a fetch by address costs the live rows' alone and one
    pass over the bucket to re-lay it. A width whose row is no whole
    number of the chip's tiles (``rows_fetchable``: 7168 and 2560, whose
    cells' buckets end at 512 rows anyway) keeps the one-hot product."""
    if rows >= GATHER_FETCH_MIN_ROWS and pk.rows_fetchable(hidden, dtype):
        return "fetch"
    return "onehot"


def hit_share(rows: int, top_k: int, scored: int) -> float:
    """The share of the experts a call can be expected to hit: ``rows x
    top_k`` pairs, each on one of the ``scored`` experts the router
    chooses among (a held expert is hit as often as any other)."""
    return 1.0 - (1.0 - 1.0 / scored) ** (rows * top_k)


def expert_form(rows: int, top_k: int, quantized: bool, held: int,
                scored: int, zero: int = 0) -> str:
    """``"gather"``, ``"dense"`` or ``"sorted"``: THE strategy of a call,
    from what its trace can see: its rows, ``top_k``, the stacks' type,
    how many experts the stacks hold (``held``) and how many outputs the
    router scores (``scored``: the experts and, behind them, ``zero``
    zero-compute outputs, on which a pair is a pair on no expert here). One algorithm, whose better form depends on how
    much of the stacks a call touches and on how many rows share a weight
    read: a handful of pairs, every scored expert here, gather their
    experts' matrices; a call whose pairs leave many of the experts
    without a row (:func:`hit_share`) sorts them and reads the hit
    experts alone; a batch that hits nearly all of them runs every held
    expert over every row (each matrix is read once and the arithmetic
    hides under the read); and from a bucket of prompt rows on that
    arithmetic costs more than the read, so the pairs are sorted again and
    only they are computed. The sorted form's product is a Pallas kernel."""
    if held + zero == scored and rows * top_k <= GATHER_MAX_ROWS:
        return "gather"
    if not pk.kernels_enabled():
        return "dense"
    least = SORTED_MIN_ROWS_INT8 if quantized else SORTED_MIN_ROWS
    most = SORTED_MAX_HIT_SHARE_INT8 if quantized else SORTED_MAX_HIT_SHARE
    few = hit_share(rows, top_k, scored) <= most
    return "sorted" if few or rows >= least else "dense"


def reads_whole_stacks(rows: int, top_k: int, router, w_gate,
                       zero: int = 0) -> bool:
    """Should the layer loop hand :func:`moe_swiglu` the whole expert
    stacks and the layer's index, for a call of ``rows`` rows under this
    ``router [.., H, scored]`` (``zero`` of its outputs zero-compute)? Yes where it takes the sorted form: a
    kernel's operand that is a scan's slice is written out first (AOT for
    v5e: a slice of each of a layer's three int8 stacks, the operation
    that costs 1.4 ms a stack where the dense form pays it, my chip run,
    PR 33), the whole stack with an index is read where it lies."""
    return expert_form(rows, top_k, isinstance(w_gate, QuantizedLinear),
                       _stack(w_gate).shape[-3], router.shape[-1],
                       zero) == "sorted"


def form_traced(rows: int) -> str | None:
    """The form the expert block took when a call of ``rows`` rows was
    last traced in this process (None: no such call was)."""
    return _traced.get(rows)


def fetch_traced(rows: int) -> bool:
    """Did a sorted call of ``rows`` rows traced in this process gather
    its rows by address (:func:`gather_form`)?"""
    return rows in _fetched


def moe_swiglu(
    x: jax.Array,  # [B, T, H]
    router_w: jax.Array,  # [H, E_global]
    w_gate: jax.Array,  # [E_local, H, F]
    w_up: jax.Array,
    w_down: jax.Array,  # [E_local, F, H]
    top_k: int,
    ep_axis: str | None = None,
    ep_size: int | None = None,
    tp_axis: str | None = None,
    routing: GroupRouting | None = None,
    held: tuple[int, int] | None = None,
    count_local: bool = False,
    layer: jax.Array | None = None,
    valid: jax.Array | None = None,
    zero_experts: int = 0,
):
    """Routed SwiGLU MLP. Returns ``[B, T, H]`` (residual NOT added); with
    ``count_local`` a pair ``(out, ExpertCount)``: each batch row's number
    of (token, chosen expert) pairs that fell on the experts held here
    (int32 ``[B]``, this rank's; the caller knows which rows are live),
    and the number of held experts that some row chose.

    ``held = (first, count)``: the expert stacks are a share of what the
    router scores, told by the configuration: global experts ``first ..
    first + count - 1`` of the router's ``E_global`` (a chip's share of an
    expert-parallel deployment, served without the other chips). The layer
    routes over all ``E_global`` and returns its own experts' part of the
    result; what the absent experts would add is left out, and nothing
    stands in for them or for their exchange. None: all ``E_global`` are
    here. Under a real ``ep`` axis the same share is split once more over
    the axis, and the parts are summed by the one ``psum``.

    The router always scores the **global** expert set; under ep the weight
    arrays hold this rank's contiguous expert slice (global experts
    ``[ep_idx*E_local, (ep_idx+1)*E_local)``) and the combine is psum'd over
    ``ep_axis`` (plus ``tp_axis`` for the row-parallel down projection — one
    fused reduction when both are given). ``ep_size`` defaults to the mesh
    axis size (callers inside shard_map just pass the axis name; a size-1
    ep axis degrades to the unsharded strategies).

    ``layer``: the three stacks are the layer loop's whole ``[L, E_local,
    ..]`` stacks and this is layer ``layer`` of them (what
    :func:`reads_whole_stacks` asks for: calls that take the sorted form).

    ``valid [B]``: the true tokens of each row of a bucketed chunk (None:
    all ``T``). The sorted form counts a token at or past its row's
    ``valid`` as it counts a pair on an absent expert: nothing of its own,
    no tile visited for it, and its result exactly zero (every true row's
    is what it is without ``valid``, bit for bit). The dense and gather
    forms have no tile to skip and compute a padding row like any other.

    ``zero_experts``: the router's LAST that many outputs are zero-compute
    experts (each returns its input): ``E_global`` counts them, ``held``
    and the stacks count experts alone. The result then carries the
    identity part ``z h`` (``z`` the sum of a token's chosen zero outputs'
    weights), every rank's alike and added once, after the ``psum``
    (named scope ``moe.zero``); a padding row's is nobody's to read.
    """
    b, t, h = x.shape
    x2d = x.reshape(b * t, h)
    with jax.named_scope("moe.router"):
        combine, w_topk, idx = router_topk(x2d, router_w, top_k, routing)

    e_local = _stack(w_gate).shape[-3]
    e_global = combine.shape[1]
    real = e_global - zero_experts  # the router's outputs that are experts
    first, count = held or (0, real)
    if ep_axis is not None and ep_size is None:
        # Static ep width from the shapes already in hand: the router
        # scores the GLOBAL expert set ([H, E_global]) while the weight
        # arrays hold this rank's local slice ([E_local, ...]), so the
        # shard count is their ratio. Shape-derived rather than
        # jax.lax.axis_size so it works on jax versions without that API
        # (and it must be a Python int — it gates the strategy below).
        ep_size = count // e_local
    sharded = ep_axis is not None and ep_size > 1
    axes: tuple[str, ...] = ()
    # trace time: one strategy a program, from the shapes (no control flow
    # in the layer body: a conditional's operands are buffers, so the
    # chip's compiler writes the scanned expert stacks out before it, 24
    # ms an admission, my chip run, PR 28)
    form = expert_form(b * t, top_k, isinstance(w_gate, QuantizedLinear),
                       e_local, e_global, zero_experts)
    assert layer is None or form == "sorted", (form, b * t)
    _traced[b * t] = form
    if form == "sorted":
        gauge = obs_metrics.gauge("moe.sorted_from_rows")
        gauge.set(min(b * t, gauge.value or b * t))
    with jax.named_scope("moe.experts"):
        lo = first
        if sharded or count != e_global:
            # the stacks hold a slice of the experts the router scored
            if sharded:
                lo = first + jax.lax.axis_index(ep_axis) * e_local
                axes += (ep_axis,)
            combine = jax.lax.dynamic_slice_in_dim(combine, lo, e_local, 1)
        live_rows = jnp.zeros((), jnp.int32)
        if form == "sorted":
            true = None if valid is None else (
                jnp.arange(t, dtype=jnp.int32) < valid[:, None]).reshape(-1)
            out, live_rows = _moe_sorted(x2d, w_topk, idx, lo, w_gate, w_up,
                                         w_down, layer, e_global, true)
        elif form == "gather":
            out = _moe_gather(x2d, w_topk, idx, w_gate, w_up, w_down,
                              clamp=zero_experts > 0)
        else:  # every held expert over every row
            out = _moe_dense(x2d, combine, w_gate, w_up, w_down)
    if tp_axis is not None:
        axes += (tp_axis,)
    if axes:
        out = jax.lax.psum(out, axes)
    if zero_experts:
        on_zero = idx >= real  # [N, k]: pairs on zero-compute outputs
        with jax.named_scope("moe.zero"):
            z = jnp.sum(jnp.where(on_zero, w_topk, 0.0), axis=1,
                        keepdims=True)
            out = (out.astype(jnp.float32)
                   + z * x2d.astype(jnp.float32)).astype(x2d.dtype)
    out = out.reshape(b, t, h)
    if count_local:
        chosen = combine > 0  # [N, E_local]
        pairs = jnp.sum(chosen, axis=1, dtype=jnp.int32)
        zero_pairs = None
        if zero_experts:
            zero_pairs = jnp.sum(on_zero, axis=1, dtype=jnp.int32)
            if sharded:  # every rank's alike: counted by the first (the
                # callers sum the counts over ep)
                zero_pairs = jnp.where(jax.lax.axis_index(ep_axis) == 0,
                                       zero_pairs, 0)
            zero_pairs = zero_pairs.reshape(b, t).sum(axis=1)
        return out, ExpertCount(
            pairs.reshape(b, t).sum(axis=1),
            jnp.sum(chosen.any(axis=0), dtype=jnp.int32),
            jnp.int32(b * t * top_k if form == "sorted" else 0), live_rows,
            zero_pairs)
    return out
