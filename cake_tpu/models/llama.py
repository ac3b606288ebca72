"""Llama-3 decoder in pure functional JAX.

Equivalent of the reference model stack (`cake-core/src/model/{llama,
transformer,attention,mlp}.rs`): token embedding + N pre-norm decoder blocks +
final RMSNorm + lm_head (llama.rs:61-76,79-143), with each block =
``rms_1 -> attn -> +residual -> rms_2 -> SwiGLU -> +residual``
(transformer.rs:48-64).

TPU-first design decisions:

- **Stacked layer weights + lax.scan.** Every per-layer weight is stored with
  a leading ``[num_layers, ...]`` axis and the block loop is a single
  ``lax.scan`` (llama.rs walks a ``Vec<Box<dyn Forwarder>>`` in Python-style
  loop, llama.rs:88-119). Scan compiles the block body once for 32/80 layers,
  and the layer axis is exactly the axis a pipeline stage shards over.
- **Functional params pytree**, no framework modules: params flow through
  `jit`/`shard_map` and shard with `NamedSharding` without indirection.
- **Static shapes everywhere**: the KV cache is preallocated
  (:mod:`cake_tpu.ops.kvcache`), decode and prefill are two jit signatures.
- `forward_layers` runs an arbitrary contiguous slice of blocks — the same
  entry point serves the single-chip model, a pipeline stage, and a remote
  worker executing its topology-assigned range (worker.rs:85-98).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from cake_tpu.models.config import LlamaConfig
from cake_tpu.ops import hyper, quant
from cake_tpu.ops.attention import (
    self_attention_block,
    window_attention_block,
)
from cake_tpu.ops.eva import eva_attention_block
from cake_tpu.ops.kda import gdn_attention_block, kda_attention_block
from cake_tpu.ops.kvcache import KVCache
from cake_tpu.ops.mamba import mamba_mixer_block
from cake_tpu.ops.mla import latent_attention_block
from cake_tpu.ops.mlp import swiglu
from cake_tpu.ops.moe import (
    ExpertCount,
    GroupRouting,
    moe_swiglu,
    reads_whole_stacks,
)
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.shortconv import conv_mixer_block

Params = dict[str, Any]

# Stacked per-layer weight names -> shape builders (L = num layers), for the
# dense-MLP, bias-free Llama base shape. :func:`layer_shapes` extends this
# per model family (Qwen2 q/k/v bias, Mixtral routed experts).
_LAYER_SHAPES = {
    "attn_norm": lambda c: (c.hidden_size,),
    "wq": lambda c: (c.hidden_size, c.num_attention_heads * c.head_dim),
    "wk": lambda c: (c.hidden_size, c.num_key_value_heads * c.head_dim),
    "wv": lambda c: (c.hidden_size, c.num_key_value_heads * c.head_dim),
    "wo": lambda c: (c.num_attention_heads * c.head_dim, c.hidden_size),
    "mlp_norm": lambda c: (c.hidden_size,),
    "w_gate": lambda c: (c.hidden_size, c.intermediate_size),
    "w_up": lambda c: (c.hidden_size, c.intermediate_size),
    "w_down": lambda c: (c.intermediate_size, c.hidden_size),
}

_BIAS_SHAPES = {
    "bq": lambda c: (c.num_attention_heads * c.head_dim,),
    "bk": lambda c: (c.num_key_value_heads * c.head_dim,),
    "bv": lambda c: (c.num_key_value_heads * c.head_dim,),
}

_MOE_SHAPES = {
    "router": lambda c: (c.hidden_size, c.num_local_experts),
    "w_gate": lambda c: (c.num_local_experts, c.hidden_size,
                         c.intermediate_size),
    "w_up": lambda c: (c.num_local_experts, c.hidden_size,
                       c.intermediate_size),
    "w_down": lambda c: (c.num_local_experts, c.intermediate_size,
                         c.hidden_size),
}


# Latent attention (ops/mla.py): the q and kv down-projections with their
# inner norms, the up-projections, and the output projection.
_LATENT_SHAPES = {
    "attn_norm": lambda c: (c.hidden_size,),
    "wq_a": lambda c: (c.hidden_size, c.q_lora_rank),
    "q_norm": lambda c: (c.q_lora_rank,),
    "wq_b": lambda c: (c.q_lora_rank, c.num_attention_heads
                       * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
    "wkv_a": lambda c: (c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim),
    "kv_norm": lambda c: (c.kv_lora_rank,),
    "wkv_b": lambda c: (c.kv_lora_rank, c.num_attention_heads
                        * (c.qk_nope_head_dim + c.v_head_dim)),
    "wo": lambda c: (c.num_attention_heads * c.v_head_dim, c.hidden_size),
    "mlp_norm": lambda c: (c.hidden_size,),
}

# A learned sparse attention's indexer beside them (ops/dsa.py): its
# queries from the query latent, one key a token behind a LayerNorm with a
# bias, a weight a head from the hidden state.
_INDEXER_SHAPES = {
    "idx_wq_b": lambda c: (c.q_lora_rank, c.index_n_heads * c.index_head_dim),
    "idx_wk": lambda c: (c.hidden_size, c.index_head_dim),
    "idx_k_norm": lambda c: (c.index_head_dim,),
    "idx_k_bias": lambda c: (c.index_head_dim,),
    "idx_w": lambda c: (c.hidden_size, c.index_n_heads),
}

# An expert layer of the latent family: the router at its published width,
# the HELD experts' stacks, and the shared experts as one SwiGLU.
_SHARED_MOE_SHAPES = {
    "router": lambda c: (c.hidden_size, c.router_outputs),
    "w_gate": lambda c: (c.n_routed_experts, c.hidden_size,
                         c.moe_intermediate_size),
    "w_up": lambda c: (c.n_routed_experts, c.hidden_size,
                       c.moe_intermediate_size),
    "w_down": lambda c: (c.n_routed_experts, c.moe_intermediate_size,
                         c.hidden_size),
    "ws_gate": lambda c: (c.hidden_size,
                          c.n_shared_experts * c.moe_intermediate_size),
    "ws_up": lambda c: (c.hidden_size,
                        c.n_shared_experts * c.moe_intermediate_size),
    "ws_down": lambda c: (c.n_shared_experts * c.moe_intermediate_size,
                          c.hidden_size),
}

# Delta-rule linear attention (ops/kda.py): the q, k, v projections with
# their depthwise convolutions' taps, the per-channel decay (full rank, a
# rate a head and a bias a channel), beta a head, the output's gate a
# channel, the norm a head's output goes through, the output projection.
_KDA_SHAPES = {
    "attn_norm": lambda c: (c.hidden_size,),
    **{f"kda_{n}": (lambda c: (c.hidden_size,
                               c.num_attention_heads * c.head_dim))
       for n in "qkv"},
    **{f"conv_{n}": (lambda c: (c.short_conv_kernel_size,
                                c.num_attention_heads * c.head_dim))
       for n in "qkv"},
    "w_decay": lambda c: (c.hidden_size, c.num_attention_heads * c.head_dim),
    "a_log": lambda c: (c.num_attention_heads,),
    "dt_bias": lambda c: (c.num_attention_heads * c.head_dim,),
    "w_beta": lambda c: (c.hidden_size, c.num_attention_heads),
    "wg": lambda c: (c.hidden_size, c.num_attention_heads * c.head_dim),
    "o_norm": lambda c: (c.head_dim,),
    "wo": lambda c: (c.num_attention_heads * c.head_dim, c.hidden_size),
    "mlp_norm": lambda c: (c.hidden_size,),
}

# The scalar-gated delta rule (ops/kda.py): ONE projection to ``[q | k | v
# | z]`` (key heads' q and k, value heads' v and output gate), one to ``[b |
# a]`` (beta and the decay's input, a value head each), the taps of the one
# convolution over ``[q | k | v]``, a rate and a bias a value head, the norm
# a head's output goes through (a plain weight), the output projection.
_GDN_SHAPES = {
    "attn_norm": lambda c: (c.hidden_size,),
    "w_qkvz": lambda c: (c.hidden_size, c.delta_conv_width
                         + c.delta_rule.value_heads * c.delta_rule.d_v),
    "w_ba": lambda c: (c.hidden_size, 2 * c.delta_rule.value_heads),
    "conv_qkv": lambda c: (c.delta_rule.taps, c.delta_conv_width),
    "a_log": lambda c: (c.delta_rule.value_heads,),
    "dt_bias": lambda c: (c.delta_rule.value_heads,),
    "o_norm": lambda c: (c.delta_rule.d_v,),
    "w_out": lambda c: (c.delta_rule.value_heads * c.delta_rule.d_v,
                        c.hidden_size),
    "mlp_norm": lambda c: (c.hidden_size,),
}

# A selective state-space mixer (ops/mamba.py): the input projection to x
# and the gate z, the depthwise convolution's taps and bias, the
# projection to the step's rank and to B and C with their three inner
# norms, the step size's projection and bias, ``A_log`` laid out ``[d_state,
# d_inner]`` as the state is, the skip ``D``, the output projection.
_MAMBA_SHAPES = {
    "attn_norm": lambda c: (c.hidden_size,),
    "w_in": lambda c: (c.hidden_size, 2 * c.mamba_d_inner),
    "conv_w": lambda c: (c.mamba_d_conv, c.mamba_d_inner),
    "conv_b": lambda c: (c.mamba_d_inner,),
    "w_x": lambda c: (c.mamba_d_inner,
                      c.mamba_dt_rank + 2 * c.mamba_d_state),
    "dt_norm": lambda c: (c.mamba_dt_rank,),
    "b_norm": lambda c: (c.mamba_d_state,),
    "c_norm": lambda c: (c.mamba_d_state,),
    "w_dt": lambda c: (c.mamba_dt_rank, c.mamba_d_inner),
    "dt_bias": lambda c: (c.mamba_d_inner,),
    "a_log": lambda c: (c.mamba_d_state, c.mamba_d_inner),
    "d_skip": lambda c: (c.mamba_d_inner,),
    "w_out": lambda c: (c.mamba_d_inner, c.hidden_size),
    "mlp_norm": lambda c: (c.hidden_size,),
}


# A gated short convolution (ops/shortconv.py): the input projection to the
# two gates and the convolved value (``[B | C | x]``), the depthwise taps,
# the output projection.
_CONV_SHAPES = {
    "attn_norm": lambda c: (c.hidden_size,),
    "w_in": lambda c: (c.hidden_size, 3 * c.hidden_size),
    "conv_w": lambda c: (c.conv_L_cache, c.hidden_size),
    "w_out": lambda c: (c.hidden_size, c.hidden_size),
    "mlp_norm": lambda c: (c.hidden_size,),
}


# A residual stream ``hc_mult`` hidden vectors wide (ops/hyper.py): each of a
# layer's two sub-layers has its own projection to the ``n^2 + 2 n`` mixing
# coefficients, their biases and the three gains; float32 whatever the
# serving type (``HC_TENSORS``: never cast, never quantised).
_HC_SHAPES = {
    f"hc_{part}_{name}": shape
    for part in ("attn", "ffn")
    for name, shape in (
        ("fn", lambda c: (c.hc_mult * c.hidden_size,
                          c.hc_mult * (c.hc_mult + 2))),
        ("base", lambda c: (c.hc_mult * (c.hc_mult + 2),)),
        ("scale", lambda c: (3,)))
}
HC_TENSORS = frozenset(_HC_SHAPES)


class Segment(NamedTuple):
    """Layers of ONE kind in a row, scanned as one stack: ``name`` is the
    stack's key in ``params["layers"]``, ``first`` the model index of its
    first layer and ``cache_first`` that layer's index in the cache
    buffers of its mixer's kind (rows, or state and tail), both in the
    first repetition of the run; a further repetition is ``stride`` model
    layers and ``cache_stride`` cached ones on."""

    name: str
    # "mla" | "mla2" | "kda" | "gdn" | "gqa" | "swa" | "mamba" | "conv" |
    # "eva"
    mixer: str
    ffn: str  # "dense" | "moe"
    first: int
    count: int
    cache_first: int
    cache_stride: int = 0


class Run(NamedTuple):
    """``segments`` in model order, ``repeats`` times over: a repeated
    period is scanned as a period (its stacks carry a leading ``[repeats,
    count]``), everything else is a run of one repetition (``[count]``)."""

    repeats: int
    segments: tuple[Segment, ...]
    stride: int = 0

    def layer_ids(self, seg: Segment):
        """Model indices of ``seg``'s layers, shaped as its stacks lead:
        ``[count]``, or ``[repeats, count]`` inside a repeated period."""
        import numpy as np

        ids = seg.first + np.arange(seg.count)
        if self.repeats == 1:
            return ids
        return ids[None] + self.stride * np.arange(self.repeats)[:, None]


def _whole_period(kinds) -> int:
    """The shortest period of several kinds that the WHOLE model repeats
    (0: none). A period that starts and ends in one kind (M7 A M6, twice)
    hides from a search over maximal stretches (M7 A M13 A M6), so
    :func:`layer_plan` also ends a stretch where a repetition ends."""
    n = len(kinds)
    for width in range(2, n // 2 + 1):
        if (n % width == 0 and len(set(kinds[:width])) > 1
                and kinds == kinds[:width] * (n // width)):
            return width
    return 0


def layer_plan(config: LlamaConfig) -> tuple[Run, ...]:
    """The order the layer loop runs a model's layers in where they are of
    several kinds (``config.segmented``), derived from
    ``config.layer_kinds`` alone: maximal stretches of one kind are
    segments, a stretch of segments that repeats is one run scanned over
    its repetitions. "Leading dense layers, then expert layers" is two
    segments (``dense``, ``moe``); delta-rule layers but every sixth is a
    period of two segments after the leading ones; state-space layers but
    the eighth of every fourteen is a period of three (M7 A M6).

    Window and full attention mixed by layer (``families.WINDOWED``): a
    leading dense layer, ``W W G``, then ``W W W G`` eleven times, is a
    dense window segment, a run of two sparse window layers, then ``G`` and
    ``W W W`` by turns, each stretch a segment of its own and, as beside
    short convolutions, NO repeated period where the layers hold routed
    experts (``periods`` below says why). (A stack a KIND, walked by one
    scan with a ``lax.switch`` over the kinds, was tried first: the chip's
    compiler then copies the rings in and out of every branch and re-lays
    the attention projections it indexes inside one, PERF.md section 7,
    PR 40.)

    Scalar-gated delta-rule layers beside gated attention
    (``families.GATED_DELTA``): ``D D D A`` all the way, every layer sparse,
    is ``D D D`` and ``A`` by turns, each stretch a segment of its own and
    NO repeated period, for the same reason.

    Short convolutions beside attention (``families.SHORT_CONV``): two
    leading dense conv layers, then ``A`` and ``c c c`` by turns with a
    ragged end, each stretch a segment of its own and NO repeated period
    where the layers hold routed experts (``periods`` below says why). A
    segment's ``cache_first`` counts the layers of ITS mixer before it: an
    attention layer's index into the rows, a conv layer's into the
    tails."""
    kinds = config.layer_kinds
    period = _whole_period(kinds)
    stretches = []  # [kind, first, count]
    for i, kind in enumerate(kinds):
        if (stretches and stretches[-1][0] == kind
                and not (period and i % period == 0)):
            stretches[-1][2] += 1
        else:
            stretches.append([kind, i, 1])
    one_mixer = len({m for m, _ in kinds}) == 1
    # A repeated period is scanned as a period but where its layers hold
    # routed experts beside short convolutions or beside window layers: an
    # admission of 128-256 rows takes the expert block's dense form (its
    # pairs hit nearly every held expert), and the chip's compiler re-lays
    # the stacks that form's product indexes inside a period (gate and up
    # of every layer copied transposed in ENTRY: 5.26 GiB of temporaries an
    # admission at 32 experts of 2048 x 1792 in 12 layers, more than a chip
    # has left beside the weights, my AOT compiles and chip run, PR 43;
    # 3.95 and 4.22 GiB at 64 experts of 2304 x 896 in two periods of four
    # layers, against 0.05-0.10 GiB for the sorted form's 512- and 1024-row
    # admissions of the same program, my AOT compiles, PR 55; 4.10 GiB at
    # 128 held experts of 2048 x 512 in two ``D D D A`` periods of
    # scalar-gated delta-rule and gated attention layers, a 128-row
    # admission, against 0.09 GiB a segment a stretch, my AOT compiles, PR
    # 57). As the
    # operand of a product behind a scan's ``xs``, a segment of one
    # repetition, a stack stays as it lies (PERF.md section 7)
    periods = config.family.expert_periods or not config.n_routed_experts
    names: dict[str, int] = {}
    # layers counted so far in the cache buffers of each mixer's kind
    cached = {"mla": 0, "mla2": 0, "kda": 0, "gdn": 0, "gqa": 0, "swa": 0,
              "mamba": 0, "conv": 0, "eva": 0}

    def segment(kind, first, count, cache_stride=0):
        mixer, ffn = kind
        base = ffn if one_mixer else f"{mixer}_{ffn}"
        names[base] = names.get(base, 0) + 1
        name = base if names[base] == 1 else f"{base}_{names[base]}"
        return Segment(name, mixer, ffn, first, count, cached[mixer],
                       cache_stride)

    runs = []
    at = 0
    while at < len(stretches):
        best = (1, 1)  # (repeats, width)
        for width in range(1, (len(stretches) - at) // 2 + 1):
            pattern = [(k, n) for k, _, n in stretches[at:at + width]]
            r = 1
            while [(k, n) for k, _, n in stretches[
                    at + r * width:at + (r + 1) * width]] == pattern:
                r += 1
            if r > 1 and r * width > best[0] * best[1]:
                best = (r, width)
        repeats, width = best if best[0] > 1 and periods else (1, 1)
        period = stretches[at:at + width]
        per_mixer = {m: sum(n for (mm, _), _, n in period if mm == m)
                     for m in cached}
        segs = []
        for kind, first, count in period:
            segs.append(segment(kind, first, count,
                                per_mixer[kind[0]] if repeats > 1 else 0))
            cached[kind[0]] += count
        for m in cached:  # the further repetitions' layers
            cached[m] += (repeats - 1) * per_mixer[m]
        runs.append(Run(repeats, tuple(segs),
                        sum(n for _, _, n in period)))
        at += repeats * width
    return tuple(runs)


def plan_segments(config: LlamaConfig):
    """``(run, segment)`` of every segment of the plan, in model order."""
    return [(run, seg) for run in layer_plan(config) for seg in run.segments]


def segment_shapes(config: LlamaConfig, seg: Segment) -> dict:
    """Per-layer weight name -> shape builder of one segment's kind."""
    if seg.mixer == "kda":
        shapes = dict(_KDA_SHAPES)
    elif seg.mixer == "gdn":
        shapes = dict(_GDN_SHAPES)
    elif seg.mixer == "mamba":
        shapes = dict(_MAMBA_SHAPES)
        if not config.mamba_conv_bias:
            del shapes["conv_b"]
    elif seg.mixer == "conv":
        shapes = dict(_CONV_SHAPES)
    elif seg.mixer == "mla2":
        # a double layer: each sub-layer's latent attention, norms and
        # dense feed-forward behind ``s0_`` / ``s1_`` (:func:`sub_layer`),
        # the one expert block's tensors under their own names
        shapes = {
            f"s{j}_{k}": shape for j in (0, 1) for k, shape in (
                *_LATENT_SHAPES.items(),
                *((k, _LAYER_SHAPES[k]) for k in ("w_gate", "w_up",
                                                  "w_down")))}
    elif seg.mixer == "eva":
        # multi-head attention's tensors and two learned vectors a head
        shapes = {k: _LAYER_SHAPES[k] for k in (
            "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm")}
        shapes["eva_phi"] = shapes["eva_mu"] = lambda c: (
            c.num_attention_heads * c.head_dim,)
    elif seg.mixer in ("gqa", "swa"):
        shapes = {k: _LAYER_SHAPES[k] for k in (
            "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm")}
        if config.qk_norm:  # one weight for all heads, each of q and k
            shapes["q_norm"] = shapes["k_norm"] = lambda c: (c.head_dim,)
        if config.attn_gate == "elementwise":  # a head's [q | gate]
            shapes["wq"] = lambda c: (
                c.hidden_size, 2 * c.num_attention_heads * c.head_dim)
        if config.family.loops:  # sandwich norms: each sub-layer's output
            shapes["attn_post_norm"] = shapes["mlp_post_norm"] = (
                _LAYER_SHAPES["attn_norm"])
    else:
        shapes = dict(_LATENT_SHAPES)
        if not config.q_lora_rank:  # one direct query projection
            for k in ("wq_a", "q_norm", "wq_b"):
                del shapes[k]
            shapes["wq"] = lambda c: (
                c.hidden_size, c.num_attention_heads
                * (c.qk_nope_head_dim + c.qk_rope_head_dim))
        if config.attn_gate:  # a sigmoid gate a head
            shapes["wg"] = lambda c: (c.hidden_size, c.num_attention_heads)
        if config.hc_mult > 1:  # the two sub-layers' mixing coefficients
            shapes.update(_HC_SHAPES)
        if config.index_topk:  # a sparse attention's indexer
            shapes.update(_INDEXER_SHAPES)
    if seg.ffn == "dense":
        shapes.update({k: _LAYER_SHAPES[k]
                       for k in ("w_gate", "w_up", "w_down")})
        return shapes
    shapes.update(_SHARED_MOE_SHAPES)
    if config.router_bias:
        shapes["b_router"] = lambda c: (c.router_outputs,)
    if not config.n_shared_experts:
        for k in ("ws_gate", "ws_up", "ws_down"):
            del shapes[k]
    if config.shared_expert_gate:  # the shared expert's own weight a token
        shapes["ws_share"] = lambda c: (c.hidden_size, 1)
    return shapes


def stack_layers(config: LlamaConfig) -> dict[str, int]:
    """Layers in each stack of a latent-family model (all repetitions of
    a repeated period counted)."""
    return {seg.name: run.repeats * seg.count
            for run, seg in plan_segments(config)}


def stack_shapes(config: LlamaConfig) -> dict[str, dict]:
    """Stack name -> per-layer weight name -> shape builder for the latent
    family (``params["layers"]`` is then a dict of stacks, one a segment
    of :func:`layer_plan`, each stacked over its own layers: ``{"dense":
    {...}, "moe": {...}}`` for leading dense layers then expert layers)."""
    return {seg.name: segment_shapes(config, seg)
            for _, seg in plan_segments(config)}


def layer_shapes(config: LlamaConfig) -> dict:
    """Per-layer weight name -> shape (without the leading ``[L]`` axis) for
    the given model family: the Llama base, plus q/k/v biases when
    ``attention_bias`` (Qwen2), with the dense MLP replaced by router +
    stacked expert weights when ``num_local_experts > 0`` (Mixtral). The
    latent family has two kinds of layer: :func:`stack_shapes`."""
    if config.segmented:
        raise ValueError("a model whose layers are of several kinds "
                         "(latent attention, a state space) has a stack a "
                         "kind: use stack_shapes(config)")
    shapes = dict(_LAYER_SHAPES)
    if config.attention_bias:
        shapes.update(_BIAS_SHAPES)
    if config.num_local_experts:
        shapes.update(_MOE_SHAPES)
    return shapes


def init_params(config: LlamaConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params pytree (test fixtures / benchmarks; real weights
    come from :mod:`cake_tpu.utils.weights`)."""
    dt = dtype or config.jax_dtype

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dt)

    def stack(shapes, L, keys):
        layers = {}
        for name, shape_fn in shapes.items():
            shape = shape_fn(config)
            k = next(keys)
            if name.endswith("norm"):
                layers[name] = jnp.ones((L,) + shape, dt)
            elif name.startswith("b"):
                # biases: small random so tests exercise a nonzero bias path
                layers[name] = (0.02 * jax.random.normal(
                    k, (L,) + shape, jnp.float32)).astype(dt)
            else:
                # fan_in is the next-to-last axis for 3D expert stacks
                # ([E, in, out]) and the first axis for plain [in, out]
                # linears
                fan_in = shape[-2] if len(shape) == 3 else shape[0]
                layers[name] = dense(k, (L,) + shape, fan_in)
        return layers

    if config.segmented:
        k_dense, k_moe, key = jax.random.split(key, 3)
        segs = plan_segments(config)
        seg_keys = (k_dense, k_moe) if len(segs) <= 2 else jax.random.split(
            k_dense, len(segs))
        layers = {}
        for (run, seg), k in zip(segs, seg_keys):
            shapes = segment_shapes(config, seg)
            flat = stack(shapes, run.repeats * seg.count,
                         iter(jax.random.split(k, len(shapes))))
            if seg.mixer == "mamba":
                flat.update(_mamba_init(config, flat, k, dt))
            if seg.mixer == "gdn":
                flat.update(_gdn_init(flat, k, dt))
            if seg.mixer == "eva":
                flat.update(_eva_init(flat, k, dt))
            if config.hc_mult > 1:
                flat.update(_hc_init(config, flat, k))
            lead = run.layer_ids(seg).shape
            layers[seg.name] = {n: w.reshape(lead + w.shape[1:])
                                for n, w in flat.items()}
        keys = iter(jax.random.split(key, 3))
    else:
        shapes = layer_shapes(config)
        keys = iter(jax.random.split(key, len(shapes) + 3))
        layers = stack(shapes, config.num_hidden_layers, keys)
    params = {
        "embed": dense(next(keys), (config.vocab_size, config.hidden_size),
                       config.hidden_size),
        "layers": layers,
        "norm_f": jnp.ones((config.hidden_size,), dt),
        "lm_head": dense(next(keys), (config.hidden_size, config.vocab_size),
                         config.hidden_size),
    }
    # a family's tensors beside these (a looped model's exit gate)
    for ours, parts in config.family.extra_tensors.items():
        k = next(keys)
        params[ours] = {
            part: dense(jax.random.fold_in(k, n), shape_fn(config),
                        config.hidden_size)
            for n, (part, (_, shape_fn)) in enumerate(parts.items())}
    return params


def _hc_init(config: LlamaConfig, stack: dict, key) -> dict:
    """A wide residual stream's tensors for a stack of layers, float32,
    seeded so that the mechanism works: gains of 1, ``phi`` of std
    ``1 / sqrt(n C)`` (a logit of std 1 on a normed stream), biases of std 1
    with 2 more on the diagonal of ``H_res``'s, so that ``H_res`` is
    neither the identity nor uniform and differs from token to token."""
    n = config.hc_mult
    diagonal = jnp.zeros((n * (n + 2),), jnp.float32).at[
        2 * n + (n + 1) * jnp.arange(n)].set(2.0)
    out = {}
    for i, part in enumerate(("attn", "ffn")):
        k_fn, k_base = jax.random.split(jax.random.fold_in(key, 51 + i))
        fn = stack[f"hc_{part}_fn"]
        out[f"hc_{part}_fn"] = jax.random.normal(
            k_fn, fn.shape, jnp.float32) / jnp.sqrt(fn.shape[-2])
        out[f"hc_{part}_base"] = diagonal + jax.random.normal(
            k_base, stack[f"hc_{part}_base"].shape, jnp.float32)
        out[f"hc_{part}_scale"] = jnp.ones(
            stack[f"hc_{part}_scale"].shape, jnp.float32)
    return out


def _eva_init(stack: dict, key, dt) -> dict:
    """An EVA layer's two learned vectors a head, seeded so that the
    mechanism works: ``phi`` of std 2 (a chunk's ``head_dim^-0.5 phi . k``
    over normed keys is then a logit of std ~2: the summary's softmax
    leans on a few of its positions) and ``mu`` of std 0.5 (a summary's
    key stands apart from its chunk's mean)."""
    k_phi, k_mu = jax.random.split(jax.random.fold_in(key, 7))
    shape = stack["eva_phi"].shape
    return {"eva_phi": (2.0 * jax.random.normal(k_phi, shape)).astype(dt),
            "eva_mu": (0.5 * jax.random.normal(k_mu, shape)).astype(dt)}


def _gdn_init(stack: dict, key, dt) -> dict:
    """Gated DeltaNet's own initialisation of what is no linear, for a
    stack of layers: ``A`` uniform in 1-16 (``A_log`` its logarithm), the
    decay's bias the inverse softplus of a step log-uniform in 0.001-0.1
    (so a token decays a state by 0.2-0.999 and a state remembers a few to
    hundreds of tokens), taps of std 0.5."""
    k_rate, k_step, k_taps = jax.random.split(key, 3)
    step = jnp.exp(jax.random.uniform(
        k_step, stack["dt_bias"].shape, jnp.float32,
        jnp.log(0.001), jnp.log(0.1)))
    return {
        "a_log": jnp.log(jax.random.uniform(
            k_rate, stack["a_log"].shape, jnp.float32, 1.0, 16.0)).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "conv_qkv": (0.5 * jax.random.normal(
            k_taps, stack["conv_qkv"].shape, jnp.float32)).astype(dt),
    }


def _mamba_init(config: LlamaConfig, stack: dict, key, dt) -> dict:
    """Mamba's own initialisation of what is no linear, for a stack of
    layers: ``A_log = log(1..d_state)`` a channel, the step size's bias the
    inverse softplus of a step log-uniform in 0.001-0.1 (so a token decays
    a state by 0.2-0.999 and a state remembers tens to hundreds of tokens),
    ``D = 1``, taps of std 0.5."""
    n, di = config.mamba_d_state, config.mamba_d_inner
    k_step, k_taps = jax.random.split(key)
    step = jnp.exp(jax.random.uniform(
        k_step, stack["dt_bias"].shape, jnp.float32,
        jnp.log(0.001), jnp.log(0.1)))
    return {
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
            stack["a_log"].shape).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "d_skip": jnp.ones_like(stack["d_skip"]),
        "conv_w": (0.5 * jax.random.normal(
            k_taps, stack["conv_w"].shape, jnp.float32)).astype(dt),
    }


def init_params_int8(config: LlamaConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params with every linear quantized to int8 — *without*
    ever materializing the full bf16/f32 model on device.

    :func:`init_params` + ``quantize_params`` peaks at full-precision bytes
    plus int8 bytes, which cannot fit Llama-3-8B on a 16 GiB v5e chip
    (~14.5 GiB usable). Here each stacked linear is generated and quantized
    inside one jitted ``lax.map`` over layers, so the f32 temporaries are
    per-layer-sized and freed at jit exit; peak stays near the int8 total.
    """
    return _init_params_quantized(config, key, dtype, bits=8)


def init_params_int4(config: LlamaConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params with every linear packed-int4 quantized
    (:class:`cake_tpu.ops.quant.Quantized4Linear`) — quarter the bf16 weight
    bytes, the bandwidth tier below :func:`init_params_int8`."""
    return _init_params_quantized(config, key, dtype, bits=4)


def _init_params_quantized(config, key, dtype, *, bits: int) -> Params:
    from functools import partial as _partial

    if config.segmented:
        raise NotImplementedError(
            "random-init quantized params cover the one-stack "
            "per-head-attention families; quantize a latent-family pytree "
            "with ops.quant.quantize_params")
    if config.num_local_experts and bits == 4:
        from cake_tpu.ops.quant import reject_int4_moe

        reject_int4_moe()

    from cake_tpu.ops.quant import (
        LAYER_LINEARS,
        Quantized4Linear,
        QuantizedLinear,
        quantize_linear,
        quantize_linear4,
    )

    if bits == 8:
        qfn, cls = quantize_linear, QuantizedLinear
        fields = ("q", "scale")
    else:
        qfn, cls = quantize_linear4, Quantized4Linear
        fields = ("qp", "scale")

    dt = dtype or config.jax_dtype
    L = config.num_hidden_layers
    shapes = layer_shapes(config)
    keys = iter(jax.random.split(key, len(shapes) + 3))

    @_partial(jax.jit, static_argnums=(1, 2, 3))
    def qdense(k, shape, fan_in, stacked):
        def one(kk):
            w = jax.random.normal(kk, shape, jnp.float32) / jnp.sqrt(fan_in)
            ql = qfn(w)  # the one quantization convention per tier
            return tuple(getattr(ql, f) for f in fields)

        if not stacked:
            return one(k)
        return jax.lax.map(one, jax.random.split(k, L))

    layers = {}
    for name, shape_fn in shapes.items():
        shape = shape_fn(config)
        k = next(keys)
        if name in LAYER_LINEARS:
            fan_in = shape[-2] if len(shape) == 3 else shape[0]
            q, scale = qdense(k, shape, fan_in, True)
            layers[name] = cls(q, scale)
        elif name == "router":  # tiny, stays full precision
            layers[name] = (
                jax.random.normal(k, (L,) + shape, jnp.float32)
                / jnp.sqrt(shape[0])
            ).astype(dt)
        elif name.startswith("b"):  # q/k/v biases stay full precision
            layers[name] = (0.02 * jax.random.normal(k, (L,) + shape,
                                                     jnp.float32)).astype(dt)
        else:  # norms
            layers[name] = jnp.ones((L,) + shape, dt)

    embed = (
        jax.random.normal(
            next(keys), (config.vocab_size, config.hidden_size), jnp.float32
        )
        / jnp.sqrt(config.hidden_size)
    ).astype(dt)
    hq, hscale = qdense(
        next(keys), (config.hidden_size, config.vocab_size),
        config.hidden_size, False,
    )
    return {
        "embed": embed,
        "layers": layers,
        "norm_f": jnp.ones((config.hidden_size,), dt),
        "lm_head": cls(hq, hscale),
    }


def embed_tokens(params: Params, tokens, config: LlamaConfig) -> jax.Array:
    """Token embedding lookup — THE embedding entry point for every
    execution path (local, pipeline builders, admission, speculation).
    Gemma multiplies the embedding output by sqrt(hidden) (``embed_scale``),
    with the normalizer rounded to the activation dtype exactly as HF does,
    so family deltas cannot drift between paths. Under a residual stream
    ``hc_mult`` hidden vectors wide the result is ``[B, T, hc_mult,
    hidden]``, the embedding in every stream (:func:`head_norm` sums them
    again; what lies between takes the last position with ``x[:, -1]``
    and asks neither for the hidden size nor for the rank)."""
    x = params["embed"][tokens].astype(config.jax_dtype)
    if config.embed_scale:
        x = x * jnp.asarray(config.hidden_size ** 0.5, config.jax_dtype)
    return hyper.widen(x, config.hc_mult)


def block_forward(
    layer: Params,  # one layer's weights (no leading L axis)
    x: jax.Array,  # [B, T, hidden]
    k_cache: jax.Array,  # [B, kv_heads, S, D]
    v_cache: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    pos,
    config: LlamaConfig,
    num_heads: int | None = None,
    num_kv_heads: int | None = None,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
    sp_size: int = 1,
    write_gate: jax.Array | None = None,
    sp_prefill: bool | None = None,
    sp_chunk: bool = False,
    ep_axis: str | None = None,
    ep_size: int | None = None,
    layer_idx: jax.Array | None = None,
    count_local: bool = False,
    expert_idx: jax.Array | None = None,
    valid: jax.Array | None = None,
    index_cache: jax.Array | None = None,
):
    """One pre-norm decoder block (transformer.rs:48-64). Returns ``(x,
    k_cache, v_cache)``; with ``count_local`` (an expert layer of the
    latent family) a fourth value, the :class:`ExpertCount` of the call
    (:func:`cake_tpu.ops.moe.moe_swiglu`). ``index_cache``: the index keys
    of a latent layer under a sparse attention (``KVCache.index``), which
    then come back right after ``v_cache``.

    ``layer_idx``: ``k_cache``/``v_cache`` are the stacked ``[L, B,
    kv_heads, S, D]`` cache and this block is layer ``layer_idx`` of it
    (:func:`forward_layers`); None: they are this layer's own buffers.
    ``expert_idx``: likewise ``layer``'s ``w_gate``/``w_up``/``w_down`` are
    the whole expert stacks and this is layer ``expert_idx`` of them.
    ``valid [B]``: the true tokens of each row of a bucketed chunk, for the
    expert block alone (:func:`cake_tpu.ops.moe.moe_swiglu`): rows past a
    frontier hide themselves from attention.

    Under tensor parallelism (inside shard_map), ``num_heads``/``num_kv_heads``
    are the per-device local counts and ``tp_axis`` names the mesh axis the
    row-parallel projections reduce over; the norm weights are replicated.
    ``sp_axis``/``sp_size``: sequence-parallel attention (ring prefill /
    distributed flash decode, see :mod:`cake_tpu.ops.ring`); the MLP needs no
    communication — it is elementwise over the sharded sequence.
    ``ep_axis``/``ep_size``: expert parallelism for MoE layers
    (:mod:`cake_tpu.ops.moe`) — the expert stack is sharded over it and the
    routed combine psums across it.

    Model-family deltas dispatch on the layer pytree itself: q/k/v bias
    arrays (``bq``/``bk``/``bv``, Qwen2) and a ``router`` + expert-stacked
    MLP (Mixtral) are used iff present, and so are a sandwich-normed
    layer's second norms (``attn_post_norm``/``mlp_post_norm``, the looped
    family: each norms its sub-layer's OUTPUT before the residual adds it);
    ``config.sliding_window`` (Mistral) narrows the causal mask. The latent family (``wkv_a`` present) attends
    through :func:`cake_tpu.ops.mla.latent_attention_block` and its expert
    layers add shared experts (``ws_*``) to the routed part.
    """
    if "wkv_a" in layer:
        return _latent_block(layer, x, k_cache, v_cache, cos, sin, pos,
                             config, write_gate, ep_axis, ep_size,
                             layer_idx, count_local, expert_idx, valid,
                             index_cache)
    h = rms_norm(x, layer["attn_norm"], config.rms_norm_eps,
                   offset=config.rms_norm_offset)
    attn_out, k_cache, v_cache = self_attention_block(
        h, layer["wq"], layer["wk"], layer["wv"], layer["wo"],
        k_cache, v_cache, cos, sin, pos,
        num_heads or config.num_attention_heads,
        num_kv_heads or config.num_key_value_heads,
        tp_axis=tp_axis,
        sp_axis=sp_axis,
        sp_size=sp_size,
        write_gate=write_gate,
        sp_prefill=sp_prefill,
        sp_chunk=sp_chunk,
        bq=layer.get("bq"),
        bk=layer.get("bk"),
        bv=layer.get("bv"),
        bo=layer.get("bo"),
        window=config.sliding_window,
        layer=layer_idx,
    )
    if "attn_post_norm" in layer:  # a sandwich norm on the sub-layer's output
        attn_out = rms_norm(attn_out, layer["attn_post_norm"],
                            config.rms_norm_eps)
    x = x + attn_out
    h = rms_norm(x, layer["mlp_norm"], config.rms_norm_eps,
                   offset=config.rms_norm_offset)
    if "router" in layer:
        mlp_out = moe_swiglu(
            h, layer["router"], layer["w_gate"], layer["w_up"],
            layer["w_down"], top_k=config.num_experts_per_tok,
            ep_axis=ep_axis, ep_size=ep_size, tp_axis=tp_axis,
            layer=expert_idx, valid=valid,
        )
    else:
        mlp_out = swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                         tp_axis=tp_axis, act=config.hidden_act)
    if "mlp_post_norm" in layer:
        mlp_out = rms_norm(mlp_out, layer["mlp_post_norm"],
                           config.rms_norm_eps)
    return x + mlp_out, k_cache, v_cache


def _sub_layer(layer, x, part: str, norm: str, config, f):
    """One sub-layer of a latent-family layer with its residual: ``x +
    F(RMS(x))``, ``f(normed) -> (y, aux)``; returns ``(x, aux)``. Where the
    layer holds ``hc_<part>_*`` (the pytree decides, as for ``bq`` or
    ``router``) ``x`` is a residual stream's hidden vectors and the
    sub-layer reads their mix and leaves its output mixed into all of them
    (ops/hyper.py)."""
    if f"hc_{part}_fn" not in layer:
        y, aux = f(rms_norm(x, layer[norm], config.rms_norm_eps))
        return x + y, aux
    co = hyper.coefficients(
        x, tuple(layer[f"hc_{part}_{t}"] for t in ("fn", "base", "scale")),
        config)
    y, aux = f(rms_norm(hyper.pre_mix(x, co), layer[norm],
                        config.rms_norm_eps))
    return hyper.post_mix(x, y, co), aux


def _latent_block(layer, x, c_cache, r_cache, cos, sin, pos, config,
                  write_gate, ep_axis, ep_size, layer_idx, count_local,
                  expert_idx, valid=None, i_cache=None):
    """:func:`block_forward` for a latent-attention layer."""
    def attend(h):
        with jax.named_scope("mla"):
            out, *caches = latent_attention_block(
                h, layer, c_cache, r_cache, cos, sin, pos, config,
                write_gate=write_gate, layer_idx=layer_idx, i_cache=i_cache)
        return out, tuple(caches)

    x, caches = _sub_layer(layer, x, "attn", "attn_norm", config, attend)
    x, local = _shared_feed_forward(layer, x, config, ep_axis, ep_size,
                                    count_local, expert_idx, valid)
    if count_local:
        return (x, *caches, local)
    return (x, *caches)


def _shared_feed_forward(layer, x, config, ep_axis, ep_size, count_local,
                         expert_idx, valid=None):
    """The feed-forward half of a latent-family layer, residual added
    (:func:`_sub_layer`). A dense layer (no ``router``) is a SwiGLU of
    ``intermediate_size``; an expert layer is ``shared(h) + sum over the
    chosen experts HELD here of w_e expert_e(h)`` (the shared expert where
    the layer holds one, times ``sigmoid(h w_share)`` where the layer holds
    ``ws_share``; the weights sigmoid scores under :class:`GroupRouting`,
    or softmax shares). ``valid [B]``: the true tokens of each row of a
    bucketed chunk, which the routed part alone is told (a padding row's
    routed result is zero; its shared expert's is nobody's to read).
    Returns ``(x, ExpertCount)``."""
    def feed(h):
        local = ExpertCount.zeros(h.shape[0])
        if "router" not in layer:
            return swiglu(h, layer["w_gate"], layer["w_up"],
                          layer["w_down"]), local
        y = _routed(layer, h, config, ep_axis, ep_size, count_local,
                    expert_idx, valid)
        if count_local:
            y, local = y
        if "ws_gate" in layer:  # every rank alike, so added after the psum
            with jax.named_scope("moe.shared"):
                shared = swiglu(h, layer["ws_gate"], layer["ws_up"],
                                layer["ws_down"])
            if "ws_share" in layer:  # weighted by the token's own gate
                with jax.named_scope("moe.shared_gate"):
                    shared = shared * jax.nn.sigmoid(quant.dense(
                        h, layer["ws_share"]).astype(jnp.float32)
                    ).astype(shared.dtype)
            y = y + shared
        return y, local

    return _sub_layer(layer, x, "ffn", "mlp_norm", config, feed)


def _routed(layer, h, config, ep_axis, ep_size, count_local, expert_idx,
            valid):
    """An expert layer's routed part of ``h`` (:func:`moe_swiglu`: the held
    experts' share and, where the router scores zero-compute outputs, the
    identity part), under the configuration's routing."""
    # softmax over ALL the router's experts with the chosen shares
    # renormalised is softmax over the chosen logits: router_topk's
    # ``routing=None`` form (families._check_told_share admits that softmax
    # and the unnormalised one, which is a GroupRouting of softmax shares)
    plain = config.scoring_func == "softmax" and config.norm_topk_prob
    routing = None if plain else GroupRouting(
        config.n_group, config.topk_group, config.norm_topk_prob,
        config.routed_scaling_factor, layer.get("b_router"),
        config.family.topk_norm_eps, config.scoring_func)
    return moe_swiglu(
        h, layer["router"], layer["w_gate"], layer["w_up"],
        layer["w_down"], top_k=config.num_experts_per_tok,
        ep_axis=ep_axis, ep_size=ep_size, routing=routing,
        held=(config.first_expert, config.n_routed_experts),
        count_local=count_local, layer=expert_idx, valid=valid,
        zero_experts=config.zero_expert_num,
    )


def sub_layer(layer: Params, j: int) -> Params:
    """Sub-layer ``j``'s tensors of a double layer, under the names the
    latent family's one-attention layer holds them by (``attn_norm``,
    ``wq_a`` .. ``wo``, ``mlp_norm``, ``w_gate``, ``w_up``, ``w_down``)."""
    prefix = f"s{j}_"
    return {k[len(prefix):]: v for k, v in layer.items()
            if k.startswith(prefix)}


def _double_block(layer, x, cache, cos, sin, pos, config, write_gate,
                  ep_axis, ep_size, layer_idx, count_local, expert_idx,
                  valid):
    """One shortcut-connected double layer over the carried cache's rows
    (planes ``2 layer_idx`` and ``2 layer_idx + 1``)::

        a0 = x  + MLA_0(RMS(x))          h = RMS(a0)
        s  = MoE(h)                      # computed here, NOT added here
        b0 = a0 + FFN_0(h)
        a1 = b0 + MLA_1(RMS(b0))
        out = a1 + FFN_1(RMS(a1)) + s

    ``s`` lives inside the block: the layer loop's carry is what every
    other family's is. Returns ``(x, cache, ExpertCount)``."""
    eps = config.rms_norm_eps

    def attend(sub, x, cache, plane):
        with jax.named_scope("mla"):
            out, c, r = latent_attention_block(
                rms_norm(x, sub["attn_norm"], eps), sub, cache.k, cache.v,
                cos, sin, pos, config, write_gate=write_gate,
                layer_idx=plane)
        return x + out, dataclasses.replace(cache, k=c, v=r)

    def feed(sub, h):
        return swiglu(h, sub["w_gate"], sub["w_up"], sub["w_down"])

    first, second = sub_layer(layer, 0), sub_layer(layer, 1)
    local = ExpertCount.zeros(x.shape[0], zero=True)
    with jax.named_scope("scmoe.first"):
        x, cache = attend(first, x, cache, 2 * layer_idx)
        h = rms_norm(x, first["mlp_norm"], eps)
        late = _routed(layer, h, config, ep_axis, ep_size, count_local,
                       expert_idx, valid)
        if count_local:
            late, local = late
        x = x + feed(first, h)
    with jax.named_scope("scmoe.second"):
        x, cache = attend(second, x, cache, 2 * layer_idx + 1)
        x = x + feed(second, rms_norm(x, second["mlp_norm"], eps)) + late
    return x, cache, local


def _kda_block(layer, x, cache, config, valid, ep_axis, ep_size, layer_idx,
               count_local, expert_idx):
    """One delta-rule layer of the latent family over the carried cache's
    recurrent buffers. Returns ``(x, cache, ExpertCount)``."""
    h = rms_norm(x, layer["attn_norm"], config.rms_norm_eps)
    with jax.named_scope("kda"):
        out, state, conv = kda_attention_block(
            h, layer, cache.state, cache.conv, config, valid=valid,
            layer_idx=layer_idx)
    x, local = _shared_feed_forward(layer, x + out, config, ep_axis,
                                    ep_size, count_local, expert_idx, valid)
    return x, dataclasses.replace(cache, state=state, conv=conv), local


def _typed_block(layer, x, cache, mixer, cos, sin, pos, config, valid,
                 ep_axis, ep_size, layer_idx, count_local, expert_idx):
    """One layer of a model whose ``layer_types`` name each layer's mixer,
    its shared-expert feed-forward included. Window and full attention
    mixed: a window layer (``mixer`` "swa") attends over its ring of the
    carried cache, a full one ("gqa") over its rows, each rotating q and k
    by ITS KIND's table (``cos``/``sin`` a dict by mixer,
    ``ops.rope.rope_tables_for``: K-EXAONE's full layers get None and
    rotate nothing, Mellum's get YaRN's beside the window layers' plain
    one). Short convolutions beside attention: a "conv" layer reads and
    writes its tail of the carried cache and no row, a full one rotates q
    and k by the model's one table. Attention norms each head of q and k
    first. ``layer_idx`` counts the layers of the mixer's own kind.
    Returns ``(x, cache, ExpertCount)``."""
    h = rms_norm(x, layer["attn_norm"], config.rms_norm_eps)
    if mixer == "conv":
        out, conv = conv_mixer_block(h, layer, cache.conv, valid=valid,
                                     layer_idx=layer_idx)
        x, local = _shared_feed_forward(layer, x + out, config, ep_axis,
                                        ep_size, count_local, expert_idx,
                                        valid)
        return x, dataclasses.replace(cache, conv=conv), local
    if mixer == "gdn":
        with jax.named_scope("gdn"):
            out, state, conv = gdn_attention_block(
                h, layer, cache.state, cache.conv, config, valid=valid,
                layer_idx=layer_idx)
        x, local = _shared_feed_forward(layer, x + out, config, ep_axis,
                                        ep_size, count_local, expert_idx,
                                        valid)
        return x, dataclasses.replace(cache, state=state, conv=conv), local
    if isinstance(cos, dict):  # a rotation a layer kind: this kind's
        cos, sin = cos[mixer], sin[mixer]
    norm = ((layer["q_norm"], layer["k_norm"], config.rms_norm_eps)
            if config.qk_norm else None)
    args = (h, layer["wq"], layer["wk"], layer["wv"], layer["wo"])
    heads = (config.num_attention_heads, config.num_key_value_heads)
    if mixer == "swa":
        with jax.named_scope("attn.swa"):
            out, ring_k, ring_v = window_attention_block(
                *args, cache.ring_k, cache.ring_v, cos, sin, pos, *heads,
                window=config.sliding_window, layer=layer_idx,
                qk_norm=norm, valid=valid)
        cache = dataclasses.replace(cache, ring_k=ring_k, ring_v=ring_v)
    else:
        with jax.named_scope("attn.full"):
            out, k, v = self_attention_block(
                *args, cache.k, cache.v, cos, sin, pos, *heads,
                layer=layer_idx, qk_norm=norm,
                gated=config.attn_gate == "elementwise")
        cache = dataclasses.replace(cache, k=k, v=v)
    x, local = _shared_feed_forward(layer, x + out, config, ep_axis,
                                    ep_size, count_local, expert_idx, valid)
    return x, cache, local


def _eva_block(layer, x, cache, cos, sin, pos, config, valid, layer_idx):
    """One EVA layer over the carried cache's ring and summary plane
    (ops/eva.py), its dense feed-forward included; the norms' ``1 + w`` is
    folded where the tensors are read. Returns ``(x, cache,
    ExpertCount)``."""
    h = rms_norm(x, layer["attn_norm"], config.rms_norm_eps)
    with jax.named_scope("attn.eva"):
        out, ring_k, ring_v, sum_k, sum_v = eva_attention_block(
            h, layer, cache, cos, sin, pos, config.num_attention_heads,
            config.window_size, config.chunk_size, layer_idx, valid=valid)
    cache = dataclasses.replace(cache, ring_k=ring_k, ring_v=ring_v,
                                sum_k=sum_k, sum_v=sum_v)
    x, local = _shared_feed_forward(layer, x + out, config, None, None,
                                    False, None)
    return x, cache, local


def _mamba_block(layer, x, cache, config, valid, layer_idx):
    """One state-space layer over the carried cache's recurrent buffers,
    its dense feed-forward included. Returns ``(x, cache, ExpertCount)``."""
    h = rms_norm(x, layer["attn_norm"], config.rms_norm_eps)
    with jax.named_scope("mamba"):
        out, state, conv = mamba_mixer_block(
            h, layer, cache.state, cache.conv, config, valid=valid,
            layer_idx=layer_idx)
    x, local = _shared_feed_forward(layer, x + out, config, None, None,
                                    False, None)
    return x, dataclasses.replace(cache, state=state, conv=conv), local


def forward_layers(
    layers: Params,  # stacked [L', ...] weights (any contiguous block range)
    x: jax.Array,  # [B, T, hidden]
    cache: KVCache,  # k/v: [L', B, kv_heads, S, D]
    cos: jax.Array,
    sin: jax.Array,
    pos,
    config: LlamaConfig,
    num_heads: int | None = None,
    num_kv_heads: int | None = None,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
    sp_size: int = 1,
    write_gate: jax.Array | None = None,
    sp_prefill: bool | None = None,
    sp_chunk: bool = False,
    ep_axis: str | None = None,
    ep_size: int | None = None,
    count_local: bool = False,
    valid: jax.Array | None = None,
    pass_norm: jax.Array | None = None,
    expert_valid: jax.Array | None = None,
):
    """Run a contiguous run of decoder blocks via ``lax.scan``. Returns
    ``(x, cache)``; with ``count_local`` (latent family) ``(x, cache,
    ExpertCount)``: each batch row's routed pairs that fell on held
    experts (int32 ``[B]``) and the held experts some row chose, summed
    over the expert layers.

    This is the TPU-native `Forwarder::forward_batch` (cake/mod.rs:143-150,
    worker.rs:208-219): one call executes any number of contiguous layers with
    no per-layer dispatch.

    What the loop carries and what it writes: the activation and the WHOLE
    stacked cache ``[L', B, kv_heads, S, D]`` are the scan's carry; the
    layer weights and a layer index are its ``xs`` (read, never written).
    Layer ``i`` writes only the ``T`` new rows of each stream, in place, at
    ``[i, b, :, pos_b : pos_b + T, :]`` of the carried buffers
    (:func:`cake_tpu.ops.kvcache.update_layer`), and attention reads layer
    ``i``'s keys and values out of the same buffers. So a donated cache
    that enters a program is the buffer that leaves it: there is no
    per-layer output stack to allocate, fill and copy back, which is what
    scanning the cache as ``xs``/``ys`` cost (two cache-sized copies, a slab
    write and a slab read per layer, in every decode step).

    The latent family's ``layers`` are a dict of stacks, one a segment of
    :func:`layer_plan` (``{"dense": ..., "moe": ...}`` for leading dense
    layers then expert layers): each segment is scanned over the ONE
    carried cache, its layers' indices into the cache buffers of their
    kind going on from where the last segment of that kind stopped; a
    repeated period of segments is one scan over its repetitions around
    the segments' own. A delta-rule or state-space layer reads and writes
    the cache's recurrent buffers in place of rows (``valid [B]``: the true
    tokens of each row of a bucketed chunk, which alone touch that state);
    a state-space model's attention layers are segments of the plain
    grouped-query block, with no rotation (``cos`` and ``sin`` None).
    Where window and full attention are mixed by layer, a window
    segment's layers attend over the cache's rings (``valid`` keeps a
    bucket's padding out of them) and a full segment's over its rows, each
    under its kind's rotation or none (``cos``/``sin`` a dict by mixer,
    :func:`_typed_block`) and indexed by the layers of its own mixer's
    kind. Short convolutions
    beside attention go the same way: a conv segment's layers read and
    write the cache's tails (``valid`` as above) and a full segment's
    rotate and attend over its rows. An expert layer's routed part is
    told the same lengths and counts a bucket's padding as nothing of its
    own (:func:`cake_tpu.ops.moe.moe_swiglu`); ``expert_valid [B]`` tells
    it where the cache holds rows alone and ``valid`` is None (rows past
    a frontier hide themselves from attention, not from an expert block).

    The looped family (``families.LOOPED``, ``config.total_ut_steps`` U > 1)
    runs its plan U times over the carried ``(h, cache)`` WITH THE SAME
    WEIGHTS, each pass under the named scope ``loop.pass``, and closes
    every pass with ``rms_norm(h, pass_norm)`` (``loop.norm``):
    ``pass_norm`` is the model's last norm (:func:`pass_norm`), which
    therefore lives INSIDE this loop and which no caller applies again
    before the head (:func:`head_norm`). Layer ``i`` of pass ``u`` reads
    and writes cache plane ``u * L + i`` (``LlamaConfig.cache_plan``'s
    ``rows`` counts ``U * L``): the keys and values a pass writes are read
    by the same pass of later tokens alone. A chunk of an admission runs
    all its passes before the next chunk, whose pass ``u`` attends over
    this chunk's plane of pass ``u``, complete by then. The passes are a
    ``lax.fori_loop`` around the one scan over the stack, the plane's
    offset a loop value; ``tests/test_chip_compile.py`` holds that the
    chip's compiler then copies neither the carried cache nor the stack
    (U unrolled scans compile to the same facts and read 1.7% more
    ``tpot_p50_ms`` on the chip: PERF.md sections 6 and 7). Limits: every token takes all U passes (``early_exit_threshold``
    1); one stage, tp = sp = 1.
    """
    rows = x.shape[0] * x.shape[1]
    batch = x.shape[0]
    if expert_valid is None:
        expert_valid = valid
    wide = config.hc_mult > 1
    if wide:  # the loops carry the stream's hidden vectors apart
        x = hyper.split(x)

    def split(stack):
        """``(scanned, whole)``: a stack's expert matrices taken out of
        what the scan slices, where this call's rows take the expert
        block's sorted form: its kernel reads a layer's matrices out of
        the whole stacks (a scan's slice would be written out for it)."""
        if "router" in stack and reads_whole_stacks(
                rows, config.num_experts_per_tok, stack["router"],
                stack["w_gate"], config.zero_expert_num):
            whole = {k: stack[k] for k in ("w_gate", "w_up", "w_down")}
            return {k: v for k, v in stack.items() if k not in whole}, whole
        return stack, {}

    def body(carry, per_layer, whole=None, mixer=None):
        h, c, *local = carry
        layer, i, *j = per_layer
        if whole:
            layer = {**layer, **whole}
        j = j[0] if j else None
        if mixer is not None:  # mixers named by layer_types: told apart
            h, c, now = _typed_block(
                layer, h, c, mixer, cos, sin, pos, config, valid, ep_axis,
                ep_size, i, count_local, j)
        elif "w_decay" in layer:
            h, c, now = _kda_block(layer, h, c, config, valid, ep_axis,
                                   ep_size, i, count_local, j)
        elif "w_in" in layer:
            h, c, now = _mamba_block(layer, h, c, config, valid, i)
        elif "eva_phi" in layer:
            h, c, now = _eva_block(layer, h, c, cos, sin, pos, config,
                                   valid, i)
        elif "s0_wkv_a" in layer:  # a double layer: planes 2 i and 2 i + 1
            h, c, now = _double_block(
                layer, h, c, cos, sin, pos, config, write_gate, ep_axis,
                ep_size, i, count_local, j, expert_valid)
        else:
            h, kc, vc, *now = block_forward(
                layer, h, c.k, c.v, cos, sin, pos, config,
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                tp_axis=tp_axis, sp_axis=sp_axis,
                sp_size=sp_size, write_gate=write_gate,
                sp_prefill=sp_prefill, sp_chunk=sp_chunk,
                ep_axis=ep_axis, ep_size=ep_size,
                layer_idx=i, count_local=count_local, expert_idx=j,
                valid=expert_valid, index_cache=c.index)
            c = dataclasses.replace(c, k=kc, v=vc)
            if c.index is not None:  # a sparse attention's index keys
                c = dataclasses.replace(c, index=now.pop(0))
            now = now[0] if now else None
        if count_local:
            return (h, c, local[0] + now), None
        return (h, c), None

    # a window layer and a full one hold the same tensors: where
    # ``layer_types`` names the mixers, a segment's mixer says which body
    # runs it (one a mixer, so that every segment of a kind traces the same
    # function)
    bodies = {m: partial(body, mixer=m)
              for m in ("swa", "gqa", "conv", "gdn")}

    def body_of(seg):
        return bodies[seg.mixer] if config.layer_types is not None else body

    def scan_segment(carry, stack, first, whole, body=body):
        """``stack``'s layers over the carry; ``first``: its first layer's
        index into the cache buffers of its kind; ``whole``: the expert
        stacks it was :func:`split` from, if any."""
        n = jax.tree.leaves(stack)[0].shape[0]
        # (a looped model's pass loop hands ``first`` in as a loop value)
        index = (jnp.arange(first, first + n, dtype=jnp.int32)
                 if isinstance(first, int)
                 else first + jnp.arange(n, dtype=jnp.int32))
        if not whole:  # ONE body for every such segment: traced once
            return jax.lax.scan(body, carry, (stack, index))[0]
        return jax.lax.scan(
            partial(body, whole=whole), carry,
            (stack, index, jnp.arange(n, dtype=jnp.int32)))[0]

    def scan_period(carry, run):
        """``run``'s segments, ``run.repeats`` times over: one scan over
        the repetitions around the segments' own. The stacks stay WHOLE
        outside both loops (``[repeats, count, ..]`` seen as ``[repeats *
        count, ..]``: no data moves) and a layer's weights are indexed out
        of them where they are used, as a scan indexes its ``xs``: a
        repetition's slice handed to the inner loops as their ``xs`` would
        be written out first, every repetition of every step (350 MiB a
        projection at published widths: the chip's compiler, PR 34)."""
        flat = {seg.name: split(jax.tree.map(
            lambda w: w.reshape((-1,) + w.shape[2:]), layers[seg.name]))
            for seg in run.segments}

        def scan_rows(carry, seg, r):
            stack, whole = flat[seg.name]
            at = r * seg.count  # the segment's first layer in its stacks
            first = seg.cache_first + r * seg.cache_stride

            def one(carry, j):
                layer = jax.tree.map(
                    lambda w: jax.lax.dynamic_index_in_dim(
                        w, at + j, 0, keepdims=False), stack)
                per_layer = (layer, first + j) + ((at + j,) if whole else ())
                return body_of(seg)(carry, per_layer, whole=whole)

            return jax.lax.scan(
                one, carry, jnp.arange(seg.count, dtype=jnp.int32))[0]

        def period(carry, r):
            for seg in run.segments:
                carry = scan_rows(carry, seg, r)
            return carry, None

        return jax.lax.scan(
            period, carry, jnp.arange(run.repeats, dtype=jnp.int32))[0]

    carry = (x, cache)
    if count_local:
        carry += (ExpertCount.zeros(batch, config.zero_expert_num > 0),)
    if not config.segmented:  # one kind of layer, one bare stack
        stack, whole = split(layers)
        return scan_segment(carry, stack, 0, whole)

    def one_pass(carry, plane=0):
        """The plan's layers, once; ``plane``: the cache plane of the
        pass's first layer."""
        for run in layer_plan(config):
            if run.repeats > 1:
                carry = scan_period(carry, run)
                continue
            for seg in run.segments:
                stack, whole = split(layers[seg.name])
                carry = scan_segment(carry, stack, plane + seg.cache_first,
                                     whole, body_of(seg))
        return carry

    if not config.family.loops:
        h, *rest = one_pass(carry)
        return (hyper.join(h) if wide else h, *rest)
    if pass_norm is None:
        raise ValueError(
            "a looped model's layer loop closes each pass with the model's "
            "last norm: call forward_layers(..., pass_norm=llama.pass_norm("
            "params, config))")
    per_pass = config.cache_plan["rows"][0] // config.total_ut_steps

    def a_pass(u, carry):
        with jax.named_scope("loop.pass"):
            h, *rest = one_pass(carry, u * per_pass)
        with jax.named_scope("loop.norm"):
            h = rms_norm(h, pass_norm, config.rms_norm_eps)
        return (h, *rest)

    return jax.lax.fori_loop(0, config.total_ut_steps, a_pass, carry)


def true_rows(config: LlamaConfig, shape: tuple[int, int], last_index):
    """``(valid, expert_valid)`` of :func:`forward_layers` for a bucketed
    chunk of ``shape = (B, T)`` whose rows' last true tokens lie at
    ``last_index [B]`` (or one index for every row): the true tokens of
    each row, int32 ``[B]``, up to and with its last true token, the whole
    chunk where that token lies in a later one. ``valid`` for a model
    whose layers hold a recurrent state, a tail or a ring of rows (None
    otherwise: rows past a frontier hide themselves), ``expert_valid``
    for a model with expert layers, whatever its cache holds (None
    otherwise): a frontier hides a padding row from attention, and an
    expert block would route and compute it all the same."""
    # (an index key lies behind its stream's frontier, as a row does; a
    # summary row does not: a bucket's padding may not enter one)
    stateful = bool(set(config.cache_plan) - {"rows", "index"})
    sparse = any(ffn == "moe" for _, ffn in config.layer_kinds)
    if not (stateful or sparse):  # such a program is told no length
        return None, None
    b, t = shape
    true = jnp.broadcast_to(jnp.minimum(last_index + 1, t),
                            (b,)).astype(jnp.int32)
    return true if stateful else None, true if sparse else None


def pass_norm(params: Params, config: LlamaConfig):
    """What :func:`forward_layers` takes as ``pass_norm``: the model's last
    norm where the layer loop applies it (a looped family, at the end of
    each pass), None for every other family."""
    return params["norm_f"] if config.family.loops else None


def head_norm(params: Params, x: jax.Array, config: LlamaConfig) -> jax.Array:
    """The model's last norm, before the head: THE place every path norms
    what it hands the head. A looped family's layer loop has applied it
    already (the last pass's closing norm), so its head norms nothing. A
    residual stream several hidden vectors wide (``x [.., hc_mult,
    hidden]``) leaves as their sum."""
    if config.family.loops:
        return x
    x = hyper.narrow(x, config.hc_mult)
    return rms_norm(x, params["norm_f"], config.rms_norm_eps,
                    offset=config.rms_norm_offset)


def forward(
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: KVCache,
    pos,
    config: LlamaConfig,
) -> tuple[jax.Array, KVCache]:
    """Full forward: embed -> blocks -> ln_f -> last position -> lm_head.

    Returns ``(logits [B, vocab] f32, new_cache)`` — logits taken at the last
    position and upcast to f32 exactly as the reference (llama.rs:124-143).
    """
    cos, sin = rope_tables_for(config, cache.max_seq)
    x = embed_tokens(params, tokens, config)
    x, cache = forward_layers(params["layers"], x, cache, cos, sin, pos, config,
                              pass_norm=pass_norm(params, config))
    x = head_norm(params, x, config)
    x_last = x[:, -1, :]
    logits = quant.dense(x_last, params["lm_head"]).astype(jnp.float32)
    return logits, cache


def hidden_forward_layers(
    layers: Params,
    x: jax.Array,
    cache: KVCache,
    pos,
    config: LlamaConfig,
    max_seq: int | None = None,
) -> tuple[jax.Array, KVCache]:
    """Convenience wrapper that builds RoPE tables internally — the entry
    point a worker jits for its assigned block range (worker.rs:203-224)."""
    cos, sin = rope_tables_for(config, cache.max_seq)
    return forward_layers(layers, x, cache, cos, sin, pos, config)
