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

from typing import Any

import jax
import jax.numpy as jnp

from cake_tpu.models.config import LlamaConfig
from cake_tpu.ops import quant
from cake_tpu.ops.attention import self_attention_block
from cake_tpu.ops.kvcache import KVCache
from cake_tpu.ops.mla import latent_attention_block
from cake_tpu.ops.mlp import swiglu
from cake_tpu.ops.moe import GroupRouting, moe_swiglu
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import rope_tables_for

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

# An expert layer of the latent family: the router at its published width,
# the HELD experts' stacks, and the shared experts as one SwiGLU.
_SHARED_MOE_SHAPES = {
    "router": lambda c: (c.hidden_size, c.router_experts),
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

# The latent family's two stacks, in the order the layer loop runs them.
STACKS = ("dense", "moe")


def stack_layers(config: LlamaConfig) -> dict[str, int]:
    """Layers in each stack of a latent-family model: the leading dense
    ones, then the expert ones."""
    dense = (config.first_k_dense_replace if config.n_routed_experts
             else config.num_hidden_layers)
    return {"dense": dense, "moe": config.num_hidden_layers - dense}


def stack_shapes(config: LlamaConfig) -> dict[str, dict]:
    """Stack name -> per-layer weight name -> shape builder for the latent
    family (``params["layers"]`` is then ``{"dense": {...}, "moe": {...}}``,
    each stacked over its own layers; an empty stack is left out)."""
    dense = dict(_LATENT_SHAPES)
    dense.update({k: _LAYER_SHAPES[k] for k in ("w_gate", "w_up", "w_down")})
    moe = dict(_LATENT_SHAPES)
    moe.update(_SHARED_MOE_SHAPES)
    if not config.n_shared_experts:
        for k in ("ws_gate", "ws_up", "ws_down"):
            del moe[k]
    count = stack_layers(config)
    return {name: shapes for name, shapes in (("dense", dense), ("moe", moe))
            if count[name]}


def layer_shapes(config: LlamaConfig) -> dict:
    """Per-layer weight name -> shape (without the leading ``[L]`` axis) for
    the given model family: the Llama base, plus q/k/v biases when
    ``attention_bias`` (Qwen2), with the dense MLP replaced by router +
    stacked expert weights when ``num_local_experts > 0`` (Mixtral). The
    latent family has two kinds of layer: :func:`stack_shapes`."""
    if config.latent:
        raise ValueError("a latent-attention model has two layer stacks: "
                         "use stack_shapes(config)")
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

    if config.latent:
        k_dense, k_moe, key = jax.random.split(key, 3)
        count = stack_layers(config)
        layers = {
            name: stack(shapes, count[name],
                        iter(jax.random.split(k, len(shapes))))
            for (name, shapes), k in zip(stack_shapes(config).items(),
                                         (k_dense, k_moe))}
        keys = iter(jax.random.split(key, 3))
    else:
        shapes = layer_shapes(config)
        keys = iter(jax.random.split(key, len(shapes) + 3))
        layers = stack(shapes, config.num_hidden_layers, keys)
    return {
        "embed": dense(next(keys), (config.vocab_size, config.hidden_size),
                       config.hidden_size),
        "layers": layers,
        "norm_f": jnp.ones((config.hidden_size,), dt),
        "lm_head": dense(next(keys), (config.hidden_size, config.vocab_size),
                         config.hidden_size),
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

    if config.latent:
        raise NotImplementedError(
            "random-init quantized params cover the per-head-attention "
            "families; quantize a latent-family pytree with "
            "ops.quant.quantize_params")
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
    so family deltas cannot drift between paths."""
    x = params["embed"][tokens].astype(config.jax_dtype)
    if config.embed_scale:
        x = x * jnp.asarray(config.hidden_size ** 0.5, config.jax_dtype)
    return x


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
):
    """One pre-norm decoder block (transformer.rs:48-64). Returns ``(x,
    k_cache, v_cache)``; with ``count_local`` (an expert layer of the
    latent family) a fourth value, each row's routed pairs that fell on
    the experts held here (:func:`cake_tpu.ops.moe.moe_swiglu`).

    ``layer_idx``: ``k_cache``/``v_cache`` are the stacked ``[L, B,
    kv_heads, S, D]`` cache and this block is layer ``layer_idx`` of it
    (:func:`forward_layers`); None: they are this layer's own buffers.

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
    MLP (Mixtral) are used iff present; ``config.sliding_window`` (Mistral)
    narrows the causal mask. The latent family (``wq_a`` present) attends
    through :func:`cake_tpu.ops.mla.latent_attention_block` and its expert
    layers add shared experts (``ws_*``) to the routed part.
    """
    h = rms_norm(x, layer["attn_norm"], config.rms_norm_eps,
                   offset=config.rms_norm_offset)
    if "wq_a" in layer:
        return _latent_block(layer, x, h, k_cache, v_cache, cos, sin, pos,
                             config, write_gate, ep_axis, ep_size,
                             layer_idx, count_local)
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
    x = x + attn_out
    h = rms_norm(x, layer["mlp_norm"], config.rms_norm_eps,
                   offset=config.rms_norm_offset)
    if "router" in layer:
        x = x + moe_swiglu(
            h, layer["router"], layer["w_gate"], layer["w_up"],
            layer["w_down"], top_k=config.num_experts_per_tok,
            ep_axis=ep_axis, ep_size=ep_size, tp_axis=tp_axis,
        )
    else:
        x = x + swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                       tp_axis=tp_axis, act=config.hidden_act)
    return x, k_cache, v_cache


def _latent_block(layer, x, h, c_cache, r_cache, cos, sin, pos, config,
                  write_gate, ep_axis, ep_size, layer_idx, count_local):
    """The rest of :func:`block_forward` for a latent-family layer: ``h``
    is the normed input. A dense layer (no ``router``) is a SwiGLU of
    ``intermediate_size``; an expert layer is ``shared(h) + sum over the
    chosen experts HELD here of w_e expert_e(h)``."""
    with jax.named_scope("mla"):
        attn_out, c_cache, r_cache = latent_attention_block(
            h, layer, c_cache, r_cache, cos, sin, pos, config,
            write_gate=write_gate, layer_idx=layer_idx)
    x = x + attn_out
    h = rms_norm(x, layer["mlp_norm"], config.rms_norm_eps)
    local = jnp.zeros((x.shape[0],), jnp.int32)
    if "router" in layer:
        y = moe_swiglu(
            h, layer["router"], layer["w_gate"], layer["w_up"],
            layer["w_down"], top_k=config.num_experts_per_tok,
            ep_axis=ep_axis, ep_size=ep_size,
            routing=GroupRouting(config.n_group, config.topk_group,
                                 config.norm_topk_prob,
                                 config.routed_scaling_factor),
            held=(config.first_expert, config.n_routed_experts),
            count_local=count_local,
        )
        if count_local:
            y, local = y
        if "ws_gate" in layer:  # every rank alike, so added after the psum
            with jax.named_scope("moe.shared"):
                y = y + swiglu(h, layer["ws_gate"], layer["ws_up"],
                               layer["ws_down"])
        x = x + y
    else:
        x = x + swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])
    if count_local:
        return x, c_cache, r_cache, local
    return x, c_cache, r_cache


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
):
    """Run a contiguous run of decoder blocks via ``lax.scan``. Returns
    ``(x, cache)``; with ``count_local`` (latent family) ``(x, cache,
    local_pairs)``, each batch row's routed pairs that fell on held
    experts, summed over the expert layers (int32 ``[B]``).

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

    The latent family's ``layers`` are two stacks (``{"dense": ..., "moe":
    ...}``, :func:`stack_shapes`): the leading dense layers are scanned,
    then the expert layers, over the ONE carried cache, the second scan's
    layer indices going on from where the first stopped.
    """
    def body(carry, per_layer):
        h, kc, vc, *local = carry
        layer, i = per_layer
        out = block_forward(layer, h, kc, vc, cos, sin, pos, config,
                            num_heads=num_heads, num_kv_heads=num_kv_heads,
                            tp_axis=tp_axis, sp_axis=sp_axis,
                            sp_size=sp_size, write_gate=write_gate,
                            sp_prefill=sp_prefill, sp_chunk=sp_chunk,
                            ep_axis=ep_axis, ep_size=ep_size,
                            layer_idx=i, count_local=count_local)
        if count_local:
            return (*out[:3], local[0] + out[3]), None
        return out, None

    stacks = ([layers[name] for name in STACKS if name in layers]
              if config.latent else [layers])
    carry = (x, cache.k, cache.v)
    if count_local:
        carry += (jnp.zeros((x.shape[0],), jnp.int32),)
    first = 0
    for stack in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        carry, _ = jax.lax.scan(
            body, carry,
            (stack, jnp.arange(first, first + n, dtype=jnp.int32)))
        first += n
    if count_local:
        return carry[0], KVCache(k=carry[1], v=carry[2]), carry[3]
    return carry[0], KVCache(k=carry[1], v=carry[2])


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
    x, cache = forward_layers(params["layers"], x, cache, cos, sin, pos, config)
    x = rms_norm(x, params["norm_f"], config.rms_norm_eps,
                   offset=config.rms_norm_offset)
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
