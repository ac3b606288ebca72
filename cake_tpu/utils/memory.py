"""Process/device memory reporting.

Equivalent of the reference's RSS logging at every phase via memory_stats +
human_bytes (cake/mod.rs:67-73, master.rs:25-28, worker.rs:102-106,
llama.rs:203-206), plus TPU-side HBM stats the reference has no analog for.
"""

from __future__ import annotations

import resource


def rss_bytes() -> int:
    """Peak resident set size of this process (linux: ru_maxrss is KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.1f} {unit}"
        n /= 1024.0
    return f"{n:.1f} PiB"


def hbm_budget(
    config,
    num_stages: int = 1,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
    max_seq: int | None = None,
    batch: int = 1,
    quant: str | None = None,
    cache_bytes_per_el: int = 2,
) -> dict:
    """Per-chip HBM budget (bytes) for a (stage, tp, sp, ep) mesh layout.

    Mirrors the sharding actually used (parallel/mesh.py param_specs +
    CACHE_SPEC): stacked layers shard over stage, linear in/out features over
    tp, KV sequence over sp and kv-heads over tp; **embed is replicated** on
    every chip and lm_head shards its vocab over tp. ``quant='int8'`` prices
    the linears at 1 byte + f32 scales, ``quant='int4'`` at half a byte
    (packed) + f32 scales (ops/quant.py layouts).

    This is the planning arithmetic behind BASELINE.json configs 4/5 (70B on
    v5e-16): it makes the "int8 is load-bearing, not optional" claim of
    SURVEY.md §7 checkable.
    """
    c = config
    el = 2 if c.dtype in ("bfloat16", "float16") else 4
    group = None
    if quant:
        from cake_tpu.ops.quant import parse_quant_spec

        quant, group = parse_quant_spec(quant)
    if quant == "int8":
        lin_el, scale_el = 1, 4
    elif quant == "int4":
        lin_el, scale_el = 0.5, 4  # packed two-per-byte (ops/quant.py int4)
    else:
        lin_el, scale_el = el, 0
    S = max_seq or c.max_seq_len
    d = c.head_dim
    if quant and not c.family.linear_tiers:
        raise ValueError(f"quantized linears are not wired for "
                         f"{c.family.what} ({c.family.linear_why})")
    if c.segmented:
        return _latent_budget(c, ep, S, batch, lin_el, scale_el, el,
                              cache_bytes_per_el)

    # per-layer linear params (full, unsharded). MoE (Mixtral families):
    # the MLP triplet multiplies by num_local_experts and its expert axis
    # shards over ep (mesh.param_specs P(STAGE, EP, ., TP)); the router is
    # tiny and replicated. ep divides ONLY the expert stacks — attention
    # and norms are replicated across ep ranks.
    n_exp = getattr(c, "num_local_experts", 0) or 0
    qkv_out = (c.num_attention_heads + 2 * c.num_key_value_heads) * d
    lin = c.hidden_size * qkv_out  # wq+wk+wv
    lin += c.num_attention_heads * d * c.hidden_size  # wo
    mlp = 3 * c.hidden_size * c.intermediate_size  # gate/up/down
    mlp_out = 2 * c.intermediate_size + c.hidden_size
    if n_exp:
        # integer division is exact here: validate_shardable guarantees
        # ep | n_exp, so the byte counts stay integral for MoE configs
        mlp = mlp * n_exp // ep
        mlp_out = mlp_out * n_exp // ep
    lin += mlp
    lin_out = qkv_out + c.hidden_size + mlp_out
    norms = 2 * c.hidden_size
    if n_exp:
        # per-layer router [H, E], replicated, full precision — priced with
        # the norms (both ride the `* el` term below)
        norms += c.hidden_size * n_exp

    layers_per_chip = c.num_hidden_layers / num_stages
    # scale elements: one per output channel (per-channel), or one per
    # (in-group, channel) = weight elements / group_size (grouped int4 —
    # e.g. g=128 on 70B w_down stores 224 scales per channel, ~6% of the
    # int4 weight bytes; a near-limit config must price them)
    layer_scales = lin / group if group else lin_out
    layer_bytes = layers_per_chip * (
        lin / tp * lin_el + layer_scales / tp * scale_el + norms * el
    )
    embed_bytes = c.vocab_size * c.hidden_size * el  # replicated
    head_scales = (
        c.hidden_size * c.vocab_size / group if group else c.vocab_size
    )
    head_bytes = (
        c.hidden_size * c.vocab_size / tp * lin_el
        + (head_scales / tp) * scale_el
        + c.hidden_size * el
    )
    # the cache's depth is the plan's (LlamaConfig.cache_plan)
    planes_per_chip = c.cache_plan["rows"][0] / num_stages
    kv_bytes = (
        planes_per_chip * batch * (c.num_key_value_heads / tp)
        * (S / sp) * d * 2 * cache_bytes_per_el
    )
    if cache_bytes_per_el == 1:
        # int8 KV (kvcache.QuantizedKV): one f32 scale per slot per head
        kv_bytes += (
            planes_per_chip * batch * (c.num_key_value_heads / tp)
            * (S / sp) * 2 * 4
        )
    total = layer_bytes + embed_bytes + head_bytes + kv_bytes
    return {
        "layers": int(layer_bytes),
        "embed_replicated": int(embed_bytes),
        "head": int(head_bytes),
        "kv_cache": int(kv_bytes),
        "total": int(total),
    }


def _latent_budget(c, ep: int, S: int, batch: int, lin_el, scale_el,
                   el: int, cache_el: int) -> dict:
    """:func:`hbm_budget` for a model whose layers are of several kinds
    (the latent-attention, shared-expert family; a state-space hybrid),
    from the shapes the model is built with (``models.llama.stack_shapes``):
    every tensor replicated but the HELD experts' stacks, which divide over
    ep; the cache is the latent row (``LlamaConfig.cache_row_values`` a
    token a layer), not per-head keys and values, for the layers that
    keep rows, and a delta-rule or state-space layer's state and
    convolution tail a stream for the others, and a ring of ``R`` rows a
    stream for a window layer, and a sparse attention's index key a token
    beside the latent row, and one summary row for every ``chunk_size``
    positions beside an EVA layer's ring (``LlamaConfig.cache_plan``: the
    capacity is ``S``, whichever kind of the plan grows with it). One stage, tp = sp = 1
    (``mesh.validate_shardable``)."""
    import math

    from cake_tpu.models.llama import HC_TENSORS, stack_layers, stack_shapes
    from cake_tpu.ops.quant import LATENT_LINEARS

    count = stack_layers(c)
    layer_bytes = 0.0
    for stack, shapes in stack_shapes(c).items():
        per_layer = 0.0
        for name, shape_fn in shapes.items():
            shape = shape_fn(c)
            if name in LATENT_LINEARS:
                held = shape[0] / ep if len(shape) == 3 else 1
                fan_in, out = shape[-2:]
                per_layer += held * (fan_in * out * lin_el + out * scale_el)
            elif name in HC_TENSORS:  # a wide residual stream's: float32
                per_layer += math.prod(shape) * 4
            else:  # norms and the router, in the serving type
                per_layer += math.prod(shape) * el
        layer_bytes += count[stack] * per_layer
    embed_bytes = c.vocab_size * c.hidden_size * el
    head_bytes = (c.hidden_size * c.vocab_size * lin_el
                  + c.vocab_size * scale_el + c.hidden_size * el)
    plan = c.cache_plan  # rows for some layers, a state for the others
    kv_bytes = (plan.get("rows", (0,))[0] * batch * S * c.cache_row_values
                * cache_el)
    if "state" in plan:  # a float32 state ...
        kv_bytes += batch * math.prod(plan["state"]) * 4
    if "conv" in plan:  # ... and a tail in the serving type, or a tail alone
        kv_bytes += batch * math.prod(plan["conv"]) * el
    if "ring" in plan:  # window layers: R rows a stream whatever S
        n, heads, rows, k_width, v_width = plan["ring"]
        kv_bytes += batch * n * heads * rows * (k_width + v_width) * cache_el
    if "index" in plan:  # a sparse attention's key a token beside the rows
        kv_bytes += batch * S * math.prod(plan["index"]) * el
    if "summary" in plan:  # EVA: a row for every ``chunk`` positions
        n, heads, chunk, k_width, v_width = plan["summary"]
        kv_bytes += (batch * n * heads * (S // chunk) * (k_width + v_width)
                     * cache_el)
    return {
        "layers": int(layer_bytes),
        "embed_replicated": int(embed_bytes),
        "head": int(head_bytes),
        "kv_cache": int(kv_bytes),
        "total": int(layer_bytes + embed_bytes + head_bytes + kv_bytes),
    }


def device_report() -> dict:
    """What this process's JAX backend is and what each local device
    holds: ``{"platform", "kind", "count", "devices": [{"id",
    "bytes_in_use", "peak_bytes_in_use", "bytes_limit"}, ...]}`` --
    platform, kind and count exactly as ``jax.devices()`` reports them
    (the serve status carries this block, so a client can tell a chip
    from a CPU without a probe of its own). The byte fields are None on
    backends that keep no ``memory_stats`` (the CPU)."""
    import jax

    devs = jax.devices()
    per = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        per.append({"id": d.id, **{
            k: stats.get(k)
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}})
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "devices": per}


def memory_report() -> str:
    """One log line: host RSS and every local device's bytes in use (all
    of them: a sharded model that landed on the first chip only must
    show in the line a user reads)."""
    parts = [f"rss {human_bytes(rss_bytes())}"]
    used = [d["bytes_in_use"] for d in device_report()["devices"]
            if d["bytes_in_use"] is not None]
    if used:
        parts.append("hbm " + "/".join(human_bytes(u) for u in used))
    return ", ".join(parts)
