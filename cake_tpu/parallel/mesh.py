"""Device mesh construction and parameter sharding specs.

The TPU-native replacement for the reference's distribution plane: instead of
one TCP worker per host with activations serialized over sockets
(`cake-core/src/cake/{client,worker,proto}`), the devices form a
`jax.sharding.Mesh` with axes

- ``stage`` — pipeline stages: the stacked layer axis shards here, the
  equivalent of the reference topology's contiguous ``model.layers.N-M``
  ranges per worker (topology.rs:46-69); activations move stage-to-stage by
  ICI ``ppermute`` inside one compiled program.
- ``tp`` — tensor parallelism (Megatron-style): attention heads and MLP
  intermediate shard here; row-parallel projections psum over it. The
  reference has no tensor parallelism (SURVEY.md §2 "not present") — on TPU
  it is the main single-token latency lever, so it is first-class.
- ``sp`` — sequence/context parallelism: the KV cache's sequence axis shards
  here; long prefill runs ring attention around the ``sp`` ring
  (:mod:`cake_tpu.ops.ring`) and decode reassembles exact softmax from
  per-shard partials. The reference hard-caps context at 4096 with no
  sequence parallelism at all (SURVEY.md §5) — on TPU this is the
  long-context axis.
- ``dp`` — data/batch parallelism for multi-stream serving (also absent in
  the single-request reference).

All collectives ride ICI when the mesh maps onto one slice; DCN only across
slices (mesh construction keeps axis order ``(dp, stage, sp, tp)`` so ``tp``
— the chattiest axis — lands on the innermost, fastest rings, with the
``sp`` ring next).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cake_tpu.models.config import LlamaConfig

DP, STAGE, SP, EP, TP = "dp", "stage", "sp", "ep", "tp"


def make_mesh(
    num_stages: int = 1,
    tp: int = 1,
    dp: int = 1,
    sp: int = 1,
    ep: int = 1,
    devices=None,
) -> Mesh:
    """Build a ``(dp, stage, sp, ep, tp)`` mesh from the flat device list.

    ``ep`` — expert parallelism (MoE families only): the expert axis of the
    routed-MLP weight stacks shards here and the combine psums over it
    (:mod:`cake_tpu.ops.moe`). Dense models leave it 1; every non-expert
    tensor is replicated over ep, so the axis is invisible to them."""
    devices = list(devices if devices is not None else jax.devices())
    need = num_stages * tp * dp * sp * ep
    if len(devices) < need:
        raise ValueError(
            f"need {need} devices for dp={dp} x stage={num_stages} x sp={sp} "
            f"x ep={ep} x tp={tp}, have {len(devices)}"
        )
    grid = np.array(devices[:need]).reshape(dp, num_stages, sp, ep, tp)
    return Mesh(grid, (DP, STAGE, SP, EP, TP))


def validate_shardable(config: LlamaConfig, num_stages: int, tp: int,
                       sp: int = 1, ep: int = 1) -> None:
    """Divisibility requirements for the (stage, sp, ep, tp) sharding."""
    family = config.family
    asked = {axis for axis, n in (("stages", num_stages), ("tp", tp),
                                  ("sp", sp), ("ep", ep)) if n > 1}
    if asked - family.shard_axes:
        only = ", ".join(f"--{axis}" for axis in sorted(family.shard_axes))
        raise ValueError(
            f"{family.what} runs as one stage with tp = 1, sp = 1" + (
                f"; only {only} shards it (its held experts)" if only
                else " and ep = 1; nothing shards it yet")
            + f": {family.shard_why}")
    if sp > 1 and config.max_seq_len % sp:
        raise ValueError(
            f"max_seq_len {config.max_seq_len} not divisible by sp {sp}"
        )
    if config.num_hidden_layers % num_stages:
        raise ValueError(
            f"num_hidden_layers {config.num_hidden_layers} not divisible by "
            f"stage count {num_stages}"
        )
    if ep > 1:
        experts = config.num_local_experts or config.n_routed_experts
        if not experts:
            raise ValueError(
                "ep > 1 requires an MoE config (num_local_experts > 0)"
            )
        if experts % ep:
            raise ValueError(
                f"num_local_experts {experts} not "
                f"divisible by ep {ep}"
            )
    for name, dim in [
        ("num_attention_heads", config.num_attention_heads),
        ("num_key_value_heads", config.num_key_value_heads),
        ("intermediate_size", config.intermediate_size),
        ("vocab_size", config.vocab_size),
    ]:
        if dim % tp:
            raise ValueError(f"{name} {dim} not divisible by tp {tp}")


def _rank(leaf) -> int:
    """Rank of a weight leaf (an int8 linear counts as its ``q``)."""
    return getattr(leaf, "q", leaf).ndim


def param_specs(params: dict | None = None) -> dict:
    """PartitionSpec pytree matching the params layout (models/llama.py):
    layer axis -> stage; head/intermediate out-features -> tp (column-
    parallel); wo/w_down in-features -> tp (row-parallel); norms and embed
    replicated; lm_head vocab -> tp. Family extensions: q/k/v biases shard
    with their projection's out-features (tp); an MoE layer's expert stacks
    ``[L, E, in, out]`` shard the expert axis over ep (router replicated —
    it is tiny and every rank routes every token).

    Pass ``params`` to get specs matching its structure where linears may be
    int8-quantized (ops.quant.QuantizedLinear): the q tensor takes the
    weight's spec, the per-output-channel scale takes the spec minus the
    in-features axis."""
    base = {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P(STAGE, None),
            "wq": P(STAGE, None, TP),
            "wk": P(STAGE, None, TP),
            "wv": P(STAGE, None, TP),
            "wo": P(STAGE, TP, None),
            "mlp_norm": P(STAGE, None),
            "w_gate": P(STAGE, None, TP),
            "w_up": P(STAGE, None, TP),
            "w_down": P(STAGE, TP, None),
        },
        "norm_f": P(None),
        "lm_head": P(None, TP),
    }
    if params is None:
        return base
    layers = params.get("layers", {})
    if layers and all(isinstance(v, dict) for v in layers.values()):
        # the latent family's stacks (models/llama.py layer_plan): one
        # stage, tp = 1, so every tensor is replicated but the held
        # experts, whose expert axis (third from last; a repeated
        # period's stacks lead with one axis more) shards over ep
        def stack_spec(stack):
            def spec(k, v):
                axes = [STAGE] + [None] * (_rank(v) - 1)
                if k in ("w_gate", "w_up", "w_down") and "router" in stack:
                    axes[-3] = EP
                return P(*axes)

            return {k: spec(k, v) for k, v in stack.items()}

        base["layers"] = {name: stack_spec(stack)
                          for name, stack in layers.items()}
    if "bq" in layers:
        base["layers"]["bq"] = P(STAGE, TP)
        base["layers"]["bk"] = P(STAGE, TP)
        base["layers"]["bv"] = P(STAGE, TP)
    if "bo" in layers:
        # applied after the tp psum -> replicated over tp
        base["layers"]["bo"] = P(STAGE, None)
    if "router" in layers:
        base["layers"]["router"] = P(STAGE, None, None)
        base["layers"]["w_gate"] = P(STAGE, EP, None, TP)
        base["layers"]["w_up"] = P(STAGE, EP, None, TP)
        base["layers"]["w_down"] = P(STAGE, EP, TP, None)
    # a family's tensors beside these (families.Family.extra_tensors: a
    # looped model's exit gate): small, replicated
    for name in params.keys() - base.keys():
        base[name] = jax.tree.map(lambda v: P(*([None] * v.ndim)),
                                  params[name])
    from cake_tpu.ops.quant import Quantized4Linear, QuantizedLinear

    def refine(p, s):
        if isinstance(p, dict):
            return {k: refine(p[k], s[k]) for k in p}
        if isinstance(p, QuantizedLinear):
            scale_spec = P(*(tuple(s)[:-2] + (s[-1],)))
            return QuantizedLinear(q=s, scale=scale_spec)
        if isinstance(p, Quantized4Linear):
            # The packed qp takes the weight's spec unchanged: adjacent-pair
            # packing (ops/quant.py) makes packed rows [a, b) the contiguous
            # original rows [2a, 2b), so in-axis (row-parallel tp) sharding
            # of the packed array is exactly the packing of the shard.
            # Per-channel scale [..., out] drops the in axis; a grouped
            # scale [..., ngroups, out] keeps the weight's spec verbatim —
            # its group axis lives along (and shards with) the in axis.
            if p.scale.ndim == p.qp.ndim:
                scale_spec = s
            else:
                scale_spec = P(*(tuple(s)[:-2] + (s[-1],)))
            return Quantized4Linear(qp=s, scale=scale_spec)
        return s

    return refine(params, base)


# KV cache [L, B, kv_heads, max_seq, head_dim]: layers over stage, batch over
# dp, kv heads over tp, sequence over sp — KV memory splits across all of
# stage, tp and sp, which is what lets 70B-class KV fit 16 GB chips.
CACHE_SPEC = P(STAGE, DP, TP, SP, None)


def cache_specs(kv_quant: str | None = None, batch_replicated: bool = False,
                held=()):
    """PartitionSpec pytree matching :func:`cake_tpu.ops.kvcache.init_cache`'s
    structure: plain buffers take CACHE_SPEC; int8 buffers take it for the
    q bytes and the same layout minus head_dim for the per-slot scales.

    ``batch_replicated``: don't shard the batch axis over dp — the layout of
    a single-row staging cache (continuous-batching admission) that must
    exist on every dp shard. ``held``: the kinds of ``LlamaConfig.
    cache_plan`` the cache holds beside rows (the plan itself will do).
    ``state``: recurrent layers' state ``[L, B, ...]`` (a delta-rule
    layer's ``[L, B, H, d_k, d_v]``, a state-space layer's ``[L, B,
    d_state, d_inner]``); ``conv``: a convolution's tail ``[L, B, taps - 1,
    C]`` (beside a state, or alone: a gated short convolution's); ``ring``:
    the window layers' rings ``[L, B, KH, R, D]``; ``index``: a sparse
    attention's index keys ``[L, B, 1, S, D]``; ``summary``: EVA
    attention's summary rows ``[L, B, KH, S // C, D]``. Each has its batch over
    dp and no later axis sharded (such a model runs as one stage with
    tp = 1)."""
    from cake_tpu.ops.kvcache import KVCache, QuantizedKV

    bd = None if batch_replicated else DP
    spec = P(STAGE, bd, TP, SP, None)
    if kv_quant == "int8":
        half = QuantizedKV(q=spec, scale=P(STAGE, bd, TP, SP))
        return KVCache(k=half, v=half)
    # what a stream holds whatever its length: the buffers of each kind
    buffers = {"state": ("state",), "conv": ("conv",),
               "ring": ("ring_k", "ring_v"), "index": ("index",),
               "summary": ("sum_k", "sum_v")}
    names = [n for kind, of in buffers.items() if kind in held for n in of]
    return KVCache(k=spec, v=spec, **dict.fromkeys(names, P(STAGE, bd)))


def shard_params(params: dict, mesh: Mesh) -> dict:
    """Place a (host or single-device) params pytree onto the mesh."""
    specs = param_specs(params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def shard_cache(cache, mesh: Mesh):
    from cake_tpu.ops.kvcache import QuantizedKV

    specs = cache_specs(
        "int8" if isinstance(cache.k, QuantizedKV) else None,
        held=[kind for kind, buf in (("state", cache.state),
                                     ("conv", cache.conv),
                                     ("ring", cache.ring_k),
                                     ("index", cache.index),
                                     ("summary", cache.sum_k))
              if buf is not None])
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), cache, specs
    )


# compiled cache-zeros programs, keyed by geometry — a fresh jit closure
# per call would re-trace and recompile on every invocation, stalling e.g.
# each continuous-batching admission behind a compile
_CACHE_PROGRAMS: dict = {}


def init_cache_on_mesh(config, mesh: Mesh, batch: int = 1,
                       max_seq: int | None = None, quant: str | None = None,
                       batch_replicated: bool = False):
    """Allocate a zeroed, mesh-sharded KV cache WITHOUT a host-side copy.

    ``shard_cache(init_cache(...))`` device_puts host zeros — invalid for
    shards this process cannot address on a multi-host pod (and a pointless
    host allocation even on one). Emitting the zeros from a compiled
    program with explicit output shardings allocates each shard directly on
    its owner device, on every host of the pod identically. Programs are
    memoized by (mesh, cache geometry), so repeat allocations — one per
    serving admission — reuse the compiled executable."""
    from functools import partial

    from cake_tpu.ops.kvcache import init_cache

    key = (mesh, tuple(config.cache_plan.items()), str(config.dtype), batch,
           max_seq or config.max_seq_len, quant, batch_replicated)
    make = _CACHE_PROGRAMS.get(key)
    if make is None:
        specs = cache_specs(quant, batch_replicated=batch_replicated,
                            held=config.cache_plan)
        out_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                              is_leaf=lambda x: isinstance(x, P))

        @partial(jax.jit, out_shardings=out_sh)
        def make():
            return init_cache(config, batch=batch, max_seq=max_seq,
                              quant=quant)

        _CACHE_PROGRAMS[key] = make
    return make()


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Resolved parallel layout for a model on a mesh."""

    mesh: Mesh
    num_stages: int
    tp: int
    dp: int
    sp: int = 1
    ep: int = 1

    @classmethod
    def build(cls, config: LlamaConfig, num_stages: int = 1, tp: int = 1,
              dp: int = 1, sp: int = 1, ep: int = 1,
              devices=None) -> "MeshPlan":
        validate_shardable(config, num_stages, tp, sp, ep)
        return cls(mesh=make_mesh(num_stages, tp, dp, sp, ep, devices),
                   num_stages=num_stages, tp=tp, dp=dp, sp=sp, ep=ep)

    @classmethod
    def from_topology(cls, config: LlamaConfig, topology, tp: int = 1,
                      dp: int = 1, sp: int = 1, ep: int = 1,
                      devices=None) -> "MeshPlan":
        """Derive the stage layout from a topology whose nodes carry mesh
        ``device`` indices.

        The single-program mesh pipeline shards the stacked layer axis
        *uniformly*, so the topology's ranges must be exactly that uniform
        split, in device order. Arbitrary/uneven layer ranges (which the
        reference allows, topology.rs:46-69) are served by the master/worker
        runtime instead; here they raise so a user's explicit placement is
        never silently replaced.
        """
        staged = sorted(
            (n for n in topology if n.device is not None),
            key=lambda n: n.device,
        )
        num_stages = max(1, len(staged))
        if staged:
            if [n.device for n in staged] != list(range(num_stages)):
                raise ValueError(
                    "topology device indices must be 0..S-1 with no gaps; got "
                    f"{[n.device for n in staged]}"
                )
            L = config.num_hidden_layers
            if L % num_stages:
                raise ValueError(
                    f"{L} layers not divisible into {num_stages} stages"
                )
            per = L // num_stages
            for s, node in enumerate(staged):
                want = list(range(s * per, (s + 1) * per))
                if node.layer_indices() != want:
                    raise ValueError(
                        f"mesh pipeline requires the uniform layer split: node "
                        f"'{node.name}' (device {s}) must own layers "
                        f"{want[0]}-{want[-1]}, got {node.layer_indices()}; "
                        "use the master/worker runtime for uneven ranges"
                    )
        return cls.build(config, num_stages=num_stages, tp=tp, dp=dp, sp=sp,
                         ep=ep, devices=devices)
