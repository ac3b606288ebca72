"""HF-checkpoint → params-pytree conversion and safetensors loading.

Equivalent of the reference's weight plane: mmap'd safetensors via the
`model.safetensors.index.json` weight_map (`utils/mod.rs:36-91`), with per-
layer tensors resolved by HF names (``model.layers.{i}.self_attn.q_proj`` …,
transformer.rs:30-38, attention.rs:92-109, mlp.rs:21-32).

Differences by design:

- HF stores linear weights ``[out, in]`` (torch Linear); the params pytree
  stores ``[in, out]`` so forward is ``x @ w`` with no transposes inside jit.
- Per-layer tensors are **stacked** into a single ``[num_layers, ...]`` array
  per weight name (the scan/pipeline layout, see models/llama.py).
- Loading accepts a layer *range* so a worker/pipeline stage loads only its
  topology-assigned slice (the reference worker loads only its own blocks,
  worker.rs:85-98; the splitter bundles are just a pre-filtered checkpoint).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Callable, Iterable

import jax.numpy as jnp
import numpy as np

# tensor names live with each family's record; the bare stack's re-exported
from cake_tpu.models.families import (  # noqa: F401
    _BIAS_MAP, _LAYER_MAP, _MOE_EXPERT_MAP, _MOE_ROUTER, _O_BIAS, FAMILIES)


def latent_stack_plan(config) -> dict:
    """Stack name -> ``(model layer ids, {ours: (HF suffix, transpose)},
    {ours: expert pattern})`` for a model whose layers are of several
    kinds (the latent family, a state-space hybrid): what both
    loaders and the writer walk. The ids are shaped as the stack leads
    (``[layers]``, or ``[repeats, layers]`` for a repeated period:
    ``models.llama.layer_plan``); ``models.llama.stack_shapes`` gives the
    shapes. Tensors of layers past the model's depth (a next-token
    prediction block) are never asked for."""
    from cake_tpu.models.llama import plan_segments, segment_shapes

    table, experts = config.family.tensor_names, config.family.expert_names
    plan = {}
    for run, seg in plan_segments(config):
        shapes = segment_shapes(config, seg)
        plan[seg.name] = (
            run.layer_ids(seg),
            {k: table[k] for k in shapes if k in table and (
                seg.ffn == "dense" or k not in experts)},
            experts if seg.ffn == "moe" else {})
    return plan


def hf_layout(ours: str, w: np.ndarray, transpose: bool) -> np.ndarray:
    """One layer's tensor as the checkpoint stores it: torch ``[out, in]``
    for a linear, ``[C, 1, K]`` for a depthwise convolution's taps."""
    if ours.startswith("conv_") and w.ndim == 2:
        return np.ascontiguousarray(w.T[:, None, :])
    return w.T if transpose else w


def hf_layer_map(num_experts: int = 0, attention_bias: bool = False,
                 o_bias: bool = False) -> dict:
    """The per-layer name map for a model family (the dense/bias-free base
    plus q/k/v biases and, for HF llama-arch ``attention_bias`` checkpoints,
    the o_proj bias; Mixtral expert tensors are handled separately because
    they stack over an expert axis)."""
    m = dict(_LAYER_MAP)
    if attention_bias:
        m.update(_BIAS_MAP)
    if o_bias:
        m[_O_BIAS[0]] = _O_BIAS[1]
    if num_experts:
        for k in ("w_gate", "w_up", "w_down"):
            del m[k]
    return m


def params_from_hf_tensors(
    get: Callable[[str], np.ndarray],
    num_layers: int,
    dtype="bfloat16",
    layer_range: tuple[int, int] | None = None,
    tie_word_embeddings: bool = False,
    include_embed: bool = True,
    include_head: bool = True,
    quantize: str | None = None,
    prequantized: bool = False,
    num_experts: int = 0,
    attention_bias: bool = False,
    o_bias: bool = False,
) -> dict:
    """Build the params pytree from a tensor lookup ``get(hf_name)``.

    ``num_experts``/``attention_bias`` select the model family's extra
    tensors (Mixtral routed experts / Qwen2 q-k-v biases — see
    ``hf_layer_map``); pass them from
    ``config.num_local_experts``/``config.attention_bias``.

    ``layer_range=(lo, hi)`` loads only blocks ``lo..hi-1`` (still stacked,
    dense from 0) — the worker/stage path.

    ``quantize="int8"``/``"int4"``/``"int4:gN"`` quantizes every linear *on
    the host as it streams in* (symmetric per-output-channel, ops.quant;
    int4 is packed two-per-byte; ``:gN`` selects N-row group-wise scales,
    int4's accuracy tier) — the bf16 weights never reach the device, so
    peak HBM is the quantized bytes. Norms and the embedding stay in
    ``dtype``. ``prequantized=True`` (a checkpoint written by
    tools/quantize_model: ``<name>.q8``/``.q4`` + ``<name>.scale`` tensors)
    reads the stored quantized bytes directly — a fraction of the IO, zero
    quantize compute; a grouped checkpoint's scale shape carries its own
    grouping, so plain ``"int4"`` loads it."""
    from cake_tpu.ops.quant import (
        LAYER_LINEARS,
        Quantized4Linear,
        QuantizedLinear,
        parse_quant_spec,
        quantize_linear4_np,
        quantize_linear_np,
    )

    tier, gsize = parse_quant_spec(quantize)
    if prequantized and tier is None:
        raise ValueError(
            "prequantized=True requires quantize='int8' or 'int4'"
        )

    lo, hi = layer_range or (0, num_layers)
    dt = jnp.dtype(dtype)

    _det: list = []  # lazy one-slot cache for _stored_group()

    def _stored_group() -> int | None:
        """The group size a pre-quantized int4 checkpoint was written at
        (None = per-channel), read off a stored scale's shape. Lazy: only
        probed when a tied head must match the layers' tier or an explicit
        :gN spec needs validation."""
        if not _det:
            try:
                name = f"model.layers.{lo}.self_attn.q_proj.weight"
                s = np.asarray(get(f"{name}.scale"))
                if s.ndim == 2:
                    in_dim = 2 * np.asarray(get(f"{name}.q4")).shape[1]
                    _det.append(in_dim // s.shape[0])
                else:
                    _det.append(None)
            except KeyError:
                _det.append(None)
        return _det[0]

    if prequantized and tier == "int4" and gsize is not None:
        stored = _stored_group()
        if stored != gsize:
            raise ValueError(
                f"checkpoint stores "
                f"{'group_size=' + str(stored) if stored else 'per-channel'}"
                f" int4, but quantize spec asked for g{gsize}"
            )

    def get_quant(name: str) -> tuple[np.ndarray, np.ndarray]:
        """(q [in, out] or qp [in/2, out] int8, scale f32) for one linear —
        stored pre-quantized or quantized here on the fly (a tied head
        reads the un-quantized embedding even in a pre-quantized
        checkpoint, at the checkpoint's OWN group size so both loaders
        stay bit-equal)."""
        if prequantized:
            suffix = ".q8" if tier == "int8" else ".q4"
            try:
                # stored in the HF [out, in] orientation (int4: [out, in/2]
                # packed along in) — transpose to the pytree layout; the
                # scale is stored in the pytree layout already
                return (np.asarray(get(f"{name}{suffix}")).T,
                        np.asarray(get(f"{name}.scale")))
            except KeyError:
                pass
        if tier == "int8":
            return quantize_linear_np(np.asarray(get(name)).T)
        g_eff = _stored_group() if prequantized else gsize
        return quantize_linear4_np(np.asarray(get(name)).T, group_size=g_eff)

    qcls = QuantizedLinear if tier == "int8" else Quantized4Linear

    if num_experts and tier == "int4":
        from cake_tpu.ops.quant import reject_int4_moe

        reject_int4_moe()

    params: dict = {}
    if hi > lo:
        layers = {}
        for ours, (suffix, transpose) in hf_layer_map(
            num_experts, attention_bias, o_bias
        ).items():
            do_quant = tier is not None and ours in LAYER_LINEARS
            per, scales = [], []
            for i in range(lo, hi):
                name = f"model.layers.{i}.{suffix}"
                if do_quant:
                    q, s = get_quant(name)
                    per.append(q)
                    scales.append(s)
                else:
                    w = np.asarray(get(name))
                    per.append(w.T if transpose else w)
            if do_quant:
                layers[ours] = qcls(
                    jnp.asarray(np.stack(per)),
                    jnp.asarray(np.stack(scales)),
                )
            else:
                layers[ours] = jnp.asarray(np.stack(per)).astype(dt)
        if num_experts:
            per_r = [
                np.asarray(get(f"model.layers.{i}.{_MOE_ROUTER}")).T
                for i in range(lo, hi)
            ]
            layers["router"] = jnp.asarray(np.stack(per_r)).astype(dt)
            for ours, pattern in _MOE_EXPERT_MAP.items():
                if tier == "int8":
                    # per-expert per-output-channel int8 (through get_quant
                    # so pre-quantized .q8 expert tensors load identically)
                    per_q, per_s = [], []
                    for i in range(lo, hi):
                        qs = [
                            get_quant(
                                f"model.layers.{i}.{pattern.format(e=e)}")
                            for e in range(num_experts)
                        ]
                        per_q.append(np.stack([q for q, _ in qs]))
                        per_s.append(np.stack([s for _, s in qs]))
                    layers[ours] = qcls(
                        jnp.asarray(np.stack(per_q)),  # [L, E, in, out]
                        jnp.asarray(np.stack(per_s)),  # [L, E, out]
                    )
                    continue
                per = [
                    np.stack([
                        np.asarray(
                            get(f"model.layers.{i}.{pattern.format(e=e)}")
                        ).T
                        for e in range(num_experts)
                    ])
                    for i in range(lo, hi)
                ]  # [L, E, in, out]
                layers[ours] = jnp.asarray(np.stack(per)).astype(dt)
        params["layers"] = layers
    if include_embed:
        params["embed"] = jnp.asarray(np.asarray(get("model.embed_tokens.weight"))).astype(dt)
    if include_head:
        params["norm_f"] = jnp.asarray(np.asarray(get("model.norm.weight"))).astype(dt)
        head_name = (
            "model.embed_tokens.weight" if tie_word_embeddings else "lm_head.weight"
        )
        if tier is not None:
            q, s = get_quant(head_name)
            params["lm_head"] = qcls(jnp.asarray(q), jnp.asarray(s))
        else:
            params["lm_head"] = jnp.asarray(np.asarray(get(head_name)).T).astype(dt)
    return params


def load_safetensors_index(model_dir: str | Path) -> dict[str, Path]:
    """Resolve tensor name -> shard file from ``model.safetensors.index.json``
    (utils/mod.rs:36-91), falling back to a single ``model.safetensors`` (the
    splitter also writes ``reduced.safetensors``)."""
    model_dir = Path(model_dir)
    index = model_dir / "model.safetensors.index.json"
    if index.exists():
        weight_map = json.loads(index.read_text())["weight_map"]
        return {name: model_dir / fname for name, fname in weight_map.items()}
    for candidate in ("model.safetensors", "reduced.safetensors"):
        f = model_dir / candidate
        if f.exists():
            from safetensors import safe_open

            with safe_open(f, framework="np") as sf:
                return {name: f for name in sf.keys()}
    raise FileNotFoundError(f"no safetensors index or file under {model_dir}")


def detect_tied_head(name_to_file: dict, model_dir, logger_name: str) -> bool:
    """True when the checkpoint stores NO lm_head.weight (plain or
    pre-quantized ``.q8``/``.q4``) — such a checkpoint can only be tied
    (Gemma, Llama-3.2-1B, Qwen2-small). Shared by both loaders; logs when
    it fires so an untied checkpoint with a broken index stays
    diagnosable."""
    import logging

    if any(n in name_to_file for n in (
            "lm_head.weight", "lm_head.weight.q8", "lm_head.weight.q4")):
        return False
    logging.getLogger(logger_name).info(
        "no stored lm_head.weight in %s — loading a tied head (the "
        "embedding); if this checkpoint is supposed to be untied, its "
        "index is incomplete", model_dir,
    )
    return True


def detect_family(name_to_file: dict) -> tuple[int, bool, bool]:
    """Detect a checkpoint's family tensors from its name index:
    ``(num_experts, attention_bias, o_bias)``. Zero/False for the Llama
    base. Keyed off the stored names themselves so no call site can
    silently drop a family's tensors by forgetting a flag."""
    import re

    bias = any(n.endswith("self_attn.q_proj.bias") for n in name_to_file)
    o_bias = any(n.endswith("self_attn.o_proj.bias") for n in name_to_file)
    experts = set()
    pat = re.compile(r"block_sparse_moe\.experts\.(\d+)\.")
    for n in name_to_file:
        m = pat.search(n)
        if m:
            experts.add(int(m.group(1)))
    return len(experts), bias, o_bias


def is_prequantized(name_to_file: dict) -> str | None:
    """Which tier tools/quantize_model wrote this checkpoint at: ``"int8"``
    (``.q8`` tensors), ``"int4"`` (``.q4``), or None (not pre-quantized).
    Truthy exactly when pre-quantized, so boolean use keeps working."""
    if any(n.endswith(".q8") for n in name_to_file):
        return "int8"
    if any(n.endswith(".q4") for n in name_to_file):
        return "int4"
    return None


def check_prequantized(name_to_file: dict, quantize: str | None) -> bool:
    """Detect a pre-quantized checkpoint and validate the requested load
    mode against it (shared by the host and direct-to-mesh loaders)."""
    from cake_tpu.ops.quant import parse_quant_spec

    pre = is_prequantized(name_to_file)
    tier, _ = parse_quant_spec(quantize)
    if pre and tier != pre:
        raise ValueError(
            f"this checkpoint is pre-quantized ({pre} .q8/.q4/.scale "
            f"tensors); load it with quantize='{pre}' (--quantize {pre})"
        )
    return bool(pre)


def load_llama_params(
    model_dir: str | Path,
    num_layers: int,
    dtype="bfloat16",
    layer_range: tuple[int, int] | None = None,
    tie_word_embeddings: bool = False,
    include_embed: bool = True,
    include_head: bool = True,
    quantize: str | None = None,
    num_experts: int | None = None,
    attention_bias: bool | None = None,
    o_bias: bool | None = None,
) -> dict:
    """Load a Llama-family checkpoint directory into the params pytree.

    Shards are opened lazily with ``safetensors.safe_open`` (zero-copy mmap,
    the equivalent of VarBuilder::from_mmaped_safetensors, cake/mod.rs:100-101)
    and only requested tensors are materialized — a worker loading 4 of 32
    layers reads only those bytes. Pre-quantized checkpoints
    (tools/quantize_model) are detected automatically, and so are the model
    family's extra tensors (Qwen2 q/k/v biases, Mixtral experts) via
    :func:`detect_family` — pass ``num_experts``/``attention_bias`` only to
    override the detection.
    """
    from safetensors import safe_open

    name_to_file = load_safetensors_index(model_dir)
    probes = [f.probe for f in FAMILIES if f.probe]
    if any(p in n for n in name_to_file for p in probes):
        # a family of several layer stacks (its checkpoint stores a tensor
        # only it has) has one loader, experts by global id: the
        # direct-to-mesh one, on a mesh of one device
        if layer_range is not None or not (include_embed and include_head):
            raise NotImplementedError(
                "a latent-attention checkpoint loads whole (no layer "
                "ranges: the family runs as one stage)")
        from cake_tpu.models.config import LlamaConfig
        from cake_tpu.parallel.mesh import make_mesh
        from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

        config = LlamaConfig.from_hf_json(Path(model_dir) / "config.json",
                                          dtype=str(jnp.dtype(dtype)))
        return load_llama_params_on_mesh(
            model_dir, config, make_mesh(), quantize=quantize,
            tie_word_embeddings=tie_word_embeddings)
    det_experts, det_bias, det_o = detect_family(name_to_file)
    if num_experts is None:
        num_experts = det_experts
    if attention_bias is None:
        attention_bias = det_bias
    if o_bias is None:
        o_bias = det_o
    if (include_head and not tie_word_embeddings
            and detect_tied_head(name_to_file, model_dir,
                                 "cake_tpu.weights")):
        tie_word_embeddings = True
    handles: dict[Path, object] = {}

    def get(name: str) -> np.ndarray:
        f = name_to_file[name]
        if f not in handles:
            handles[f] = safe_open(f, framework="np")
        return handles[f].get_tensor(name)

    try:
        return params_from_hf_tensors(
            get,
            num_layers,
            dtype=dtype,
            layer_range=layer_range,
            tie_word_embeddings=tie_word_embeddings,
            include_embed=include_embed,
            include_head=include_head,
            quantize=quantize,
            prequantized=check_prequantized(name_to_file, quantize),
            num_experts=num_experts,
            attention_bias=attention_bias,
            o_bias=o_bias,
        )
    finally:
        for h in handles.values():
            if hasattr(h, "close"):
                h.close()
            elif hasattr(h, "__exit__"):
                h.__exit__(None, None, None)


def latent_hf_tensors(params: dict, config) -> dict[str, np.ndarray]:
    """A params pytree of several layer stacks (the latent family, a
    state-space hybrid) as Hugging Face tensors (torch ``[out, in]``), the
    held experts under their global ids: what :func:`save_llama_params`
    writes and the plain references (``cake_tpu/testing/reference_*.py``)
    read. A tied head is not stored."""
    def stored(w, fold=None):
        """``w`` as the checkpoint stores it (``families.Fold``)."""
        if fold is None:
            return w
        return fold.save(config, w.astype(np.float32)).astype(w.dtype)

    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        config.family.final_norm: stored(np.asarray(params["norm_f"]),
                                         config.family.final_norm_fold),
    }
    if not config.tie_word_embeddings:
        head = np.asarray(params["lm_head"]).T
        # a head of several prediction blocks (EvaByte's ``num_pred_heads``):
        # the program holds block 0, the model's own next token; the file
        # keeps the stored shape, the other blocks zero
        tensors["lm_head.weight"] = np.concatenate(
            [head] + [np.zeros_like(head)] * (config.num_pred_heads - 1))
    for ours, parts in config.family.extra_tensors.items():
        for part, (name, _) in parts.items():
            tensors[name] = np.asarray(params[ours][part])
    for stack, (ids, plain, experts) in latent_stack_plan(config).items():
        flat = ids.reshape(-1)
        for ours, (suffix, transpose, *fold) in plain.items():
            stacked = np.asarray(params["layers"][stack][ours])
            stacked = stacked.reshape((flat.size,) + stacked.shape[ids.ndim:])
            for i, layer in enumerate(flat):
                tensors[f"model.layers.{layer}.{suffix}"] = hf_layout(
                    ours, stored(stacked[i], *fold), transpose)
        for ours, pattern in experts.items():
            stacked = np.asarray(params["layers"][stack][ours])
            stacked = stacked.reshape((flat.size,) + stacked.shape[ids.ndim:])
            for i, layer in enumerate(flat):
                for e in range(stacked.shape[1]):
                    name = pattern.format(e=config.first_expert + e)
                    tensors[f"model.layers.{layer}.{name}"] = (
                        stacked[i, e].T)
    return tensors


def save_llama_params(params: dict, model_dir: str | Path,
                      num_layers: int | None = None, config=None):
    """Write a params pytree back to HF-format safetensors (test fixtures and
    the splitter round-trip). Inverse of :func:`load_llama_params`. A
    latent-family pytree (a dict of layer stacks) needs its ``config``."""
    from safetensors.numpy import save_file

    model_dir = Path(model_dir)
    model_dir.mkdir(parents=True, exist_ok=True)
    if "wq" not in params["layers"]:
        if config is None:
            raise ValueError("saving a latent-family pytree needs config=")
        return _save_tensors(latent_hf_tensors(params, config), model_dir)
    tensors: dict[str, np.ndarray] = {}
    if "embed" in params:
        tensors["model.embed_tokens.weight"] = np.asarray(params["embed"])
    if "norm_f" in params:
        tensors["model.norm.weight"] = np.asarray(params["norm_f"])
        tensors["lm_head.weight"] = np.asarray(params["lm_head"]).T
    L = params["layers"]["wq"].shape[0] if num_layers is None else num_layers
    layers = params["layers"]
    moe = "router" in layers
    fam_map = hf_layer_map(
        num_experts=layers["w_gate"].shape[1] if moe else 0,
        attention_bias="bq" in layers,
        o_bias="bo" in layers,
    )
    for ours, (suffix, transpose) in fam_map.items():
        stacked = np.asarray(layers[ours])
        for i in range(L):
            w = stacked[i]
            tensors[f"model.layers.{i}.{suffix}"] = w.T if transpose else np.ascontiguousarray(w)
    if moe:
        router = np.asarray(layers["router"])  # [L, H, E]
        E = router.shape[-1]
        for i in range(L):
            tensors[f"model.layers.{i}.{_MOE_ROUTER}"] = router[i].T
        # materialize ONE expert stack to host at a time (for a
        # Mixtral-scale pytree each [L, E, in, out] leaf is tens of GB;
        # holding all three at once would triple peak host RAM)
        for ours, pattern in _MOE_EXPERT_MAP.items():
            stacked = np.asarray(layers[ours])
            for i in range(L):
                for e in range(E):
                    # real copy (not a .T view) so `del stacked` frees the
                    # stack before the next one materializes
                    tensors[
                        f"model.layers.{i}.{pattern.format(e=e)}"
                    ] = np.ascontiguousarray(stacked[i, e].T)
            del stacked
    return _save_tensors(tensors, model_dir)


def _save_tensors(tensors: dict, model_dir: Path) -> Path:
    from safetensors.numpy import save_file

    out = model_dir / "model.safetensors"
    # bf16 numpy isn't universally supported by safetensors.numpy; store f32
    tensors = {k: np.ascontiguousarray(v, dtype=np.float32) for k, v in tensors.items()}
    save_file(tensors, out)
    index = {
        "metadata": {"total_size": int(sum(v.nbytes for v in tensors.values()))},
        "weight_map": {k: "model.safetensors" for k in tensors},
    }
    (model_dir / "model.safetensors.index.json").write_text(json.dumps(index))
    return out


def write_random_q8_checkpoint(config, model_dir: str | Path, seed: int,
                               workers: int = 4) -> dict:
    """Write a seeded random-weight checkpoint in the pre-quantized int8
    layout (tools/quantize_model: ``<hf_name>.q8`` + ``.scale``), one
    safetensors file per layer, streaming: host RAM holds ``workers``
    layers of int8 bytes plus a few 512-channel float32 chunks, never the
    model. :func:`save_llama_params` materializes the whole pytree and
    writes float32 into one file — 29 GB at Mistral-7B size; this is what
    lets chip_smoke.py put a full-width model in front of the real loaders
    (``load_llama_params_on_mesh``) in about a minute.

    Weights follow :func:`cake_tpu.models.llama.init_params_int8`: each
    linear is ``normal / sqrt(fan_in)`` quantized by the one convention
    (``quantize_linear_np``, applied to 512 output channels at a time —
    its scale is per output channel, so chunking changes nothing), norms
    are ones. Layer ``i`` draws from ``default_rng([seed, i])``, so the
    bytes depend only on (config, seed), not on ``workers``. Dense
    bias-free families only. Returns ``{"bytes", "files", "tensors"}``."""
    from concurrent.futures import ThreadPoolExecutor

    from safetensors.numpy import save_file

    from cake_tpu.models.llama import layer_shapes
    from cake_tpu.ops.quant import LAYER_LINEARS, quantize_linear_np

    if config.num_local_experts or config.attention_bias:
        raise NotImplementedError(
            "write_random_q8_checkpoint covers the dense bias-free "
            "families (Llama, Mistral)")
    model_dir = Path(model_dir)
    model_dir.mkdir(parents=True, exist_ok=True)
    h = config.hidden_size
    # logical (in, out) sizes of the per-layer linears
    shapes = layer_shapes(config)
    linears = {name: shapes[name](config) for name in LAYER_LINEARS}

    def q8(rng, name: str, fan_in: int, out: int) -> dict:
        # stored in the HF [out, in] orientation
        q = np.empty((out, fan_in), np.int8)
        scale = np.empty((out,), np.float32)
        inv = np.float32(1.0 / np.sqrt(fan_in))
        for lo in range(0, out, 512):
            hi = min(out, lo + 512)
            w = rng.standard_normal((fan_in, hi - lo), dtype=np.float32)
            w *= inv
            qc, scale[lo:hi] = quantize_linear_np(w)
            q[lo:hi] = qc.T
        return {f"{name}.q8": q, f"{name}.scale": scale}

    def write(fname: str, tensors: dict) -> tuple[str, list, int]:
        save_file(tensors, model_dir / fname)
        return fname, list(tensors), sum(t.nbytes for t in tensors.values())

    def layer(i: int):
        rng = np.random.default_rng([seed, i])
        ones = np.ones((h,), np.float32)
        tensors = {f"model.layers.{i}.{_LAYER_MAP['attn_norm'][0]}": ones,
                   f"model.layers.{i}.{_LAYER_MAP['mlp_norm'][0]}": ones}
        for ours, (fan_in, out) in linears.items():
            tensors.update(q8(
                rng, f"model.layers.{i}.{_LAYER_MAP[ours][0]}", fan_in, out))
        return write(f"model-layer-{i:05d}.safetensors", tensors)

    def ends():
        rng = np.random.default_rng([seed, config.num_hidden_layers])
        embed = rng.standard_normal((config.vocab_size, h), dtype=np.float32)
        embed *= np.float32(1.0 / np.sqrt(h))
        tensors = {"model.embed_tokens.weight": embed,
                   "model.norm.weight": np.ones((h,), np.float32)}
        tensors.update(q8(rng, "lm_head.weight", h, config.vocab_size))
        return write("model-ends.safetensors", tensors)

    with ThreadPoolExecutor(max_workers=workers) as pool:
        jobs = [pool.submit(ends)] + [
            pool.submit(layer, i) for i in range(config.num_hidden_layers)]
        done = [j.result() for j in jobs]
    weight_map = {name: fname for fname, names, _ in done for name in names}
    total = sum(n for _, _, n in done)
    (model_dir / "model.safetensors.index.json").write_text(json.dumps({
        "metadata": {"total_size": total, "cake_quant": "int8"},
        "weight_map": weight_map,
    }))
    (model_dir / "config.json").write_text(json.dumps(config.to_hf_dict()))
    return {"bytes": total, "files": len(done), "tensors": len(weight_map)}
