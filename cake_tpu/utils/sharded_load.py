"""Direct-to-mesh checkpoint loading: each shard's bytes, nothing more.

The reference worker loads ONLY its topology-assigned blocks' weights
(`cake-core/src/cake/worker.rs:85-98`); this is the mesh-path equivalent.
:func:`load_llama_params_on_mesh` assembles the sharded params pytree with
``jax.make_array_from_callback``: every *addressable* shard's bytes are read
straight out of the mmap'd safetensors (``safe_open(...).get_slice``) and
placed on its device — there is never a full-model host copy, and on a
multi-host pod each host reads only the layer ranges its local devices'
stages own. Contrast ``load_llama_params`` + ``shard_params``, which builds
the entire pytree on host first (~70 GB host RAM for 70B int8, with
full-model quantize time, on *every* host).

Quantize-on-load (``quantize="int8"``/``"int4"``) stays shard-local where
the math allows: column-parallel linears (wq/wk/wv/w_gate/w_up, and lm_head)
shard out-features, and the per-output-channel scale depends only on the
full in-axis — present in every shard — so quantizing the column slice
equals quantizing the full weight and slicing. Row-parallel linears
(wo/w_down) shard the in-axis, so their callbacks read the full
``[in, out]`` layer weight, quantize, and slice — one layer at a time,
never the whole stage. For int4 the *packed* row axis is what shards:
adjacent-pair packing (ops/quant.py) keeps every packed-row range a
contiguous original-row range, so the reads stay single slices.
"""

from __future__ import annotations

import itertools
import logging

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cake_tpu.models.config import LlamaConfig
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.parallel.mesh import EP, STAGE, TP
from cake_tpu.utils.weights import (
    _BIAS_MAP,
    _LAYER_MAP,
    _MOE_EXPERT_MAP,
    _MOE_ROUTER,
    detect_family,
    detect_tied_head,
    hf_layer_map,
    load_safetensors_index,
)

# column-parallel: out-features shard over tp, in-axis full per shard
_COL_PARALLEL = ("wq", "wk", "wv", "w_gate", "w_up")
# row-parallel: in-features shard over tp (scale needs the full in-axis)
_ROW_PARALLEL = ("wo", "w_down")


class CheckpointReader:
    """Sliced mmap reads over a safetensors checkpoint, with byte
    accounting (``bytes_read``) so tests can assert a stage loads ~1/S of
    the model."""

    def __init__(self, model_dir):
        self.name_to_file = load_safetensors_index(model_dir)
        self._handles: dict = {}
        self.bytes_read = 0

    def _slice(self, name: str):
        from safetensors import safe_open

        f = self.name_to_file[name]
        h = self._handles.get(f)
        if h is None:
            h = self._handles[f] = safe_open(f, framework="np")
        return h.get_slice(name)

    def read1d(self, name: str, sl: slice = slice(None)) -> np.ndarray:
        out = np.asarray(self._slice(name)[sl])
        self.bytes_read += out.nbytes
        return out

    def read2d(self, name: str, rows: slice, cols: slice,
               transpose: bool) -> np.ndarray:
        """Logical ``[rows, cols]`` slice; ``transpose=True`` when the
        checkpoint stores the torch ``[out, in]`` layout and the logical
        layout is ``[in, out]``."""
        sl = self._slice(name)
        if len(sl.get_shape()) == 3:
            # a depthwise convolution's taps, torch's [C, 1, K]
            out = np.asarray(sl[cols, :, rows])[:, 0, :].T
        elif transpose:
            out = np.asarray(sl[cols, rows]).T
        else:
            out = np.asarray(sl[rows, cols])
        self.bytes_read += out.nbytes
        return out

    def shape(self, name: str) -> tuple:
        """Stored shape without reading tensor bytes."""
        return tuple(self._slice(name).get_shape())

    def close(self) -> None:
        for h in self._handles.values():
            if hasattr(h, "close"):
                h.close()
        self._handles.clear()


def _np_dtype(dtype) -> np.dtype:
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16 if str(dtype) == "bfloat16" else dtype)


def _memo(cb):
    cache: dict = {}

    def wrapped(index):
        key = tuple((s.start, s.stop, s.step) for s in index)
        if key not in cache:
            cache[key] = cb(index)
        return cache[key]

    return wrapped


def _assemble(shape, mesh: Mesh, spec: P, cb):
    return jax.make_array_from_callback(
        tuple(shape), NamedSharding(mesh, spec), _memo(cb)
    )


def load_llama_params_on_mesh(
    model_dir,
    config: LlamaConfig,
    mesh: Mesh,
    quantize: str | None = None,
    tie_word_embeddings: bool = False,
) -> dict:
    """Load a checkpoint directory directly into mesh-sharded global arrays
    (the layout of :func:`cake_tpu.parallel.mesh.param_specs`). Bitwise
    equal to ``shard_params(load_llama_params(...), mesh)`` — tested — but
    reads only addressable shards' bytes and holds at most one layer weight
    of host scratch at a time."""
    from cake_tpu.ops.quant import (
        Quantized4Linear,
        QuantizedLinear,
        pack_int4_np,
        parse_quant_spec,
        quantize_linear4_np,
        quantize_linear_np,
    )
    from cake_tpu.utils.weights import check_prequantized

    tier, gsize = parse_quant_spec(quantize)
    int4 = tier == "int4"
    # tier plumbing: stored-tensor suffix, host quantizer, quantized class,
    # and the packed-row factor (int4 stores K/2 rows per K in-features)
    qsuffix = ".q4" if int4 else ".q8"
    np_qfn = quantize_linear4_np if int4 else quantize_linear_np
    qcls = Quantized4Linear if int4 else QuantizedLinear
    qmax = 7 if int4 else 127
    krows = 2 if int4 else 1  # original rows per stored quantized row

    reader = CheckpointReader(model_dir)
    if config.segmented:
        try:
            return _load_latent_on_mesh(reader, model_dir, config, mesh,
                                        quantize, tie_word_embeddings)
        finally:
            reader.close()
    num_experts, attention_bias, o_bias = detect_family(reader.name_to_file)
    if not tie_word_embeddings and detect_tied_head(
            reader.name_to_file, model_dir, "cake_tpu.sharded_load"):
        tie_word_embeddings = True
    if num_experts and int4:
        from cake_tpu.ops.quant import reject_int4_moe

        reject_int4_moe()
    prequantized = check_prequantized(reader.name_to_file, quantize)
    # Grouped int4 (the accuracy tier): the direct-to-mesh path supports it
    # for PRE-QUANTIZED checkpoints (stored [ngroups, out] scales slice
    # like any tensor); on-the-fly grouped quantize would re-read full
    # weights per shard for no benefit over quantizing once offline.
    group = None  # in-rows per scale group, detected from the checkpoint
    if int4 and prequantized:
        probe = f"model.layers.0.{_LAYER_MAP['wq'][0]}.scale"
        if probe in reader.name_to_file:
            sshape = reader.shape(probe)
            if len(sshape) == 2:
                group = config.hidden_size // sshape[0]
    if gsize is not None and not prequantized:
        raise ValueError(
            "grouped int4 quantize-on-load is not supported on the "
            "direct-to-mesh path; pre-quantize once with "
            "`python -m cake_tpu.tools.quantize_model --bits 4 "
            f"--group-size {gsize}` and load that checkpoint"
        )
    if gsize is not None and prequantized and gsize != group:
        # covers both a different stored group size AND a per-channel
        # checkpoint (group None) — never silently drop a requested tier
        raise ValueError(
            f"checkpoint stores "
            f"{'group_size=' + str(group) if group else 'per-channel'} "
            f"int4, but quantize spec asked for g{gsize}"
        )
    dt = _np_dtype(config.dtype)
    L = config.num_hidden_layers
    h = config.hidden_size
    d = config.head_dim  # explicit per-head width (Gemma: heads*d != h)
    shapes = {
        "attn_norm": (L, h),
        "wq": (L, h, config.num_attention_heads * d),
        "wk": (L, h, config.num_key_value_heads * d),
        "wv": (L, h, config.num_key_value_heads * d),
        "wo": (L, config.num_attention_heads * d, h),
        "mlp_norm": (L, h),
        "w_gate": (L, h, config.intermediate_size),
        "w_up": (L, h, config.intermediate_size),
        "w_down": (L, config.intermediate_size, h),
    }

    def norm_cb(suffix):
        def cb(index):
            lsl, _ = index
            lo, hi, _ = lsl.indices(L)
            return np.stack([
                reader.read1d(f"model.layers.{i}.{suffix}")
                for i in range(lo, hi)
            ]).astype(dt)

        return cb

    def linear_cb(suffix, transpose):
        def cb(index):
            lsl, rsl, csl = index
            lo, hi, _ = lsl.indices(L)
            return np.stack([
                reader.read2d(f"model.layers.{i}.{suffix}", rsl, csl,
                              transpose)
                for i in range(lo, hi)
            ]).astype(dt)

        return cb

    # Per-(tensor, column-range) scale memo. Scales are tiny ([out] f32 per
    # layer) but cost a weight read to compute — the memo means each weight
    # is read for quantization context exactly once per distinct need:
    # row-parallel shards read one full weight for the scale, then only
    # their own row slices; the scale leaf's callbacks are pure memo hits.
    scale_memo: dict[tuple, np.ndarray] = {}

    def _key(name: str, csl: slice) -> tuple:
        return (name, csl.start, csl.stop)

    def _scale(name: str, transpose: bool, csl: slice) -> np.ndarray:
        """Scale for columns ``csl`` (full in-axis — exact by construction)."""
        key = _key(name, csl)
        if key not in scale_memo:
            full = _key(name, slice(None))
            if full in scale_memo:
                scale_memo[key] = scale_memo[full][csl]
            else:
                w = reader.read2d(name, slice(None), csl, transpose)
                scale_memo[key] = np_qfn(w)[1]
        return scale_memo[key]

    def quant_q_cb(suffix, transpose, row_parallel, kdim):
        def cb(index):
            lsl, rsl, csl = index
            lo, hi, _ = lsl.indices(L)
            # int4 shards the PACKED row axis: stored rows [a, b) are the
            # contiguous original rows [2a, 2b) (adjacent-pair packing,
            # ops/quant.py), so the weight read stays one contiguous slice
            a, b, _ = rsl.indices(kdim // krows)
            wr = slice(a * krows, b * krows)
            per = []
            for i in range(lo, hi):
                name = f"model.layers.{i}.{suffix}"
                if prequantized:
                    # stored quantized bytes in the HF [out, in(/2)]
                    # orientation: read exactly this shard's slice
                    per.append(
                        reader.read2d(f"{name}{qsuffix}", rsl, csl, True))
                elif row_parallel:
                    # scale needs the full in-axis (memoized: one full read
                    # per layer, shared across tp shards and the scale
                    # leaf); the quantized bytes then need only this
                    # shard's rows
                    s = _scale(name, transpose, csl)
                    w = reader.read2d(name, wr, csl, transpose)
                    q = np.clip(
                        np.round(np.asarray(w, np.float32) / s),
                        -qmax, qmax).astype(np.int8)
                    if int4:
                        q = pack_int4_np(q)
                    per.append(q)
                else:
                    q, s = np_qfn(reader.read2d(name, wr, csl, transpose))
                    scale_memo.setdefault(_key(name, csl), s)
                    per.append(q)
            return np.stack(per)

        return cb

    def quant_scale_cb(suffix, transpose):
        def cb(index):
            if group is not None:
                # grouped scale leaf [L, ngroups, out]: stored exactly so
                lsl, gsl, csl = index
                lo, hi, _ = lsl.indices(L)
                return np.stack([
                    reader.read2d(f"model.layers.{i}.{suffix}.scale",
                                  gsl, csl, False)
                    for i in range(lo, hi)
                ])
            lsl, csl = index
            lo, hi, _ = lsl.indices(L)
            if prequantized:
                return np.stack([
                    reader.read1d(f"model.layers.{i}.{suffix}.scale", csl)
                    for i in range(lo, hi)
                ])
            return np.stack([
                _scale(f"model.layers.{i}.{suffix}", transpose, csl)
                for i in range(lo, hi)
            ])

        return cb

    try:
        layers: dict = {}
        for ours, (suffix, transpose) in hf_layer_map(
            num_experts, attention_bias, o_bias
        ).items():
            if ours == "bo":
                # o_proj bias [L, hidden]: applied after the tp psum, so
                # replicated like the norms
                layers[ours] = _assemble((L, h), mesh, P(STAGE, None),
                                         norm_cb(suffix))
                continue
            if ours in _BIAS_MAP:
                # q/k/v bias [L, out]: shards with the projection's
                # out-features (column-parallel tp)
                out_dim = shapes[ours.replace("b", "w", 1)][2]

                def bias_cb(sfx):
                    def cb(index):
                        lsl, csl = index
                        lo, hi, _ = lsl.indices(L)
                        return np.stack([
                            reader.read1d(f"model.layers.{i}.{sfx}", csl)
                            for i in range(lo, hi)
                        ]).astype(dt)

                    return cb

                layers[ours] = _assemble((L, out_dim), mesh, P(STAGE, TP),
                                         bias_cb(suffix))
                continue
            shape = shapes[ours]
            if len(shape) == 2:
                layers[ours] = _assemble(shape, mesh, P(STAGE, None),
                                         norm_cb(suffix))
                continue
            spec = (P(STAGE, TP, None) if ours in _ROW_PARALLEL
                    else P(STAGE, None, TP))
            if tier is not None:
                qshape = (L, shape[1] // krows, shape[2])
                if group is not None:
                    # grouped scale [L, ngroups, out] takes the weight's
                    # spec — the group axis lives along (and shards with)
                    # the in axis (mesh.param_specs, same rule)
                    scale_spec = spec
                    scale_shape = (L, shape[1] // group, shape[2])
                else:
                    scale_spec = (P(STAGE, None) if ours in _ROW_PARALLEL
                                  else P(STAGE, TP))
                    scale_shape = (L, shape[2])
                layers[ours] = qcls(
                    _assemble(qshape, mesh, spec,
                              quant_q_cb(suffix, transpose,
                                         ours in _ROW_PARALLEL, shape[1])),
                    _assemble(scale_shape, mesh, scale_spec,
                              quant_scale_cb(suffix, transpose)),
                )
            else:
                layers[ours] = _assemble(shape, mesh, spec,
                                         linear_cb(suffix, transpose))

        if num_experts:
            # router [L, H, E]: tiny, replicated (every rank routes every
            # token); expert stacks [L, E, in, out]: expert axis over ep,
            # features over tp like the dense MLP
            def router_cb(index):
                lsl, rsl, csl = index
                lo, hi, _ = lsl.indices(L)
                return np.stack([
                    reader.read2d(f"model.layers.{i}.{_MOE_ROUTER}",
                                  rsl, csl, True)
                    for i in range(lo, hi)
                ]).astype(dt)

            layers["router"] = _assemble((L, h, num_experts), mesh,
                                         P(STAGE, None, None), router_cb)

            def expert_cb(pattern):
                def cb(index):
                    lsl, esl, rsl, csl = index
                    lo, hi, _ = lsl.indices(L)
                    e_lo, e_hi, _ = esl.indices(num_experts)
                    return np.stack([
                        np.stack([
                            reader.read2d(
                                f"model.layers.{i}."
                                f"{pattern.format(e=e)}", rsl, csl, True)
                            for e in range(e_lo, e_hi)
                        ])
                        for i in range(lo, hi)
                    ]).astype(dt)

                return cb

            def expert_quant_q_cb(pattern, row_parallel):
                """Expert int8 bytes [L', E', rows, cols] — same
                shard-local-exactness rules as the dense linears: column-
                parallel quantizes the column slice directly (scale needs
                only the full in-axis, present per shard); row-parallel
                reads the full in-axis once per (layer, expert) for the
                memoized scale, then only its own rows."""
                def cb(index):
                    lsl, esl, rsl, csl = index
                    lo, hi, _ = lsl.indices(L)
                    e_lo, e_hi, _ = esl.indices(num_experts)
                    per = []
                    for i in range(lo, hi):
                        rows_e = []
                        for e in range(e_lo, e_hi):
                            name = (f"model.layers.{i}."
                                    f"{pattern.format(e=e)}")
                            if prequantized:
                                rows_e.append(reader.read2d(
                                    f"{name}{qsuffix}", rsl, csl, True))
                            elif row_parallel:
                                s = _scale(name, True, csl)
                                w = reader.read2d(name, rsl, csl, True)
                                rows_e.append(np.clip(
                                    np.round(np.asarray(w, np.float32) / s),
                                    -qmax, qmax).astype(np.int8))
                            else:
                                q, s = np_qfn(
                                    reader.read2d(name, rsl, csl, True))
                                scale_memo.setdefault(_key(name, csl), s)
                                rows_e.append(q)
                        per.append(np.stack(rows_e))
                    return np.stack(per)

                return cb

            def expert_scale_cb(pattern):
                def cb(index):
                    lsl, esl, csl = index
                    lo, hi, _ = lsl.indices(L)
                    e_lo, e_hi, _ = esl.indices(num_experts)
                    per = []
                    for i in range(lo, hi):
                        rows_e = []
                        for e in range(e_lo, e_hi):
                            name = (f"model.layers.{i}."
                                    f"{pattern.format(e=e)}")
                            if prequantized:
                                rows_e.append(
                                    reader.read1d(f"{name}.scale", csl))
                            else:
                                rows_e.append(_scale(name, True, csl))
                        per.append(np.stack(rows_e))
                    return np.stack(per)

                return cb

            fdim = config.intermediate_size
            for ours, (din, dout, spec) in {
                "w_gate": (h, fdim, P(STAGE, EP, None, TP)),
                "w_up": (h, fdim, P(STAGE, EP, None, TP)),
                "w_down": (fdim, h, P(STAGE, EP, TP, None)),
            }.items():
                pattern = _MOE_EXPERT_MAP[ours]
                if tier == "int8":
                    row_par = ours == "w_down"
                    scale_spec = (P(STAGE, EP, None) if row_par
                                  else P(STAGE, EP, TP))
                    layers[ours] = qcls(
                        _assemble((L, num_experts, din, dout), mesh, spec,
                                  expert_quant_q_cb(pattern, row_par)),
                        _assemble((L, num_experts, dout), mesh, scale_spec,
                                  expert_scale_cb(pattern)),
                    )
                else:
                    layers[ours] = _assemble(
                        (L, num_experts, din, dout), mesh, spec,
                        expert_cb(pattern),
                    )

        embed_name = "model.embed_tokens.weight"
        head_name = embed_name if tie_word_embeddings else "lm_head.weight"
        params: dict = {"layers": layers}
        params["embed"] = _assemble(
            (config.vocab_size, h), mesh, P(None, None),
            lambda index: reader.read2d(embed_name, index[0], index[1],
                                        False).astype(dt),
        )
        params["norm_f"] = _assemble(
            (h,), mesh, P(None),
            lambda index: reader.read1d("model.norm.weight",
                                        index[0]).astype(dt),
        )
        if tier is not None:
            # lm_head is column-parallel over vocab: shard-local quantize
            # is exact (full in-axis per shard); its scales ride the same
            # memo so the scale leaf re-reads nothing. A tied head has no
            # stored .q8/.q4 (the embedding stays full-precision) and falls
            # back to on-the-fly quantize — at the checkpoint's detected
            # group size, so the head matches the layers' tier.
            head_prequant = (
                prequantized
                and f"{head_name}{qsuffix}" in reader.name_to_file
            )

            # one read + one quantize per column range for the grouped
            # tied-head fallback — head_q and head_scale share the result
            # (the grouped analog of scale_memo; both specs are P(None, TP),
            # so the row axis is always full and columns key the memo)
            head_g_memo: dict[tuple, tuple] = {}

            def _head_grouped(csl: slice) -> tuple:
                key = (csl.start, csl.stop)
                if key not in head_g_memo:
                    w = reader.read2d(head_name, slice(0, h), csl, True)
                    head_g_memo[key] = quantize_linear4_np(
                        w, group_size=group)
                return head_g_memo[key]

            def head_q(index):
                if head_prequant:
                    return reader.read2d(f"{head_name}{qsuffix}", index[0],
                                         index[1], True)
                if group is not None:
                    return _head_grouped(index[1])[0]
                a, b, _ = index[0].indices(h // krows)
                w = reader.read2d(
                    head_name, slice(a * krows, b * krows), index[1], True)
                q, s = np_qfn(w)
                scale_memo.setdefault(_key(head_name, index[1]), s)
                return q

            def head_scale(index):
                if group is not None:
                    if head_prequant:
                        return reader.read2d(f"{head_name}.scale",
                                             index[0], index[1], False)
                    return _head_grouped(index[1])[1]
                if head_prequant:
                    return reader.read1d(f"{head_name}.scale", index[0])
                return _scale(head_name, True, index[0])

            if group is not None:
                head_scale_leaf = _assemble(
                    (h // group, config.vocab_size), mesh, P(None, TP),
                    head_scale)
            else:
                head_scale_leaf = _assemble(
                    (config.vocab_size,), mesh, P(TP), head_scale)
            params["lm_head"] = qcls(
                _assemble((h // krows, config.vocab_size), mesh,
                          P(None, TP), head_q),
                head_scale_leaf,
            )
        else:
            params["lm_head"] = _assemble(
                (h, config.vocab_size), mesh, P(None, TP),
                lambda index: reader.read2d(head_name, index[0], index[1],
                                            True).astype(dt),
            )
        return params
    finally:
        reader.close()


def _load_latent_on_mesh(reader: CheckpointReader, model_dir,
                         config: LlamaConfig, mesh: Mesh,
                         quantize: str | None,
                         tie_word_embeddings: bool) -> dict:
    """A model whose layers are of several kinds (the latent-attention,
    shared-expert family; a state-space hybrid, under its own tensor
    names) onto the mesh: a dict of
    layer stacks, one a segment of ``models.llama.layer_plan``
    (``params["layers"] = {"dense": ..., "moe": ...}`` for leading dense
    layers then expert layers; a delta-rule hybrid's segments the same
    way, a repeated period's stacks leading ``[repeats, layers]``), the held
    experts read by their global ids (``config.first_expert`` on), every
    tensor replicated but the expert stacks, whose expert axis shards over
    ep (the family runs as one stage with tp = 1:
    ``mesh.validate_shardable``). int8 (on load or stored ``.q8``)
    quantizes every linear of ``quant.LATENT_LINEARS``; each layer's
    tensor is read, and quantized, on its own, so host scratch is one
    tensor."""
    from cake_tpu.models.llama import HC_TENSORS, stack_shapes
    from cake_tpu.ops.quant import (LATENT_LINEARS, QuantizedLinear,
                                    parse_quant_spec, quantize_linear_np,
                                    reject_int4_moe)
    from cake_tpu.utils.weights import check_prequantized, latent_stack_plan

    tier, _ = parse_quant_spec(quantize)
    if tier == "int4":
        reject_int4_moe()
    if tier is not None and tier not in config.family.linear_tiers:
        raise NotImplementedError(
            f"quantized linears are not wired for {config.family.what} "
            f"({config.family.linear_why}); serve it in bf16")
    prequantized = check_prequantized(reader.name_to_file, quantize)
    if not tie_word_embeddings and detect_tied_head(
            reader.name_to_file, model_dir, "cake_tpu.sharded_load"):
        tie_word_embeddings = True
    dt = _np_dtype(config.dtype)
    scales: dict[str, np.ndarray] = {}  # a linear's scale, once computed
    asked: set[str] = set()  # every stored tensor some leaf names

    def stored(name: str) -> bool:
        return prequantized and f"{name}.q8" in reader.name_to_file

    def quantized(name: str) -> np.ndarray:
        """q [in, out] int8 of one linear, stored so or quantized here
        (its scale is then kept for the scale leaf)."""
        if stored(name):
            return reader.read2d(f"{name}.q8", slice(None), slice(None),
                                 True)
        q, scales[name] = quantize_linear_np(
            reader.read2d(name, slice(None), slice(None), True))
        return q

    def scale_of(name: str) -> np.ndarray:
        if stored(name):
            return reader.read1d(f"{name}.scale")
        if name not in scales:
            quantized(name)
        return scales[name]

    def stacked(names_of, lead: tuple[int, ...], shape: tuple, spec: P,
                transpose: bool, quant: bool, dt=dt, fold=None):
        """One stacked leaf: ``names_of(i, [e])`` is the stored tensor of
        each leading index; 1-D tensors and plain linears in the serving
        type (``dt``: a wide residual stream's tensors stay float32),
        quantized linears as (q, scale). ``fold``
        (``models.families.Fold``): the tensor is stored otherwise than
        the program holds it (a norm as ``w - 1``, a fused projection a key
        head's group at a time): read whole, folded in float32, then
        sliced."""
        names = [names_of(*at) for at in np.ndindex(*lead)]
        asked.update(names)
        lacks = [n for n in names if ".indexer." in n and not (
            n in reader.name_to_file or stored(n))]
        if lacks:
            raise ValueError(
                f"the checkpoint stores no {lacks[0]}: a model under a "
                f"learned sparse attention (index_topk {config.index_topk})"
                " needs its indexer's five tensors a layer "
                "(self_attn.indexer.wq_b, wk, k_norm.weight, k_norm.bias, "
                "weights_proj)")

        def gather(index, read):
            grids = [range(*sl.indices(n)) for sl, n in zip(index, lead)]
            out = np.stack([read(names_of(*ids))
                            for ids in itertools.product(*grids)])
            return out.reshape(tuple(len(g) for g in grids) + out.shape[1:])

        lead_spec = tuple(spec)[:len(lead)]
        if fold is not None:
            def whole(n):
                w = (reader.read1d(n) if len(shape) == 1 else reader.read2d(
                    n, slice(None), slice(None), transpose))
                return fold.load(config, w.astype(np.float32))

            return _assemble(
                lead + shape, mesh, P(*lead_spec, *([None] * len(shape))),
                lambda ix: gather(ix, lambda n: whole(n)[
                    tuple(ix[len(lead):])]).astype(dt))
        if len(shape) == 1:
            return _assemble(lead + shape, mesh, P(*lead_spec, None),
                             lambda ix: gather(ix, lambda n: reader.read1d(
                                 n, ix[-1])).astype(dt))
        if not quant:
            def bounded(sl, n):
                """``sl`` of the LEAF's axis of ``n``: a stored tensor may
                hold more than the program does (a head of several
                prediction blocks, of which block 0 is read)."""
                return slice(*sl.indices(n))

            return _assemble(
                lead + shape, mesh, P(*lead_spec, None, None),
                lambda ix: gather(ix, lambda n: reader.read2d(
                    n, bounded(ix[-2], shape[0]), bounded(ix[-1], shape[1]),
                    transpose)).astype(dt))
        return QuantizedLinear(
            _assemble(lead + shape, mesh, P(*lead_spec, None, None),
                      lambda ix: gather(
                          ix, lambda n: quantized(n)[ix[-2], ix[-1]])),
            _assemble(lead + shape[1:], mesh, P(*lead_spec, None),
                      lambda ix: gather(ix, lambda n: scale_of(n)[ix[-1]])))

    shapes = stack_shapes(config)
    layers: dict = {}
    for stack, (ids, plain, experts) in latent_stack_plan(config).items():
        out = {}
        lead = P(STAGE, *([None] * (ids.ndim - 1)))
        for ours, (suffix, transpose, *fold) in plain.items():
            out[ours] = stacked(
                lambda *at, s=suffix, ids=ids: (
                    f"model.layers.{ids[at]}.{s}"),
                ids.shape, shapes[stack][ours](config), lead, transpose,
                tier is not None and ours in LATENT_LINEARS,
                np.dtype(np.float32) if ours in HC_TENSORS else dt,
                *fold)
        for ours, pattern in experts.items():
            out[ours] = stacked(
                lambda *at, p=pattern, ids=ids: (
                    f"model.layers.{ids[at[:-1]]}."
                    f"{p.format(e=config.first_expert + at[-1])}"),
                ids.shape + (config.n_routed_experts,),
                shapes[stack][ours](config)[1:], P(*lead, EP), True,
                tier is not None)
        layers[stack] = out

    h, v = config.hidden_size, config.vocab_size
    head_name = ("model.embed_tokens.weight" if tie_word_embeddings
                 else "lm_head.weight")
    params = {
        "layers": layers,
        "embed": stacked(lambda: "model.embed_tokens.weight", (), (v, h),
                         P(), False, False),
        "norm_f": stacked(lambda: config.family.final_norm, (), (h,), P(),
                          False, False, fold=config.family.final_norm_fold),
        "lm_head": stacked(lambda: head_name, (), (h, v), P(), True,
                           tier is not None),
    }
    # a family's tensors beside these (a looped model's exit gate): read
    # and kept, in the shapes the checkpoint stores
    for ours, parts in config.family.extra_tensors.items():
        params[ours] = {
            part: stacked(lambda n=name: n, (), shape_fn(config), P(),
                          False, False)
            for part, (name, shape_fn) in parts.items()}
    # what the checkpoint stores and no leaf names (a next-token prediction
    # block's ``mtp.*``, layers past the served depth), a tensor once
    # whatever forms it is stored in
    skipped = {n.removesuffix(".q8").removesuffix(".scale")
               for n in reader.name_to_file} - asked
    obs_metrics.counter("load.tensors_skipped").inc(len(skipped))
    if skipped:
        logging.getLogger("cake_tpu.sharded_load").info(
            "%d stored tensors are no part of the served model and were "
            "not read (%s ...)", len(skipped), sorted(skipped)[0])
    return params
