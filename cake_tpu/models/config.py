"""Model architecture configuration.

TPU-native equivalent of the reference's config plane
(`cake-core/src/model/config.rs`): a dataclass deserialized from a HuggingFace
`config.json` (hidden/intermediate sizes, layer/head counts, `rms_norm_eps`,
`rope_theta`, bos/eos ids — config.rs:13-26), plus the generation-time maximum
sequence length (the reference hard-caps MAX_SEQ_LEN=4096, config.rs:6; here it
is a tunable because the TPU build supports long context).

Families read: the dense and Mixtral-style decoders (one bare stack),
six whose layers are of several kinds (``segmented``: a stack a stretch of
one kind, ``models/llama.py`` ``layer_plan``), one whose layers run
several times a token (``total_ut_steps``: one stack, a cache plane a layer
and a pass), and one whose every layer is a double layer (two latent
attentions, two cache planes: ``zero_expert_num`` and the fields beside it). A family's config.json and
checkpoint, its checks and what is wired for it are its record in
``models/families.py`` (``LlamaConfig.family``); fields and presets here.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import NamedTuple, Sequence

import jax.numpy as jnp

from cake_tpu.models import families

# Reference default (config.rs:6). Overridable per-config here.
DEFAULT_MAX_SEQ_LEN = 4096


class DeltaRule(NamedTuple):
    """The sizes of a model's delta-rule layers (ops/kda.py)."""

    key_heads: int
    value_heads: int
    d_k: int
    d_v: int
    taps: int


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Llama-family architecture hyper-parameters.

    Field names mirror the HF ``config.json`` keys the reference reads
    (`config.rs:13-26`) so `from_hf_dict` is a direct mapping.
    """

    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    # HF `rope_scaling` dict, e.g. Llama-3.1's {"rope_type": "llama3",
    # "factor": 8.0, ...} or {"rope_type": "linear", "factor": N}. None = no
    # scaling (Llama-3.0, the reference's model of record).
    rope_scaling: dict | None = None
    bos_token_id: int | None = 128000
    eos_token_id: int | Sequence[int] | None = 128001
    tie_word_embeddings: bool = False
    max_seq_len: int = DEFAULT_MAX_SEQ_LEN
    dtype: str = "bfloat16"
    # --- model-family axes (all default to the Llama-3 shape) -------------
    # HF `model_type`: "llama" | "mistral" | "qwen2" | "mixtral". The same
    # functional decoder serves every family; the fields below are the only
    # architectural deltas (the reference serves exactly one family,
    # llama.rs — families are a capability extension of the Generator seam,
    # model/mod.rs:21-29).
    model_type: str = "llama"
    # q/k/v projection bias (Qwen2; HF Llama's `attention_bias` key maps
    # here too). Qwen2 itself is o-bias-free, but llama-arch
    # `attention_bias` checkpoints may carry an o_proj bias — the loaders
    # detect it per-checkpoint (utils/weights detect_family o_bias) and
    # attention plumbs it through, so no config field gates it.
    attention_bias: bool = False
    # Sliding-window attention (Mistral): key positions more than `window`
    # behind the query are masked out. None = full causal.
    sliding_window: int | None = None
    # MoE (Mixtral): 0 = dense MLP; >0 = routed SwiGLU experts per layer.
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    # Explicit per-head width (Gemma: heads * head_dim != hidden_size).
    # None resolves to hidden_size // num_attention_heads in __post_init__,
    # so every consumer reads a concrete int.
    head_dim: int | None = None
    # Gated-MLP activation: "silu" (SwiGLU — every Llama-family model) or
    # "gelu_tanh" (GeGLU — Gemma; HF spells it gelu_pytorch_tanh).
    hidden_act: str = "silu"
    # Gemma normalization deltas: RMSNorm scales by (1 + w), and the
    # embedding output is multiplied by sqrt(hidden_size).
    rms_norm_offset: bool = False
    embed_scale: bool = False
    # --- latent attention + shared/routed experts (DeepSeek-V3's keys; HF
    # `model_type` "deepseek_v3" | "axk1") ---------------------------------
    # Multi-head latent attention: kv_lora_rank > 0 selects it. The cache
    # then holds one row of kv_lora_rank + qk_rope_head_dim values a token a
    # layer, shared by every head (`cache_row`), and no per-head keys or
    # values (ops/mla.py).
    q_lora_rank: int | None = None
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The first `first_k_dense_replace` layers keep a dense SwiGLU of
    # `intermediate_size`; every later layer routes over experts of
    # `moe_intermediate_size` beside `n_shared_experts` shared ones.
    first_k_dense_replace: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    # Experts HELD here (the expert stacks' leading axis). The router's
    # width is `router_experts` (the published count; None = all are
    # held), and the held ones are global experts `first_expert ..
    # first_expert + n_routed_experts - 1`: a chip's share of an
    # expert-parallel deployment, told by the configuration
    # (config.json `expert_share`) and not by a mesh axis.
    n_routed_experts: int = 0
    router_experts: int | None = None
    first_expert: int = 0
    scoring_func: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # A per-expert correction bias (``topk_method: "noaux_tc"``,
    # ``moe_router_enable_expert_bias``): it enters the router's CHOICE
    # (groups and top-k on ``score + bias``) and not the weights.
    router_bias: bool = False
    # --- delta-rule linear attention beside latent attention (HF
    # `model_type` "bailing_hybrid") ----------------------------------------
    # ``layer_group_size`` G > 0: layer i attends through latent attention
    # if ``(i + 1) % G == 0`` and through KDA otherwise (a gated delta
    # rule with a per-channel decay, ops/kda.py): heads of ``head_dim``
    # keys and values, a causal depthwise convolution of
    # ``short_conv_kernel_size`` taps on q, k and v, log-decays in
    # ``(kda_lower_bound, 0)``. A KDA layer keeps no rows: its cache is a
    # float32 state a head and the convolutions' last inputs
    # (``cache_plan``). ``attn_gate``: the sigmoid output gate's
    # granularity on the latent layers ("head_wise"); KDA layers gate a
    # channel.
    layer_group_size: int = 0
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    attn_gate: str | None = None
    # --- selective state-space (Mamba-1) layers beside grouped-query
    # attention (HF `model_type` "jamba") ------------------------------------
    # ``attn_layer_period`` P > 0: layer i is grouped-query attention with
    # NO rotary (or any other) position embedding if ``i % P ==
    # attn_layer_offset`` and a Mamba mixer otherwise (ops/mamba.py):
    # ``mamba_expand * hidden_size`` channels, each a ``mamba_d_state``-wide
    # float32 state a stream, a causal depthwise convolution of
    # ``mamba_d_conv`` taps (with a bias if ``mamba_conv_bias``), a step
    # size a channel through a ``mamba_dt_rank`` bottleneck. A Mamba layer
    # keeps no rows: its cache is the state ``[d_state, d_inner]`` and the
    # convolution's last inputs (``cache_plan``). Every layer's
    # feed-forward is the dense SwiGLU.
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0  # 0: ceil(hidden_size / 16), HF's "auto"
    mamba_conv_bias: bool = True
    # --- window and full grouped-query attention mixed by layer (HF
    # `model_type` "exaone_moe" | "mellum") ----------------------------------
    # ``layer_types``: "sliding_attention" | "full_attention" a layer. A
    # window layer sees the last ``sliding_window`` positions, which it
    # keeps as a ring of ``ring_rows`` rows a stream whatever the capacity
    # (``cache_plan``); a full layer keeps every row. ``layer_rope``: the
    # rotation of q and k a layer KIND, read from the file (``rotation``
    # gives a kind's rope parameters, or None for a kind that carries NO
    # position embedding): K-EXAONE rotates its window layers and nothing
    # on its full ones; Mellum rotates its window layers plainly and its
    # full ones under YaRN, two tables in one program
    # (``ops.rope.rope_tables_for``). ``qk_norm``: an RMSNorm over each
    # head of q and k (one ``[head_dim]`` weight for all heads), before
    # the rotation. The feed-forward is the shared-expert family's
    # (``first_k_dense_replace`` leading dense layers, then
    # ``n_routed_experts`` held of ``router_experts`` beside
    # ``n_shared_experts``, which may be none), sigmoid-scored with a
    # bias or softmax-scored over all experts (``scoring_func``).
    layer_types: tuple[str, ...] | None = None
    layer_rope: tuple | None = None
    qk_norm: bool = False
    # --- gated short-convolution layers beside grouped-query attention (HF
    # `model_type` "lfm2_moe") -----------------------------------------------
    # ``layer_types``: "conv" | "full_attention" a layer. A conv layer is
    # ``[B | C | x] = u W_in; y = conv(B * x); out = (C * y) W_out`` with a
    # causal depthwise convolution of ``conv_L_cache`` taps and no
    # activation (ops/shortconv.py): it keeps NO rows and no state, only
    # the convolution's last ``conv_L_cache - 1`` inputs a stream
    # (``cache_plan``: ``conv`` and no ``state``). A full layer rotates q
    # and k (unlike the window family's) behind the per-head ``qk_norm``.
    # The feed-forward is the shared-expert family's with no shared expert:
    # ``first_k_dense_replace`` leading dense layers, then ALL
    # ``n_routed_experts`` sigmoid-scored, bias-corrected experts.
    conv_L_cache: int = 3
    conv_bias: bool = False
    # --- one set of layers run several times a token (HF `model_type`
    # "ouro") ----------------------------------------------------------------
    # ``total_ut_steps`` U > 1: ``h = E[tokens]``; U times over, the
    # ``num_hidden_layers`` layers in order WITH THE SAME WEIGHTS, then
    # ``h = RMS(h; model.norm)``: the last norm closes every pass, and the
    # head reads the last pass's normed state with no further norm. A
    # layer is sandwich-normed: the sub-layer's output goes through a
    # second norm before the residual adds it. The keys and values a
    # layer writes in pass ``u`` are read by that layer in pass ``u`` of
    # later tokens alone: the cache holds a plane a (pass, layer) pair,
    # plane ``u * num_hidden_layers + i`` (``cache_plan``).
    # ``early_exit_threshold``: the cumulative exit probability (a
    # sigmoid gate on each pass's output, ``params["exit_gate"]``) at
    # which a token leaves the loop; only 1 (never early) is served, and
    # the gate then changes no logit.
    total_ut_steps: int = 1
    early_exit_threshold: float = 1.0
    # --- a residual stream several hidden vectors wide (manifold-
    # constrained hyper-connections; HF `model_type` "xing4_0", beside the
    # latent family's keys) --------------------------------------------------
    # ``hc_mult`` n > 1: a token's state between sub-layers is ``X [n,
    # hidden]`` (the embedding n times over at the entry, the streams' sum
    # before the last norm). Each sub-layer reads ``u = sum_j H_pre[j]
    # X[j]`` and leaves ``X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]``,
    # the 2n + n^2 coefficients a function of the token's own state
    # (ops/hyper.py): ``H_res`` is ``exp`` of logits clamped to
    # ``hc_res_clamp`` taken through ``hc_sinkhorn_iters`` rounds of row
    # and column normalisation (``hc_eps`` in each division), so that it
    # is doubly stochastic. 1: the plain residual every other model has.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple[float, float] = (-30.0, 30.0)
    # --- scalar-gated delta-rule layers beside gated grouped-query
    # attention (HF `model_type` "qwen3_next") -------------------------------
    # ``layer_types``: "linear_attention" | "full_attention" a layer (the
    # file gives ``full_attention_interval`` P: full where ``(i + 1) % P ==
    # 0``). A linear layer is a gated delta rule (ops/kda.py) of
    # ``linear_num_value_heads`` heads of ``linear_key_head_dim`` x
    # ``linear_value_head_dim`` state over ``linear_num_key_heads`` key
    # heads (value head ``h`` reads key head ``h // (Hv / Hk)``), a log-decay
    # a HEAD (``-exp(A_log) softplus(a + dt_bias)``, unbounded below), a
    # causal depthwise convolution of ``linear_conv_kernel_dim`` taps over
    # ``[q | k | v]``, the output normed a head and gated by ``silu(z)``. A
    # full layer is grouped-query attention whose q projection carries a
    # gate a channel beside each head (``attn_gate`` "elementwise": ``out *
    # sigmoid(gate)`` before ``wo``), whose q and k heads are normed
    # (``qk_norm``) and of whose ``head_dim`` channels the first
    # ``rope_fraction`` rotate (the file's ``partial_rotary_factor``). Every
    # layer routes over softmax-scored experts beside ONE shared expert
    # weighted by ``sigmoid(x w_sg)`` (``shared_expert_gate``). The file's
    # norms are stored as ``w - 1``; the loaders add the one.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    rope_fraction: float = 1.0
    shared_expert_gate: bool = False
    # --- a learned sparse attention over the latent cache (DeepSeek sparse
    # attention; HF `model_type` "glm_moe_dsa", beside the latent family's
    # keys) ------------------------------------------------------------------
    # ``index_topk`` K > 0: every latent layer holds an indexer
    # (ops/dsa.py): ``index_n_heads`` queries of ``index_head_dim`` from the
    # query latent, ONE key of ``index_head_dim`` a token (a LayerNorm with
    # a bias behind its projection), the first ``qk_rope_head_dim`` channels
    # of each rotated by the latent attention's tables, and a weight a head
    # from the hidden state. A query's index score of row ``s`` is ``sum_j
    # w_j relu(q_j . k_s)`` in float32; it attends the K rows of highest
    # score among those at or before it (all of them up to K rows; a tie
    # goes to the lower row). The cache keeps the key beside the latent
    # row: a third kind of row (``cache_plan``'s ``index``). 0: plain
    # latent attention.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # --- a shortcut-connected double layer with zero-compute experts
    # (LongCat-Flash's keys; HF `model_type` "longcat_flash", beside the
    # latent attention's) ------------------------------------------------------
    # One published layer is TWO latent attentions and TWO dense SwiGLUs of
    # ``intermediate_size`` around ONE expert block that reads the first
    # sub-layer's normed input and whose result is added a sub-layer late
    # (models/llama.py ``_double_block``): ``a0 = x + MLA_0(RMS(x)); h =
    # RMS(a0); s = MoE(h); b0 = a0 + FFN_0(h); a1 = b0 + MLA_1(RMS(b0)); out
    # = a1 + FFN_1(RMS(a1)) + s``. A layer keeps TWO cache planes (layer
    # ``l``'s attention ``j`` is plane ``2 l + j``, ``cache_plan``). The
    # router scores ``router_experts + zero_expert_num`` outputs by softmax
    # over all of them, chooses on ``p + bias``, and weighs the chosen by
    # ``routed_scaling_factor p`` with no renormalisation; the last
    # ``zero_expert_num`` outputs are no experts: one that is chosen returns
    # its input (``zero_expert_type`` "identity", the only kind computed).
    # ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: the query (behind its
    # up-projection) times ``(hidden / q_lora_rank)^0.5`` and the normed key
    # latent times ``(hidden / kv_lora_rank)^0.5``; both are constants on a
    # norm's output ahead of a linear and fold into that norm's weight
    # where the tensors are read (``families.Fold``).
    zero_expert_num: int = 0
    zero_expert_type: str = "identity"
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # --- EVA attention: an exact window and a summary of what left it
    # (EvaByte's keys; HF `model_type` "evabyte") ------------------------------
    # ``attention_class`` "eva": every layer is multi-head attention whose
    # softmax runs over TWO sets of rows at once (ops/eva.py). With ``W =
    # window_size`` and ``C = chunk_size``, a query at position ``n`` sees
    # exactly the keys of its own window (``m // W == n // W``, ``m <= n``:
    # the window does not slide, it RESETS every ``W`` positions) and, for
    # every chunk of ``C`` positions of every window COMPLETED before its
    # own, one learned summary row ``(k~, v~)``: ``v~`` the chunk's values
    # under a softmax of ``phi_h . k_m`` (a learned vector a head), ``k~``
    # the chunk's mean key plus a learned ``mu_h``. No layer holds a row a
    # position: the cache is a ring of ``W`` rows and a plane of one summary
    # row for every ``C`` positions (``cache_plan``'s ``ring`` and
    # ``summary``). ``num_pred_heads``: the stored head holds that many
    # blocks of ``vocab_size`` rows; the model's own next-token logits are
    # block 0's, the only one the served path loads (the others propose
    # further tokens for the release's self-speculative decoding).
    attention_class: str | None = None
    window_size: int = 0
    chunk_size: int = 0
    num_pred_heads: int = 1

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim",
                self.hidden_size // self.num_attention_heads,
            )
        self.family.check(self)
        families.check_residual_path(self)
        families.check_gated_keys(self)
        families.check_indexer(self)
        families.check_zero_experts(self)
        families.check_attention_class(self)
        # validate at construction, not as a KeyError deep in a jit trace
        if self.hidden_act not in ("silu", "gelu_tanh"):
            raise ValueError(
                f"hidden_act must be 'silu' or 'gelu_tanh', got "
                f"{self.hidden_act!r} (HF's 'gelu_pytorch_tanh' maps to "
                "'gelu_tanh' via from_hf_dict)"
            )
        if self.num_local_experts and self.hidden_act != "silu":
            raise ValueError(
                "MoE expert MLPs are SwiGLU-only (ops/moe.py has no "
                "activation plumbing); hidden_act must be 'silu' when "
                "num_local_experts > 0"
            )

    @property
    def family(self) -> families.Family:
        """The first record of ``models/families.py`` the fields select."""
        return next(f for f in families.FAMILIES if f.selects(self))

    @property
    def num_kv_groups(self) -> int:
        """Query heads per KV head (GQA group size, attention.rs:84-89)."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def ring_rows(self) -> int:
        """Rows ``R`` of a window layer's ring, a function of the window
        alone: position ``p`` lives at row ``p % R``. A layer writes its
        new row and then attends, so the ``sliding_window`` newest
        positions (the query's own among them) are all a step reads, and
        an admission chunk reads the rows before it and then writes its
        own last ``R``: the window itself is enough. It is rounded up to
        whole ``(16, 128)`` tiles of a bfloat16 buffer's last two axes,
        which the published windows (128, 1024) are already (``window <=
        R < window + 16``)."""
        if self.attention_class == "eva":  # the window itself: it resets
            return self.window_size
        return -(-self.sliding_window // 16) * 16

    def rotation(self, layer_type: str) -> dict | None:
        """The rope parameters of the layers of one ``layer_types`` kind
        (``rope_type``, ``rope_theta`` and, under YaRN, its keys), or None
        where that kind rotates nothing or the model has no such layer:
        ``layer_rope`` as a dict."""
        rope = dict(self.layer_rope).get(layer_type)
        return None if rope is None else dict(rope)

    @property
    def segmented(self) -> bool:
        """Whether the layers are of several kinds, so that
        ``params["layers"]`` is a dict of stacks, one a segment of
        ``models.llama.layer_plan``, and not one bare stack."""
        return self.family is not families.GQA

    @property
    def cache_token_bytes(self) -> int:
        """Bytes the ``rows`` of ``cache_plan`` hold for one token of one
        stream, in the serving type: every plane's keys and values, and a
        sparse attention's index key beside each (``index``)."""
        plan = self.cache_plan
        planes = plan.get("rows", (0,))[0]
        layers, heads, width = plan.get("index", (0, 0, 0))
        values = planes * self.cache_row_values + layers * heads * width
        if "summary" in plan:  # one row for every ``chunk`` positions
            n, heads, chunk, k_width, v_width = plan["summary"]
            values += n * heads * (k_width + v_width) // chunk
        return values * self.jax_dtype.itemsize

    def stream_bytes(self, capacity: int) -> int:
        """Bytes one stream's whole reservation holds at ``capacity``
        positions, in the serving type: what grows with the capacity
        (``cache_token_bytes`` a position) and a ring's rows, which do
        not (a staging row of an admission is this large)."""
        ring = self.cache_plan.get("ring")
        fixed = 0 if ring is None else (
            ring[0] * ring[1] * ring[2] * (ring[3] + ring[4])
            * self.jax_dtype.itemsize)
        return self.cache_token_bytes * capacity + fixed

    @property
    def resid_token_bytes(self) -> int:
        """Bytes one token's residual state holds between sub-layers, in
        the serving type: ``hc_mult`` hidden vectors."""
        return self.hc_mult * self.hidden_size * self.jax_dtype.itemsize

    @property
    def mamba_d_inner(self) -> int:
        """Channels of a Mamba mixer."""
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> tuple[tuple[str, str], ...]:
        """``(mixer, feed-forward)`` of every layer, in model order: the
        mixer is "gqa", "swa" (grouped-query attention through a window,
        where ``layer_types`` says so), "mla", "kda", "gdn" (the
        scalar-gated delta rule, where ``layer_types`` says so), "mamba" or
        "conv" (a gated short convolution, where ``layer_types`` says so),
        or "mla2" (a shortcut-connected double layer: two latent attentions,
        two dense feed-forwards and the expert block between them, where the
        family's layers are such), or "eva" (attention over a window that
        resets and the summaries of the windows before it, where
        ``attention_class`` says so), the feed-forward "dense" or "moe".
        THE place the layer order comes from (models/llama.py
        ``layer_plan`` groups it into scanned segments, the cache and the
        loaders count it)."""
        n = self.num_hidden_layers
        experts = self.num_local_experts or self.n_routed_experts
        dense = self.first_k_dense_replace if self.n_routed_experts else (
            0 if self.num_local_experts else n)
        by_type = self.family.layer_mixers  # found once, not once a layer

        def mixer(i):
            if self.layer_types is not None:
                return by_type[self.layer_types[i]]
            if self.attn_layer_period:
                return ("gqa" if i % self.attn_layer_period
                        == self.attn_layer_offset else "mamba")
            if self.attention_class == "eva":
                return "eva"
            if not self.kv_lora_rank:
                return "gqa"
            if self.family.planes_a_layer == 2:
                return "mla2"
            g = self.layer_group_size
            return "mla" if not g or (i + 1) % g == 0 else "kda"

        return tuple((mixer(i), "moe" if experts and i >= dense else "dense")
                     for i in range(n))

    @property
    def router_outputs(self) -> int:
        """Outputs of an expert layer's router: the published experts
        (``router_experts``, of which ``n_routed_experts`` are held here)
        and, behind them, the ``zero_expert_num`` zero-compute ones."""
        return ((self.router_experts or self.n_routed_experts)
                + self.zero_expert_num)

    @property
    def delta_rule(self) -> DeltaRule:
        """``(key heads, value heads, d_k, d_v, taps)`` of the model's
        delta-rule layers (ops/kda.py), from the MIXER's own keys: KDA's are
        the attention's (as many key heads as value heads of ``head_dim``,
        ``short_conv_kernel_size`` taps), the scalar-gated rule's are the
        ``linear_*`` ones."""
        if self.family.recurrent_mixer == "gdn":
            return DeltaRule(
                self.linear_num_key_heads, self.linear_num_value_heads,
                self.linear_key_head_dim, self.linear_value_head_dim,
                self.linear_conv_kernel_dim)
        h, d = self.num_attention_heads, self.head_dim
        return DeltaRule(h, h, d, d, self.short_conv_kernel_size)

    @property
    def delta_conv_width(self) -> int:
        """Channels of a delta-rule layer's convolution: ``[q | k | v]``."""
        hk, hv, dk, dv, _ = self.delta_rule
        return 2 * hk * dk + hv * dv

    @property
    def cache_plan(self) -> dict[str, tuple[int, ...]]:
        """What the cache holds, a kind of state each: ``rows`` ``(layers,
        heads, k_width, v_width)`` for the layers that keep rows (every
        layer of a model with one kind of attention), and for the layers
        that hold a recurrent state ``state`` (float32) and ``conv`` (the
        convolutions' last inputs), shaped by their mixer: delta-rule
        layers ``(layers, value heads, d_k, d_v)`` and ``(layers, taps - 1,
        2 key heads d_k + value heads d_v)`` (the q, k and v convolutions;
        ``delta_rule``: the mixer's own heads, not the attention's), Mamba
        layers ``(layers,
        d_state, d_inner)`` (channels last, on the lanes) and ``(layers,
        taps - 1, d_inner)``. Window layers (``layer_types``) keep ``ring``
        ``(layers, heads, R, k_width, v_width)``: ``R = ring_rows`` rows a
        stream whatever the capacity, and ``rows`` then counts the full
        layers alone. Short-convolution layers keep ``conv`` ``(layers,
        taps - 1, hidden)`` and NO ``state``: who asks whether a model
        holds a state asks for the key. A kind with no layer is left
        out. Where the layers run ``total_ut_steps`` times a token, ``rows``
        counts a plane a layer AND a pass (layer ``i`` in pass ``u`` is
        plane ``u * num_hidden_layers + i``): the cache's depth is the
        plan's, not ``num_hidden_layers``. A shortcut-connected double layer
        keeps TWO planes (its attention ``j`` is plane ``2 l + j``). Under a
        learned sparse attention
        (``index_topk`` > 0) every latent layer keeps ``index`` ``(layers,
        1, index_head_dim)`` beside its rows: the indexer's one key a
        token, normed and rotated, in the serving type. EVA layers
        (``attention_class``) keep NO ``rows``: a ``ring`` of ``window_size``
        rows whose live rows are those of the query's own window (row ``p %
        W``; the window resets, it does not slide) and ``summary``
        ``(layers, heads, chunk_size, k_width, v_width)``: ONE row for every
        ``chunk_size`` positions, ``capacity // chunk_size`` rows a stream
        (the plan names the positions a row stands for; the capacity is the
        allocation's: ``ops.kvcache.init_cache``)."""
        mixers = [m for m, _ in self.layer_kinds]
        recurrent = self.family.recurrent_mixer
        held = mixers.count(recurrent)
        ring = mixers.count("swa")
        plan = {}
        eva = mixers.count("eva")
        if eva:
            heads, *widths = self.cache_row
            plan["ring"] = (eva, heads, self.ring_rows, *widths)
            plan["summary"] = (eva, heads, self.chunk_size, *widths)
            return plan
        if len(mixers) - held - ring:
            # a looped model keeps a plane a layer AND a pass, a double
            # layer one for each of its two attentions
            plan["rows"] = ((len(mixers) - held - ring) * self.total_ut_steps
                            * self.family.planes_a_layer,) + self.cache_row
        if self.index_topk:
            plan["index"] = (mixers.count("mla"), 1, self.index_head_dim)
        if ring:
            heads, *widths = self.cache_row
            plan["ring"] = (ring, heads, self.ring_rows, *widths)
        if held and recurrent in ("kda", "gdn"):
            _, hv, dk, dv, taps = self.delta_rule
            plan["state"] = (held, hv, dk, dv)
            plan["conv"] = (held, taps - 1, self.delta_conv_width)
        elif held and recurrent == "conv":
            plan["conv"] = (held, self.conv_L_cache - 1, self.hidden_size)
        elif held:
            plan["state"] = (held, self.mamba_d_state, self.mamba_d_inner)
            plan["conv"] = (held, self.mamba_d_conv - 1, self.mamba_d_inner)
        return plan

    @property
    def rope_dim(self) -> int:
        """Channels of a head that rotary embeddings cover; 0: the model
        has no position embedding (position comes from the recurrence).
        Where window and full layers are mixed this is the width of
        whichever kinds rotate (``layer_rope``: the layer loop hands each
        kind its own table, or none). Beside short-convolution layers the
        full layers rotate the whole head; beside scalar-gated delta-rule
        layers they rotate the head's first ``rope_fraction`` channels
        (``ops.rope.apply_rope`` leaves the rest as they are)."""
        if self.attn_layer_period:
            return 0
        if self.kv_lora_rank:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.rope_fraction)

    @property
    def cache_row(self) -> tuple[int, int, int]:
        """``(heads, k_width, v_width)`` of the cache's two buffers ``[L, B,
        heads, S, width]``: THE place every allocation, spec and byte count
        takes the cache's row from. Grouped-query attention keeps keys and
        values per KV head; latent attention keeps the normed latent (in
        ``k``) and the roped shared key part (in ``v``), once for all
        heads, or both in one row of ``k`` under a learned sparse attention
        (``index_topk`` > 0)."""
        if self.kv_lora_rank and self.index_topk:
            # under a learned sparse attention a step GATHERS its chosen
            # rows, and a gather costs a row whatever its width: the latent
            # and the roped part lie in ONE row of the first buffer (the
            # second holds nothing), so that a chosen row is fetched once;
            # padded to whole lane tiles of 128 (576 -> 640), which the
            # chip otherwise lays out rows-on-lanes and re-lays for every
            # program that gathers from it (PERF.md section 7)
            width = self.kv_lora_rank + self.qk_rope_head_dim
            return 1, -(-width // 128) * 128, 0
        if self.kv_lora_rank:
            return 1, self.kv_lora_rank, self.qk_rope_head_dim
        return self.num_key_value_heads, self.head_dim, self.head_dim

    @property
    def cache_row_values(self) -> int:
        """Values the cache holds for one token of one layer."""
        heads, k_width, v_width = self.cache_row
        return heads * (k_width + v_width)

    @property
    def attn_scale(self) -> float:
        """Softmax scale of the attention scores: ``d^-0.5`` over the query
        width, times YaRN's ``mscale^2`` where the rope scaling gives
        ``mscale_all_dim`` (latent attention)."""
        if not self.kv_lora_rank:
            return self.head_dim ** -0.5
        from cake_tpu.ops.rope import yarn_mscale

        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling or {}
        if rs.get("mscale_all_dim"):
            scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
        return scale

    def eos_ids(self) -> tuple[int, ...]:
        """Normalized EOS id set (reference checks config ids or "</s>",
        llama.rs:17,26-29,271)."""
        if self.eos_token_id is None:
            return ()
        if isinstance(self.eos_token_id, int):
            return (self.eos_token_id,)
        return tuple(self.eos_token_id)

    @classmethod
    def from_hf_dict(cls, d: dict, **overrides) -> "LlamaConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in d.items() if k in known}
        # HF configs carry torch_dtype, not dtype.
        td = d.get("torch_dtype")
        if td and "dtype" not in overrides:
            kwargs["dtype"] = {"float16": "bfloat16", "bfloat16": "bfloat16",
                               "float32": "float32"}.get(td, "bfloat16")
        # the file's `model_type` decides the spelling, defaults and limits
        family = families.BY_MODEL_TYPE.get(d.get("model_type"),
                                            families.GQA)
        if family.layer_mixers is None:
            # Hugging Face writes a `layer_types` list for every family
            # (all "full_attention" where nothing windows by layer); only
            # the families that mix mixers by layer read it
            kwargs.pop("layer_types", None)
        read = family.read(d)
        if "hidden_act" not in read and d.get("hidden_act") not in (
                None, "silu"):
            raise ValueError(
                f"unsupported hidden_act {d['hidden_act']!r} for "
                f"model_type {d.get('model_type')!r}"
            )
        return cls(**{**kwargs, **read, **overrides})

    @classmethod
    def from_hf_json(cls, path: str | Path, **overrides) -> "LlamaConfig":
        with open(path) as f:
            return cls.from_hf_dict(json.load(f), **overrides)

    def to_hf_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.pop("max_seq_len")
        d.pop("dtype")
        for key in ("rope_scaling", "sliding_window"):
            if d[key] is None:
                d.pop(key)
        if not d["num_local_experts"]:
            d.pop("num_local_experts")
            if not self.n_routed_experts:
                d.pop("num_experts_per_tok")
        if not d["attention_bias"]:
            d.pop("attention_bias")
        if d["hidden_act"] == "silu":
            d.pop("hidden_act")
        else:  # HF spelling
            d["hidden_act"] = "gelu_pytorch_tanh"
        for key in ("rms_norm_offset", "embed_scale"):
            if not d[key]:
                d.pop(key)
        width, first = d.pop("router_experts"), d.pop("first_expert")
        if self.n_routed_experts and width != self.n_routed_experts:
            d["expert_share"] = {
                "n_routed_experts": width,
                "ep": width // self.n_routed_experts,
                "rank": first // self.n_routed_experts}
        d.pop("qk_norm")  # the family's, not a key of any config.json
        # the keys another family's file carries go; this family's are
        # written in its own spelling (its `read` reads them back)
        family = self.family
        for f in families.FIELDS - set(family.fields):
            d.pop(f)
        family.write(self, d)
        if not self.router_bias:
            d.pop("router_bias", None)
        return d



def llama3_8b(**overrides) -> LlamaConfig:
    """Meta-Llama-3-8B — the reference's model of record (cake/mod.rs:88-96)."""
    return LlamaConfig(**overrides)


def llama2_7b(**overrides) -> LlamaConfig:
    """Llama-2-7B: MHA (kv_heads == heads, GQA group 1), 11008 intermediate,
    32000 vocab, rope_theta 10000 — the pre-GQA family the reference's
    candle stack also serves; exercises the group=1 attention path."""
    base = dict(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=11008,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=32,
        rope_theta=10000.0,
        max_seq_len=4096,
        bos_token_id=1,  # sentencepiece ids, NOT the Llama-3 defaults
        eos_token_id=2,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def llama3_70b(**overrides) -> LlamaConfig:
    base = dict(
        hidden_size=8192,
        intermediate_size=28672,
        num_hidden_layers=80,
        num_attention_heads=64,
        num_key_value_heads=8,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def mistral_7b(**overrides) -> LlamaConfig:
    """Mistral-7B-v0.1: Llama geometry with a 4096-token sliding window and
    32000 vocab — exercises the windowed-mask attention path."""
    base = dict(
        model_type="mistral",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=8,
        rope_theta=10000.0,
        sliding_window=4096,
        bos_token_id=1,
        eos_token_id=2,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def qwen2_7b(**overrides) -> LlamaConfig:
    """Qwen2-7B: GQA with q/k/v projection bias, 152k vocab, tied-embedding
    variants in the smaller sizes — exercises the biased-projection path."""
    base = dict(
        model_type="qwen2",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_hidden_layers=28,
        num_attention_heads=28,
        num_key_value_heads=4,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        attention_bias=True,
        bos_token_id=151643,
        eos_token_id=151643,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def mixtral_8x7b(**overrides) -> LlamaConfig:
    """Mixtral-8x7B: Mistral geometry with 8 routed SwiGLU experts per
    layer, top-2 — the MoE family (expert-parallel over the mesh's ep
    axis, ops/moe.py)."""
    base = dict(
        model_type="mixtral",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=8,
        rope_theta=1000000.0,
        num_local_experts=8,
        num_experts_per_tok=2,
        bos_token_id=1,
        eos_token_id=2,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def gemma_7b(**overrides) -> LlamaConfig:
    """Gemma-7B: MHA with explicit head_dim 256 (16 x 256 != hidden 3072),
    GeGLU MLP, (1+w) RMSNorm, sqrt(hidden)-scaled embeddings, tied head —
    the structurally-different fifth family."""
    base = dict(
        model_type="gemma",
        vocab_size=256000,
        hidden_size=3072,
        intermediate_size=24576,
        num_hidden_layers=28,
        num_attention_heads=16,
        num_key_value_heads=16,
        head_dim=256,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        hidden_act="gelu_tanh",
        rms_norm_offset=True,
        embed_scale=True,
        tie_word_embeddings=True,
        bos_token_id=2,
        eos_token_id=1,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def axk1_ep16(**overrides) -> LlamaConfig:
    """A.X-K1 (https://huggingface.co/skt/A.X-K1, `model_type` "axk1",
    DeepSeek-V3's keys) at its published widths, as ONE chip of 16 that
    share each layer's 192 experts holds it: global experts 0-11 beside
    the whole router, attention and shared expert. 61 layers as published;
    a chip serves the depth of its pipeline stage (`num_hidden_layers=`)
    and its slice of the vocabulary (`vocab_size=`)."""
    base = dict(
        model_type="axk1",
        vocab_size=163840,
        hidden_size=7168,
        intermediate_size=18432,
        num_hidden_layers=61,
        num_attention_heads=64,
        num_key_value_heads=64,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        rope_scaling={"type": "yarn", "factor": 32, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        max_seq_len=131072,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        first_k_dense_replace=1,
        moe_intermediate_size=2048,
        n_shared_experts=1,
        n_routed_experts=12,
        router_experts=192,
        first_expert=0,
        num_experts_per_tok=8,
        scoring_func="sigmoid",
        n_group=8,
        topk_group=4,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        bos_token_id=0,
        eos_token_id=1,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def ling3flash_ep4(**overrides) -> LlamaConfig:
    """Ling-3.0-flash (https://huggingface.co/inclusionAI/Ling-3.0-flash,
    `model_type` "bailing_hybrid") at its published widths, as ONE chip of
    the 4 that share each layer's 512 experts holds it: global experts
    0-127 beside the whole router (and its bias), mixers and shared
    expert. 42 layers as published (KDA but every sixth, which is latent
    attention; two leading dense layers); a chip serves the depth of its
    pipeline stage (`num_hidden_layers=`, `first_k_dense_replace=`) and
    its slice of the vocabulary (`vocab_size=`)."""
    base = dict(
        model_type="bailing_hybrid",
        vocab_size=157184,
        hidden_size=2560,
        intermediate_size=6144,
        num_hidden_layers=42,
        num_attention_heads=32,
        num_key_value_heads=32,
        head_dim=128,
        rms_norm_eps=1e-6,
        rope_theta=6000000.0,
        max_seq_len=262144,
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        attn_gate="head_wise",
        layer_group_size=6,
        short_conv_kernel_size=4,
        kda_lower_bound=-5.0,
        first_k_dense_replace=2,
        moe_intermediate_size=768,
        n_shared_experts=1,
        n_routed_experts=128,
        router_experts=512,
        first_expert=0,
        num_experts_per_tok=8,
        scoring_func="sigmoid",
        router_bias=True,
        n_group=8,
        topk_group=4,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        bos_token_id=0,
        eos_token_id=1,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def jamba2_3b(**overrides) -> LlamaConfig:
    """AI21-Jamba2-3B (https://huggingface.co/ai21labs/AI21-Jamba2-3B,
    `model_type` "jamba") at its published sizes: 28 layers, Mamba mixers
    (5120 channels of a 16-wide state) but layers 7 and 21, which are
    attention of 20 query heads over one key/value head with no position
    embedding; the dense 8192-wide SwiGLU in every layer; a tied head."""
    base = dict(
        model_type="jamba",
        vocab_size=65536,
        hidden_size=2560,
        intermediate_size=8192,
        num_hidden_layers=28,
        num_attention_heads=20,
        num_key_value_heads=1,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        max_seq_len=262144,
        attn_layer_period=14,
        attn_layer_offset=7,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank=160,
        mamba_conv_bias=True,
        bos_token_id=1,
        eos_token_id=2,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def xing4_29b(**overrides) -> LlamaConfig:
    """Xing4.0-29B-A4B (https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B,
    `model_type` "xing4_0", DeepSeek-V3's keys and the ``hc_*`` ones) at
    its published sizes: 40 layers of latent attention (32 heads of 128 +
    64, YaRN factor 64) under a residual stream FOUR hidden vectors wide
    that every sub-layer mixes by a per-token doubly stochastic matrix (20
    Sinkhorn rounds); two leading dense layers (9216), then all 64
    bias-corrected sigmoid-scored experts (1024) top-4 beside a shared
    one; an untied head. A chip serves the depth of its pipeline stage
    (`num_hidden_layers=`, `first_k_dense_replace=`)."""
    base = dict(
        model_type="xing4_0",
        vocab_size=131072,
        hidden_size=3584,
        intermediate_size=9216,
        num_hidden_layers=40,
        num_attention_heads=32,
        num_key_value_heads=32,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        max_seq_len=262144,
        q_lora_rank=768,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        first_k_dense_replace=2,
        moe_intermediate_size=1024,
        n_shared_experts=1,
        n_routed_experts=64,
        num_experts_per_tok=4,
        scoring_func="sigmoid",
        router_bias=True,
        n_group=1,
        topk_group=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.0,
        hc_mult=4,
        hc_sinkhorn_iters=20,
        hc_eps=1e-6,
        hc_res_clamp=(-30.0, 30.0),
        bos_token_id=0,
        eos_token_id=1,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def glm5_ep16(**overrides) -> LlamaConfig:
    """GLM-5 (https://huggingface.co/zai-org/GLM-5, `model_type`
    "glm_moe_dsa": DeepSeek-V3's keys under a learned sparse attention) at
    its published widths, as ONE chip of 16 that share each layer's 256
    experts holds it: global experts 0-15 beside the whole router,
    attention, indexer and shared expert. 78 layers of latent attention
    (64 heads of 192 + 64 query/key and 256 value channels, plain rope)
    whose queries attend the 2048 rows a 32-head indexer of 128 scores
    highest; three leading dense layers (12288), then bias-corrected
    sigmoid-scored experts (2048) top-8 in one group beside a shared one;
    an untied head. A chip serves the depth of its pipeline stage
    (`num_hidden_layers=`, `first_k_dense_replace=`) and its slice of the
    vocabulary (`vocab_size=`)."""
    base = dict(
        model_type="glm_moe_dsa",
        vocab_size=154880,
        hidden_size=6144,
        intermediate_size=12288,
        num_hidden_layers=78,
        num_attention_heads=64,
        num_key_value_heads=64,
        head_dim=64,
        rms_norm_eps=1e-5,
        rope_theta=1000000.0,
        max_seq_len=202752,
        q_lora_rank=2048,
        kv_lora_rank=512,
        qk_nope_head_dim=192,
        qk_rope_head_dim=64,
        v_head_dim=256,
        index_n_heads=32,
        index_head_dim=128,
        index_topk=2048,
        first_k_dense_replace=3,
        moe_intermediate_size=2048,
        n_shared_experts=1,
        n_routed_experts=16,
        router_experts=256,
        first_expert=0,
        num_experts_per_tok=8,
        scoring_func="sigmoid",
        router_bias=True,
        n_group=1,
        topk_group=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        bos_token_id=0,
        eos_token_id=1,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def longcat_flash_ep32(**overrides) -> LlamaConfig:
    """LongCat-Flash (the language model of https://huggingface.co/
    meituan-longcat/LongCat-Flash-Omni, `model_type` "longcat_flash") at
    its published widths, as ONE chip of the 32 that share each layer's
    512 experts holds it: global experts 0-15 beside the whole router (768
    outputs: the 512 experts and 256 zero-compute identities, top-12 on
    softmax share + bias, the chosen shares times 6 and not renormalised),
    both latent attentions (64 heads of 128 + 64 over a 1536-wide query
    and a 512-wide key latent, the two `mla_scale_*` factors) and both
    dense 12288-wide feed-forwards of every double layer. 28 double layers
    as published (56 cache planes); a chip serves the depth of its
    pipeline stage (`num_hidden_layers=`) and its slice of the vocabulary
    (`vocab_size=`)."""
    base = dict(
        model_type="longcat_flash",
        vocab_size=131072,
        hidden_size=6144,
        intermediate_size=12288,
        num_hidden_layers=28,
        num_attention_heads=64,
        num_key_value_heads=64,
        rms_norm_eps=1e-5,
        rope_theta=10000000.0,
        max_seq_len=131072,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        mla_scale_q_lora=True,
        mla_scale_kv_lora=True,
        moe_intermediate_size=2048,
        n_routed_experts=16,
        router_experts=512,
        first_expert=0,
        zero_expert_num=256,
        zero_expert_type="identity",
        num_experts_per_tok=12,
        scoring_func="softmax",
        router_bias=True,
        norm_topk_prob=False,
        routed_scaling_factor=6.0,
        bos_token_id=0,
        eos_token_id=1,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def ouro_2_6b(**overrides) -> LlamaConfig:
    """Ouro-2.6B (https://huggingface.co/ByteDance/Ouro-2.6B, `model_type`
    "ouro") at its published sizes: 48 sandwich-normed layers of 16 query
    heads over 16 key/value heads of 128 and a 5632-wide SwiGLU, run four
    times a token over ONE set of weights with the last norm between
    passes; an untied head; 192 cache planes (1.5 MiB a token in bf16)."""
    base = dict(
        model_type="ouro",
        vocab_size=49152,
        hidden_size=2048,
        intermediate_size=5632,
        num_hidden_layers=48,
        num_attention_heads=16,
        num_key_value_heads=16,
        head_dim=128,
        rms_norm_eps=1e-6,
        rope_theta=1000000.0,
        max_seq_len=65536,
        total_ut_steps=4,
        early_exit_threshold=1.0,
        bos_token_id=1,
        eos_token_id=2,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def evabyte_6p5b(**overrides) -> LlamaConfig:
    """EvaByte 6.5B (https://huggingface.co/EvaByte/EvaByte, `model_type`
    "evabyte") at its published sizes: 32 pre-norm layers of EVA attention
    (32 heads of 128, an exact window of 2048 positions that resets, one
    summary row for every 16 positions of the windows before it) and an
    11008-wide SwiGLU over a byte vocabulary of 320 (256 bytes + 64
    special ids) with eight prediction heads, of which head 0 is the
    model's own next byte; norm weights stored as ``w - 1``."""
    base = dict(
        model_type="evabyte",
        attention_class="eva",
        vocab_size=320,
        hidden_size=4096,
        intermediate_size=11008,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=32,
        rms_norm_eps=1e-5,
        rope_theta=100000.0,
        max_seq_len=32768,
        window_size=2048,
        chunk_size=16,
        num_pred_heads=8,
        bos_token_id=1,
        eos_token_id=2,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def _repeated(pattern, layers: int) -> tuple[str, ...]:
    """``layer_types`` for ``layers`` layers from one period of the
    pattern (or from the whole list: it then is, or is cut to, them)."""
    return tuple(pattern[i % len(pattern)] for i in range(layers))


def _window_layers_rotate(theta: float) -> dict:
    """K-EXAONE's ``layer_rope``: the window layers rotate plainly, the
    full ones carry no position embedding."""
    return {"sliding_attention": {"rope_type": "default",
                                  "rope_theta": float(theta)},
            "full_attention": None}


def kexaone_ep8(**overrides) -> LlamaConfig:
    """K-EXAONE-236B-A23B (https://huggingface.co/LGAI-EXAONE/
    K-EXAONE-236B-A23B, `model_type` "exaone_moe") at its published
    widths, as ONE chip of the 8 that share each layer's 128 experts holds
    it: global experts 0-15 beside the whole router (and its bias),
    attention and shared expert. 48 layers as published: a window of 128
    on every layer but each fourth, which attends fully with no position
    embedding; one leading dense layer. A chip serves the depth of its
    pipeline stage (`num_hidden_layers=` with `layer_types=` cut to it)
    and its slice of the vocabulary (`vocab_size=`)."""
    base = dict(
        model_type="exaone_moe",
        vocab_size=153600,
        hidden_size=6144,
        intermediate_size=18432,
        num_hidden_layers=48,
        num_attention_heads=64,
        num_key_value_heads=8,
        head_dim=128,
        rms_norm_eps=1e-5,
        rope_theta=1000000.0,
        max_seq_len=262144,
        sliding_window=128,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        qk_norm=True,
        first_k_dense_replace=1,
        moe_intermediate_size=2048,
        n_shared_experts=1,
        n_routed_experts=16,
        router_experts=128,
        first_expert=0,
        num_experts_per_tok=8,
        scoring_func="sigmoid",
        router_bias=True,
        n_group=1,
        topk_group=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        bos_token_id=0,
        eos_token_id=1,
    )
    base.update(overrides)
    base["layer_types"] = _repeated(base["layer_types"],
                                    base["num_hidden_layers"])
    base.setdefault("layer_rope", _window_layers_rotate(base["rope_theta"]))
    return LlamaConfig(**base)


def mellum2_12b(**overrides) -> LlamaConfig:
    """Mellum2-12B-A2.5B-Instruct (https://huggingface.co/JetBrains/
    Mellum2-12B-A2.5B-Instruct, `model_type` "mellum") at its published
    sizes: 28 layers, a window of 1024 on every layer but each fourth; the
    window layers rotate q and k plainly (theta 5e5) and the full ones
    under YaRN (factor 16 over an original 8192, ``attention_factor``
    1.2772588722239782 on cos and sin): two rotations in one model, by
    layer kind. 32 QK-normed query heads over 4 key/value heads of 128;
    every layer routes over all 64 softmax-scored experts (896 wide) top-8
    with the chosen shares renormalised, no shared expert, no bias; an
    untied head. A chip serves the depth of its pipeline stage
    (`num_hidden_layers=` with `layer_types=` cut to it)."""
    base = dict(
        model_type="mellum",
        vocab_size=98304,
        hidden_size=2304,
        intermediate_size=7168,
        num_hidden_layers=28,
        num_attention_heads=32,
        num_key_value_heads=4,
        head_dim=128,
        rms_norm_eps=1e-6,
        max_seq_len=131072,
        sliding_window=1024,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        layer_rope={
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782}},
        qk_norm=True,
        moe_intermediate_size=896,
        n_routed_experts=64,
        num_experts_per_tok=8,
        scoring_func="softmax",
        norm_topk_prob=True,
        bos_token_id=0,
        eos_token_id=1,
    )
    base.update(overrides)
    base["layer_types"] = _repeated(base["layer_types"],
                                    base["num_hidden_layers"])
    return LlamaConfig(**base)


def qwen3next_ep4(**overrides) -> LlamaConfig:
    """Qwen3-Next-80B-A3B-Instruct (https://huggingface.co/Qwen/
    Qwen3-Next-80B-A3B-Instruct, `model_type` "qwen3_next") at its
    published widths, as ONE chip of the 4 that share each layer's 512
    experts holds it: global experts 0-127 beside the whole router, mixers
    and gated shared expert. 48 layers as published, every one sparse: a
    scalar-gated delta rule (16 key heads under 32 value heads of 128 x
    128 state, 4 taps) but each fourth, which is gated grouped-query
    attention of 16 QK-normed query heads over 2 key/value heads of 256,
    the first 64 channels rotated; top-10 of 512 softmax-scored experts
    (512 wide). A chip serves the depth of its pipeline stage
    (`num_hidden_layers=`; `layer_types` is cut to it) and its slice of
    the vocabulary (`vocab_size=`)."""
    base = dict(
        model_type="qwen3_next",
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=5120,
        num_hidden_layers=48,
        num_attention_heads=16,
        num_key_value_heads=2,
        head_dim=256,
        rms_norm_eps=1e-6,
        rope_theta=10000000.0,
        rope_fraction=0.25,
        max_seq_len=262144,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_num_key_heads=16,
        linear_num_value_heads=32,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
        qk_norm=True,
        attn_gate="elementwise",
        moe_intermediate_size=512,
        n_shared_experts=1,
        shared_expert_gate=True,
        n_routed_experts=128,
        router_experts=512,
        first_expert=0,
        num_experts_per_tok=10,
        scoring_func="softmax",
        norm_topk_prob=True,
        bos_token_id=0,
        eos_token_id=1,
    )
    base.update(overrides)
    base["layer_types"] = _repeated(base["layer_types"],
                                    base["num_hidden_layers"])
    return LlamaConfig(**base)


# LFM2-8B-A1B's 24 layers: c c A, then c c c A four times, c c A c c
_LFM2_8B_LAYERS = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


def lfm2_8b_a1b(**overrides) -> LlamaConfig:
    """LFM2-8B-A1B (https://huggingface.co/LiquidAI/LFM2-8B-A1B,
    `model_type` "lfm2_moe") at its published sizes: 24 layers, gated
    short convolutions of 3 taps but layers 2, 6, 10, 14, 18 and 21, which
    are attention of 32 roped, QK-normed query heads of 64 over 8
    key/value heads; two leading dense layers (7168), then all 32
    bias-corrected sigmoid-scored experts (1792) top-4 and no shared one;
    a tied head. A chip serves the depth of its pipeline stage
    (`num_hidden_layers=`; `layer_types` is cut to it)."""
    base = dict(
        model_type="lfm2_moe",
        vocab_size=65536,
        hidden_size=2048,
        intermediate_size=7168,
        num_hidden_layers=24,
        num_attention_heads=32,
        num_key_value_heads=8,
        rms_norm_eps=1e-5,
        rope_theta=1000000.0,
        max_seq_len=128000,
        tie_word_embeddings=True,
        layer_types=_LFM2_8B_LAYERS,
        qk_norm=True,
        conv_L_cache=3,
        conv_bias=False,
        first_k_dense_replace=2,
        moe_intermediate_size=1792,
        n_routed_experts=32,
        num_experts_per_tok=4,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        routed_scaling_factor=1.0,
        bos_token_id=1,
        eos_token_id=7,
    )
    base.update(overrides)
    base["layer_types"] = _repeated(base["layer_types"],
                                    base["num_hidden_layers"])
    return LlamaConfig(**base)


def tiny(**overrides) -> LlamaConfig:
    """Tiny random-weight config for tests (SURVEY.md §4 test strategy)."""
    base = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        rope_theta=10000.0,
        bos_token_id=1,
        eos_token_id=2,
        max_seq_len=128,
        dtype="float32",
    )
    base.update(overrides)
    return LlamaConfig(**base)


def tiny_moe(**overrides) -> LlamaConfig:
    """Tiny Mixtral-shaped fixture (4 experts, top-2)."""
    base = dict(model_type="mixtral", num_local_experts=4,
                num_experts_per_tok=2)
    base.update(overrides)
    return tiny(**base)


def tiny_mla_moe(**overrides) -> LlamaConfig:
    """Tiny latent-attention, shared-expert fixture that keeps every ratio
    of the published family (DeepSeek-V3's keys): rope/nope split of a
    head, q and kv latents narrower than the heads they expand to, one
    leading dense layer then expert layers, 16 sigmoid-scored experts in
    4 groups of which 2 are kept, top-4 inside them, one shared expert,
    YaRN over a short original window."""
    base = dict(
        model_type="deepseek_v3",
        num_hidden_layers=3,
        num_key_value_heads=4,
        q_lora_rank=24,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        first_k_dense_replace=1,
        moe_intermediate_size=32,
        n_shared_experts=1,
        n_routed_experts=16,
        num_experts_per_tok=4,
        scoring_func="sigmoid",
        n_group=4,
        topk_group=2,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        rope_scaling={"type": "yarn", "factor": 4.0, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
                      "original_max_position_embeddings": 32},
    )
    base.update(overrides)
    return tiny(**base)


def tiny_xing4(**overrides) -> LlamaConfig:
    """Tiny fixture of the latent family under a wide residual stream
    (Xing4.0's keys): four hidden vectors a token, 20 Sinkhorn rounds, one
    leading dense layer then two expert layers of 16 bias-corrected
    sigmoid-scored experts top-4 in ONE group beside a shared one, YaRN
    over a short original window."""
    base = dict(
        model_type="xing4_0",
        router_bias=True,
        n_group=1,
        topk_group=1,
        routed_scaling_factor=2.0,
        rms_norm_eps=1e-6,
        hc_mult=4,
    )
    base.update(overrides)
    return tiny_mla_moe(**base)


def tiny_glm_dsa(**overrides) -> LlamaConfig:
    """Tiny fixture of the latent family under a learned sparse attention
    (GLM-5's keys): plain rope, heads as wide for keys as for values (16 +
    8 = 24, as 192 + 64 = 256), an indexer of 4 heads of 16 that chooses
    ``index_topk`` 8 rows, fewer than the test contexts hold, one leading
    dense layer then two expert layers of 16 bias-corrected
    sigmoid-scored experts top-4 in ONE group beside a shared one."""
    base = dict(
        model_type="glm_moe_dsa",
        rope_scaling=None,
        rope_theta=10000.0,
        v_head_dim=24,
        index_n_heads=4,
        index_head_dim=16,
        index_topk=8,
        router_bias=True,
        n_group=1,
        topk_group=1,
        rms_norm_eps=1e-5,
    )
    base.update(overrides)
    return tiny_mla_moe(**base)


def tiny_longcat_flash(**overrides) -> LlamaConfig:
    """Tiny fixture of the shortcut-connected double layer (LongCat-Flash's
    keys) that keeps the published ratios: three double layers (six cache
    planes), latents narrower than the heads they expand to with both
    `mla_scale_*` factors on (1.63 and 2), a router of 24 outputs, 16
    experts (all held) and 8 zero-compute identities, a third of them as
    published, top-4 on softmax share + bias, the chosen shares times 6
    and not renormalised, no shared expert, plain rope."""
    base = dict(
        model_type="longcat_flash",
        num_hidden_layers=3,
        num_key_value_heads=4,
        q_lora_rank=24,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        mla_scale_q_lora=True,
        mla_scale_kv_lora=True,
        moe_intermediate_size=32,
        n_routed_experts=16,
        zero_expert_num=8,
        num_experts_per_tok=4,
        scoring_func="softmax",
        router_bias=True,
        norm_topk_prob=False,
        routed_scaling_factor=6.0,
        rope_theta=10000000.0,
    )
    base.update(overrides)
    return tiny(**base)


def tiny_kda_hybrid(**overrides) -> LlamaConfig:
    """Tiny fixture of the delta-rule + latent hybrid that keeps the
    published family's ratios (Ling-3.0's keys): period 3 (K K M), one
    leading dense layer, a direct query projection, a head-wise gate on
    the latent layers, 16 bias-corrected sigmoid-scored experts in 4
    groups of which 2 are kept, top-4, one shared expert."""
    base = dict(
        model_type="bailing_hybrid",
        num_hidden_layers=4,
        num_key_value_heads=4,
        head_dim=16,
        q_lora_rank=None,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        attn_gate="head_wise",
        layer_group_size=3,
        first_k_dense_replace=1,
        moe_intermediate_size=32,
        n_shared_experts=1,
        n_routed_experts=16,
        num_experts_per_tok=4,
        scoring_func="sigmoid",
        router_bias=True,
        n_group=4,
        topk_group=2,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        rms_norm_eps=1e-6,
    )
    base.update(overrides)
    return tiny(**base)


def tiny_jamba(**overrides) -> LlamaConfig:
    """Tiny fixture of the state-space + attention hybrid that keeps the
    published family's pattern (Jamba's keys): attention is one layer in
    four (M A M M, twice: a period that starts and ends in Mamba layers,
    as the published M7 A M6 does), one key/value head under four query
    heads, 128 channels of an 8-wide state through a rank-8 step size, a
    convolution bias, a tied head."""
    base = dict(
        model_type="jamba",
        num_hidden_layers=8,
        num_key_value_heads=1,
        attn_layer_period=4,
        attn_layer_offset=1,
        mamba_d_state=8,
        mamba_dt_rank=8,
        tie_word_embeddings=True,
        rms_norm_eps=1e-6,
    )
    base.update(overrides)
    return tiny(**base)


def tiny_exaone_moe(**overrides) -> LlamaConfig:
    """Tiny fixture of the window + full attention family that keeps the
    published pattern (K-EXAONE's keys): two whole ``LLLG`` periods (a
    window of 8 on every layer but each fourth, which attends fully and
    rotates nothing; a ring of 16 rows), QK-normed heads, a leading dense
    layer, 16 bias-corrected sigmoid-scored experts top-4 of which 4 are
    held (rank 1 of 4), one shared expert."""
    base = dict(
        model_type="exaone_moe",
        num_hidden_layers=8,
        head_dim=16,
        sliding_window=8,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        qk_norm=True,
        first_k_dense_replace=1,
        moe_intermediate_size=32,
        n_shared_experts=1,
        n_routed_experts=4,
        router_experts=16,
        first_expert=4,
        num_experts_per_tok=4,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
    )
    base.update(overrides)
    base["layer_types"] = _repeated(base["layer_types"],
                                    base["num_hidden_layers"])
    base.setdefault("layer_rope", _window_layers_rotate(
        base.get("rope_theta", 10000.0)))
    return tiny(**base)


def tiny_mellum(**overrides) -> LlamaConfig:
    """Tiny fixture of the window + full attention family under Mellum's
    keys, which keeps the published pattern: two whole ``LLLG`` periods (a
    window of 8 over a ring of 16 rows), the window layers rotated plainly
    and the full ones under YaRN (factor 4 over an original 16 positions,
    an explicit ``attention_factor``), QK-normed heads, every layer
    sparse: all 16 softmax-scored experts top-4, the chosen shares
    renormalised, no shared expert, no bias, an untied head."""
    base = dict(
        model_type="mellum",
        num_hidden_layers=8,
        head_dim=16,
        sliding_window=8,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        layer_rope={
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000.0},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                "original_max_position_embeddings": 16, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.1386294361119891}},
        qk_norm=True,
        moe_intermediate_size=32,
        n_routed_experts=16,
        num_experts_per_tok=4,
        scoring_func="softmax",
        norm_topk_prob=True,
        rms_norm_eps=1e-6,
    )
    base.update(overrides)
    base["layer_types"] = _repeated(base["layer_types"],
                                    base["num_hidden_layers"])
    return tiny(**base)


def tiny_qwen3_next(**overrides) -> LlamaConfig:
    """Tiny fixture of the scalar-gated delta-rule + gated attention family
    that keeps the published ratios (Qwen3-Next's keys): two whole ``D D D
    A`` periods, 2 key heads under 4 value heads of 16 x 8 state (a key
    width that differs from the value width hides no mix-up), 4 taps; 4
    gated, QK-normed query heads over 2 key/value heads of 16 whose first
    8 channels rotate; every layer sparse: 16 softmax-scored experts top-4
    of which 4 are held (rank 1 of 4) beside ONE gated shared expert."""
    base = dict(
        model_type="qwen3_next",
        num_hidden_layers=8,
        head_dim=16,
        rope_fraction=0.5,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_num_key_heads=2,
        linear_num_value_heads=4,
        linear_key_head_dim=16,
        linear_value_head_dim=8,
        linear_conv_kernel_dim=4,
        qk_norm=True,
        attn_gate="elementwise",
        moe_intermediate_size=32,
        n_shared_experts=1,
        shared_expert_gate=True,
        n_routed_experts=4,
        router_experts=16,
        first_expert=4,
        num_experts_per_tok=4,
        scoring_func="softmax",
        norm_topk_prob=True,
        rms_norm_eps=1e-6,
        rope_theta=10000000.0,
    )
    base.update(overrides)
    base["layer_types"] = _repeated(base["layer_types"],
                                    base["num_hidden_layers"])
    return tiny(**base)


def tiny_ouro(**overrides) -> LlamaConfig:
    """Tiny fixture of the looped family (Ouro's keys): three layers run
    three times a token (nine cache planes: a count of passes that differs
    from every other count would hide no mix-up, so both are 3 and the
    tests tell a pass from a layer by their order), as many key/value
    heads as query heads, an untied head."""
    base = dict(
        model_type="ouro",
        num_hidden_layers=3,
        num_key_value_heads=4,
        total_ut_steps=3,
        rms_norm_eps=1e-6,
        rope_theta=1000000.0,
    )
    base.update(overrides)
    return tiny(**base)


def tiny_evabyte(**overrides) -> LlamaConfig:
    """Tiny fixture of the EVA family (EvaByte's keys): three layers of
    four heads over a window of 32 positions in chunks of 4 (eight summary
    rows a completed window), two prediction heads, 128 positions: a
    prompt and an answer cross chunk ends and a window reset within a
    few dozen tokens."""
    base = dict(
        model_type="evabyte",
        attention_class="eva",
        num_hidden_layers=3,
        num_key_value_heads=4,
        window_size=32,
        chunk_size=4,
        num_pred_heads=2,
        rms_norm_eps=1e-5,
        rope_theta=100000.0,
    )
    base.update(overrides)
    return tiny(**base)


def tiny_lfm2_moe(**overrides) -> LlamaConfig:
    """Tiny fixture of the short-convolution + attention family that keeps
    the published pattern (LFM2-MoE's keys): ``c c A c c A c c A c``
    (two leading conv layers, ``A c c`` twice, a ragged end), 3 taps,
    QK-normed roped heads of 16, two leading dense layers, then all 8
    bias-corrected sigmoid-scored experts top-2 and no shared one, a tied
    head."""
    base = dict(
        model_type="lfm2_moe",
        num_hidden_layers=10,
        layer_types=tuple("full_attention" if i in (2, 5, 8) else "conv"
                          for i in range(10)),
        qk_norm=True,
        conv_L_cache=3,
        first_k_dense_replace=2,
        moe_intermediate_size=32,
        n_routed_experts=8,
        num_experts_per_tok=2,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        routed_scaling_factor=1.0,
        tie_word_embeddings=True,
        rope_theta=1000000.0,
    )
    base.update(overrides)
    base["layer_types"] = _repeated(base["layer_types"],
                                    base["num_hidden_layers"])
    return tiny(**base)
