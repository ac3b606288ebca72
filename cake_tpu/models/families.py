"""What is wired for each family of models, declared once.

A family is one :class:`Family` record; ``LlamaConfig.family`` finds it
from a configuration's fields, ``LlamaConfig.from_hf_dict`` from a file's
``model_type`` (``BY_MODEL_TYPE``). Consumers look the record up and
ask it; what a cache HOLDS is asked of ``LlamaConfig.cache_plan``. The
records are data: nothing outside ``cake_tpu/models/`` makes or edits one,
and no flag sets one. Every reader refuses, and does not guess, what its
file asks for and nothing here computes; the readings this repo makes of
a file are its benchmark configuration's ``assumed``.

The records (``FAMILIES``, the first that selects a configuration wins):
``LOOPED`` (``ouro``: ``total_ut_steps`` passes over one set of
sandwich-normed layers, the last norm closing each pass, a cache plane a
layer AND a pass; keys ``total_ut_steps``, ``early_exit_threshold``;
limits: threshold 1 only, every layer full attention, no window, no rope
scaling, slot layout, no sharding, no quantized tier), ``SHORT_CONV``
(``lfm2_moe``), ``GATED_DELTA`` (``qwen3_next``: scalar-gated delta-rule
layers, whose state is shaped by the MIXER's own heads, beside gated,
part-rotated grouped-query attention, softmax-scored experts beside a
gated shared one; norm weights stored as ``w - 1`` and fused projections
stored a key head's group at a time, both folded where the tensors are
read: ``Fold``), ``WINDOWED`` (``exaone_moe``, and ``mellum``: the rotation
a layer KIND is data read from the file, ``LlamaConfig.layer_rope``),
``STATE_SPACE`` (``jamba``), ``SHORTCUT`` (``longcat_flash``: a
shortcut-connected double layer, two latent attentions and two dense
feed-forwards around one expert block whose result lands a sub-layer
late, two cache planes a layer; a router that scores zero-compute
outputs behind its experts, by softmax over all of them; the two
``mla_scale_*`` factors folded into the latents' norm weights where the
tensors are read: ``Fold``; limits: identity zero experts only, slot
layout, ``ep`` alone, no quantized tier),
``EVA`` (``evabyte``: multi-head attention over an exact window that
RESETS every ``window_size`` positions and one learned summary row for
every ``chunk_size`` positions of the windows completed before it, one
softmax over both, ops/eva.py; no layer holds a row a position: the cache
is a ring and a plane of summaries, ``cache_plan``'s ``summary``; norm
weights stored as ``w - 1``, a head of ``num_pred_heads`` blocks of which
block 0 is loaded; limits: as many key/value heads as query heads, slot
layout, no sharding, no quantized tier, whole-prompt admission),
``HYBRID`` (``bailing_hybrid``), ``LATENT`` (``deepseek_v3``, ``axk1``,
``xing4_0``, ``glm_moe_dsa``) and ``GQA``, the bare stack every other
``model_type`` is read as. A residual stream several hidden vectors wide (``hc_mult`` > 1:
manifold-constrained hyper-connections, ``ops/hyper.py``) is no mixer and
no record of its own: ``LATENT`` reads its keys, every other family
refuses them (``check_residual_path``). A learned sparse attention
(``index_topk`` > 0: an indexer of ``index_n_heads`` heads of
``index_head_dim`` scores every cached row and a query attends the
``index_topk`` best, ``ops/dsa.py``) is the same: ``LATENT`` reads its keys
(``glm_moe_dsa``) and keeps one index key a token a layer beside the
latent row (``cache_plan``'s ``index``), every other family refuses them
(``check_indexer``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple


def _nothing(*_):
    return {}


@dataclasses.dataclass(frozen=True)
class Family:
    # HF `model_type`s whose config.json `read` reads, and whether a
    # configuration's FIELDS say it is of this family
    model_types: tuple[str, ...]
    selects: Callable[[Any], bool]
    # config.json <-> fields: the fields only this family's file carries;
    # file -> fields to set beside the keys that are fields; (config, dict
    # of its fields) -> the family's own spelling, in place; what a
    # configuration may ask for (LlamaConfig.__post_init__: it fills in
    # what the family derives)
    fields: tuple[str, ...] = ()
    read: Callable[[dict], dict] = _nothing
    write: Callable[[Any, dict], Any] = _nothing
    check: Callable[[Any], Any] = _nothing
    # `layer_types` entry -> the layer's mixer, where the family reads one
    layer_mixers: dict | None = None
    # the mixer of its layers that carry something from token to token in
    # place of rows: "kda" (ops/kda.py), "mamba" (ops/mamba.py), "conv"
    # (ops/shortconv.py: a tail of inputs and NO state), or None
    recurrent_mixer: str | None = None
    # checkpoint tensors under `model.layers.{i}.`: ours -> (HF suffix,
    # transpose?[, Fold: how it is stored where that is not how the program
    # holds it]), a stack taking its own; ours -> a routed expert's
    # pattern ({e}: its global id); the last norm (and its Fold); part of
    # a name only this family's checkpoints store
    tensor_names: dict = dataclasses.field(default_factory=dict)
    expert_names: dict = dataclasses.field(default_factory=dict)
    final_norm: str = "model.norm.weight"
    final_norm_fold: Any = None
    probe: str | None = None
    # tensors beside the layers, the embedding, the last norm and the
    # head: ours (a key of ``params``) -> {part: (HF name, shape(config))}
    extra_tensors: dict = dataclasses.field(default_factory=dict)
    # what is wired, and the family's sentence on why where it is not
    # (`what`: the family as a refusal names it): the mesh axes that may be
    # more than 1, the quantized tiers of its linears and of its cache
    what: str = ""
    shard_axes: frozenset = frozenset(("stages", "tp", "sp", "ep"))
    shard_why: str = ""
    linear_tiers: tuple[str, ...] = ("int8", "int4")
    linear_why: str = ""
    cache_tiers: tuple[str, ...] = ("int8",)
    cache_why: str = ""
    # whether its expert layers count the routed pairs that fall on the
    # held experts (an expert model told its share)
    counts_held_experts: bool = False
    # where a block's arithmetic differs: whether a repeated period of
    # expert layers is scanned as one run (models/llama.py layer_plan says
    # why not); what the chosen experts' scores are normalised over,
    # beside their sum. (Which layers rotate q and k, and how, is no
    # family's: a configuration's ``layer_rope`` says it a layer kind.)
    expert_periods: bool = True
    topk_norm_eps: float = 1e-20
    # whether the layer loop runs its plan ``total_ut_steps`` times over
    # one set of weights with the model's last norm at the end of each
    # pass (models/llama.py forward_layers): the head then norms nothing
    loops: bool = False
    # cache planes a layer keeps: 2 where one published layer is a
    # shortcut-connected double layer (two latent attentions; models/llama.py
    # ``_double_block``: its attention ``j`` of layer ``l`` is plane ``2 l +
    # j`` and ``LlamaConfig.layer_kinds`` names its mixer "mla2")
    planes_a_layer: int = 1


# --- tensor names -----------------------------------------------------------

# our stacked name -> (HF suffix, transpose?)
_LAYER_MAP = {
    "attn_norm": ("input_layernorm.weight", False),
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "mlp_norm": ("post_attention_layernorm.weight", False),
    "w_gate": ("mlp.gate_proj.weight", True),
    "w_up": ("mlp.up_proj.weight", True),
    "w_down": ("mlp.down_proj.weight", True),
}

# q/k/v projection biases (Qwen2 family; HF llama-arch `attention_bias`)
_BIAS_MAP = {
    "bq": ("self_attn.q_proj.bias", False),
    "bk": ("self_attn.k_proj.bias", False),
    "bv": ("self_attn.v_proj.bias", False),
}
# o_proj bias: HF llama-arch `attention_bias: true` biases o_proj too
# (Qwen2 does not) — tracked separately so each checkpoint loads exactly
# the tensors it stores.
_O_BIAS = ("bo", ("self_attn.o_proj.bias", False))

# Mixtral MoE naming: w1 = gate proj, w3 = up proj, w2 = down proj; the
# router is `block_sparse_moe.gate`. Expert tensors are stacked over a new
# leading E axis per layer ([L, E, in, out] in the pytree).
_MOE_EXPERT_MAP = {
    "w_gate": "block_sparse_moe.experts.{e}.w1.weight",
    "w_up": "block_sparse_moe.experts.{e}.w3.weight",
    "w_down": "block_sparse_moe.experts.{e}.w2.weight",
}
_MOE_ROUTER = "block_sparse_moe.gate.weight"

# The latent-attention, shared-expert family (DeepSeek-V3's tensor names,
# which `model_type` "axk1" is assumed to share): every layer's attention,
# then either a dense MLP (the leading `first_k_dense_replace` layers) or a
# router, the shared experts and the routed experts BY THEIR GLOBAL IDS
# (a cut checkpoint holds a slice of them: models/config.py first_expert).
_LATENT_MAP = {
    "attn_norm": ("input_layernorm.weight", False),
    "wq_a": ("self_attn.q_a_proj.weight", True),
    "q_norm": ("self_attn.q_a_layernorm.weight", False),
    "wq_b": ("self_attn.q_b_proj.weight", True),
    "wkv_a": ("self_attn.kv_a_proj_with_mqa.weight", True),
    "kv_norm": ("self_attn.kv_a_layernorm.weight", False),
    "wkv_b": ("self_attn.kv_b_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "mlp_norm": ("post_attention_layernorm.weight", False),
}
_LATENT_DENSE_MAP = {k: _LAYER_MAP[k] for k in ("w_gate", "w_up", "w_down")}
_LATENT_MOE_MAP = {
    "router": ("mlp.gate.weight", True),
    "ws_gate": ("mlp.shared_experts.gate_proj.weight", True),
    "ws_up": ("mlp.shared_experts.up_proj.weight", True),
    "ws_down": ("mlp.shared_experts.down_proj.weight", True),
}
_LATENT_EXPERT_MAP = {
    "w_gate": "mlp.experts.{e}.gate_proj.weight",
    "w_up": "mlp.experts.{e}.up_proj.weight",
    "w_down": "mlp.experts.{e}.down_proj.weight",
}

# A residual stream ``hc_mult`` hidden vectors wide (`model_type` "xing4_0";
# the names are ASSUMED, the benchmark configuration lists them): each
# sub-layer's projection to its ``n^2 + 2n`` mixing coefficients as a torch
# linear ``[n^2 + 2n, n hidden]``, their biases and the three gains (pre,
# post, res), all float32 whatever the serving type.
_HC_MAP = {f"hc_{part}_{t}": (f"hc_{part}_{t}", t == "fn")
           for part in ("attn", "ffn") for t in ("fn", "base", "scale")}

# A learned sparse attention's indexer (`model_type` "glm_moe_dsa";
# DeepSeek-V3.2's names, which the benchmark configuration lists as
# ASSUMED for GLM-5): its queries from the query latent, one key a token
# behind a LayerNorm with a bias, a weight a head from the hidden state.
_INDEXER_MAP = {
    "idx_wq_b": ("self_attn.indexer.wq_b.weight", True),
    "idx_wk": ("self_attn.indexer.wk.weight", True),
    "idx_k_norm": ("self_attn.indexer.k_norm.weight", False),
    "idx_k_bias": ("self_attn.indexer.k_norm.bias", False),
    "idx_w": ("self_attn.indexer.weights_proj.weight", True),
}

# Delta-rule layers beside latent ones (`model_type` "bailing_hybrid"; the
# names are ASSUMED, the benchmark configuration lists them: FLA's KDA
# module under `self_attn.`, the convolutions as torch depthwise `[C, 1,
# K]`, DeepSeek-V3's names for what the two families share) and what the
# family adds to a latent layer and to the router.
_KDA_MAP = {
    "kda_q": ("self_attn.q_proj.weight", True),
    "kda_k": ("self_attn.k_proj.weight", True),
    "kda_v": ("self_attn.v_proj.weight", True),
    "conv_q": ("self_attn.q_conv1d.weight", True),
    "conv_k": ("self_attn.k_conv1d.weight", True),
    "conv_v": ("self_attn.v_conv1d.weight", True),
    "w_decay": ("self_attn.f_proj.weight", True),
    "a_log": ("self_attn.A_log", False),
    "dt_bias": ("self_attn.dt_bias", False),
    "w_beta": ("self_attn.b_proj.weight", True),
    "o_norm": ("self_attn.o_norm.weight", False),
}
_HYBRID_EXTRA_MAP = {
    "wq": ("self_attn.q_proj.weight", True),
    "wg": ("self_attn.g_proj.weight", True),
}
# the router's correction bias, under each family's own name
_HYBRID_BIAS = {"b_router": ("mlp.gate.expert_bias", False)}
_LATENT_BIAS = {"b_router": ("mlp.gate.e_score_correction_bias", False)}

# Window and full grouped-query attention mixed by layer, with the
# shared-expert feed-forward (`model_type` "exaone_moe" and "mellum"; the
# names are ASSUMED, the benchmark configurations list them: Llama's for the
# attention with a `q_norm` / `k_norm` weight a head width wide,
# DeepSeek-V3's for the experts and for the router's bias, which are
# Qwen3-MoE's too: `mlp.gate.weight`, `mlp.experts.{e}.*`). A layer is
# asked for the tensors its shapes name (a Mellum layer for no shared
# expert and no bias). The next-token prediction block (`mtp.*`) is never
# asked for.
_WINDOWED_MAP = {
    **{k: _LAYER_MAP[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                  "mlp_norm")},
    "q_norm": ("self_attn.q_norm.weight", False),
    "k_norm": ("self_attn.k_norm.weight", False),
    **_LATENT_DENSE_MAP, **_LATENT_MOE_MAP, **_LATENT_BIAS,
}

# State-space layers beside grouped-query attention (`model_type` "jamba",
# Hugging Face's own names, which config.json does not carry: the benchmark
# configuration lists them): the mixer under `mamba.`, its convolution as
# torch depthwise `[C, 1, K]` WITH a bias, `A_log` as `[d_inner, d_state]`
# (ours is laid out as the state is, `[d_state, d_inner]`), the feed-forward
# under `feed_forward.`, its norm `pre_ff_layernorm`, the model's last norm
# `model.final_layernorm`.
_MAMBA_MAP = {
    "w_in": ("mamba.in_proj.weight", True),
    "conv_w": ("mamba.conv1d.weight", True),
    "conv_b": ("mamba.conv1d.bias", False),
    "w_x": ("mamba.x_proj.weight", True),
    "dt_norm": ("mamba.dt_layernorm.weight", False),
    "b_norm": ("mamba.b_layernorm.weight", False),
    "c_norm": ("mamba.c_layernorm.weight", False),
    "w_dt": ("mamba.dt_proj.weight", True),
    "dt_bias": ("mamba.dt_proj.bias", False),
    "a_log": ("mamba.A_log", True),
    "d_skip": ("mamba.D", False),
    "w_out": ("mamba.out_proj.weight", True),
}
_STATE_SPACE_MAP = {
    **{k: _LAYER_MAP[k] for k in ("attn_norm", "wq", "wk", "wv", "wo")},
    "mlp_norm": ("pre_ff_layernorm.weight", False),
    "w_gate": ("feed_forward.gate_proj.weight", True),
    "w_up": ("feed_forward.up_proj.weight", True),
    "w_down": ("feed_forward.down_proj.weight", True),
}

# Gated short convolutions beside grouped-query attention, every expert
# held (`model_type` "lfm2_moe"; the names are ASSUMED, the benchmark
# configuration lists them: Hugging Face's Lfm2Moe modules): a layer's
# norms `operator_norm` and `ffn_norm`, the mixer under `conv.` with its
# taps as torch depthwise `[C, 1, K]`, the attention's output projection
# `out_proj` and head norms `q_layernorm` / `k_layernorm`, the feed-forward
# under `feed_forward.` in Mixtral's w1 (gate) / w3 (up) / w2 (down), the
# router `feed_forward.gate` with its `expert_bias`, the model's last norm
# `model.embedding_norm`.
_SHORT_CONV_MAP = {
    "attn_norm": ("operator_norm.weight", False),
    "w_in": ("conv.in_proj.weight", True),
    "conv_w": ("conv.conv.weight", True),
    "w_out": ("conv.out_proj.weight", True),
    **{k: _LAYER_MAP[k] for k in ("wq", "wk", "wv")},
    "wo": ("self_attn.out_proj.weight", True),
    "q_norm": ("self_attn.q_layernorm.weight", False),
    "k_norm": ("self_attn.k_layernorm.weight", False),
    "mlp_norm": ("ffn_norm.weight", False),
    "w_gate": ("feed_forward.w1.weight", True),
    "w_up": ("feed_forward.w3.weight", True),
    "w_down": ("feed_forward.w2.weight", True),
    "router": ("feed_forward.gate.weight", True),
    "b_router": ("feed_forward.expert_bias", False),
}
_SHORT_CONV_EXPERT_MAP = {
    "w_gate": "feed_forward.experts.{e}.w1.weight",
    "w_up": "feed_forward.experts.{e}.w3.weight",
    "w_down": "feed_forward.experts.{e}.w2.weight",
}

# A looped decoder: one set of sandwich-normed layers run several times a
# token (`model_type` "ouro"; the names are ASSUMED, the benchmark
# configuration lists them: Llama's, a sub-layer's second norm under its
# first one's name with `_2`, the exit gate a linear of one output).
_LOOPED_MAP = {
    **_LAYER_MAP,
    "attn_post_norm": ("input_layernorm_2.weight", False),
    "mlp_post_norm": ("post_attention_layernorm_2.weight", False),
}
_EXIT_GATE = {
    "weight": ("model.early_exit_gate.weight", lambda c: (1, c.hidden_size)),
    "bias": ("model.early_exit_gate.bias", lambda c: (1,)),
}


class Fold(NamedTuple):
    """How a checkpoint stores a tensor where that is not how the program
    holds it, as the third element of a ``tensor_names`` entry: ``load(
    config, stored)`` gives ours from the stored tensor in its logical
    layout (a linear ``[in, out]``, float32), ``save`` the inverse. Both
    loaders and the writer apply it to the whole tensor
    (``utils/sharded_load.py``, ``utils/weights.py``)."""

    load: Callable
    save: Callable


# a norm's weight stored as an offset from one (``x * rsqrt(..) * (1 +
# w)``): folded on load, so that the program's ``rms_norm`` stays one
# function
_ONE_PLUS = Fold(lambda c, w: 1.0 + w, lambda c, w: w - 1.0)


def _grouped_columns(c, widths) -> "np.ndarray":
    """Stored column of each of our columns for a projection fused a KEY
    HEAD'S GROUP at a time: the checkpoint stores, for key head 0, then 1,
    .., ``widths`` channels of each part side by side (``[q_0 | k_0 | v_0 |
    z_0 | q_1 | ..]``); ours is part by part (``[q | k | v | z]``, heads in
    order inside each)."""
    import numpy as np

    hk = c.delta_rule.key_heads
    starts = np.concatenate([[0], np.cumsum(widths)])
    base = np.arange(hk)[:, None] * starts[-1]
    return np.concatenate([
        (base + starts[i] + np.arange(w)).reshape(-1)
        for i, w in enumerate(widths)])


def _group_fold(widths_of) -> Fold:
    def load(c, w):
        return w[..., _grouped_columns(c, widths_of(c))]

    def save(c, w):
        import numpy as np

        out = np.empty_like(w)
        out[..., _grouped_columns(c, widths_of(c))] = w
        return out

    return Fold(load, save)


def _qkvz_widths(c):
    hk, hv, dk, dv, _ = c.delta_rule
    return (dk, dk, hv // hk * dv, hv // hk * dv)


# Scalar-gated delta-rule layers beside gated attention (`model_type`
# "qwen3_next"; Hugging Face's Qwen3Next modules, which config.json does not
# carry: the benchmark configuration lists them): the mixer under
# `linear_attn.`, its projections fused a key head's group at a time
# (`in_proj_qkvz`: `[q | k | v | z]` of key head 0, then of 1, ..;
# `in_proj_ba`: `[b | a]` likewise), ONE convolution over `[q | k | v]` as
# torch depthwise `[C, 1, K]`, `norm` a PLAIN weight; the attention's
# `q_proj` a head's `[q | gate]` side by side; every other norm stored as
# `w - 1`; the shared expert under `mlp.shared_expert.` (singular) with its
# gate `mlp.shared_expert_gate` a linear of one output.
_GATED_DELTA_MAP = {
    "attn_norm": ("input_layernorm.weight", False, _ONE_PLUS),
    "mlp_norm": ("post_attention_layernorm.weight", False, _ONE_PLUS),
    "w_qkvz": ("linear_attn.in_proj_qkvz.weight", True,
               _group_fold(_qkvz_widths)),
    "w_ba": ("linear_attn.in_proj_ba.weight", True, _group_fold(
        lambda c: (c.delta_rule.value_heads // c.delta_rule.key_heads,) * 2)),
    "conv_qkv": ("linear_attn.conv1d.weight", True),
    "a_log": ("linear_attn.A_log", False),
    "dt_bias": ("linear_attn.dt_bias", False),
    "o_norm": ("linear_attn.norm.weight", False),
    "w_out": ("linear_attn.out_proj.weight", True),
    **{k: _LAYER_MAP[k] for k in ("wq", "wk", "wv", "wo")},
    "q_norm": ("self_attn.q_norm.weight", False, _ONE_PLUS),
    "k_norm": ("self_attn.k_norm.weight", False, _ONE_PLUS),
    "router": ("mlp.gate.weight", True),
    "ws_gate": ("mlp.shared_expert.gate_proj.weight", True),
    "ws_up": ("mlp.shared_expert.up_proj.weight", True),
    "ws_down": ("mlp.shared_expert.down_proj.weight", True),
    "ws_share": ("mlp.shared_expert_gate.weight", True),
}


# --- checks shared by several families --------------------------------------

def _check_told_share(c, scorings=("sigmoid",), unnormalised=False):
    """The shared-expert feed-forward's routing, and the held experts'
    place among the router's (``router_experts`` is filled in).
    ``scorings``: the scoring functions the family's layers compute.
    ``"softmax"`` is wired in TWO forms, both over ALL the router's
    outputs and in one group: the chosen shares renormalised over their
    sum and nothing else, which IS softmax over the chosen logits
    (``ops/moe.py`` ``router_topk``'s ``routing=None`` form;
    ``testing/reference_mellum.py`` computes the long form and the tests
    hold the two together); and the chosen shares as they are (NOT
    renormalised), times a scaling factor, the choice on ``share + bias``
    where the model has one (``GroupRouting(scoring="softmax")``;
    ``testing/reference_longcat_flash.py``; ``unnormalised``: the family
    whose layers were held to that reference admits it). Groups, or a
    renormalised share beside a bias or a scaling factor, are neither."""
    if not c.n_routed_experts:
        return
    if c.scoring_func not in scorings:
        raise ValueError(
            f"scoring_func {c.scoring_func!r} is not wired for "
            f"{c.family.what or 'the shared-expert family'} (only "
            f"{', '.join(scorings)}; sigmoid is the group-limited routing)")
    if c.scoring_func == "softmax" and (
            c.n_group != 1 or c.topk_group != 1 or (c.norm_topk_prob and (
                c.router_bias or c.routed_scaling_factor != 1.0))
            or not (c.norm_topk_prob or unnormalised)):
        raise ValueError(
            "scoring_func 'softmax' is wired as softmax over all the "
            "router's outputs in one group, the chosen shares either "
            "renormalised (norm_topk_prob true, routed_scaling_factor 1, no "
            "routing bias) or, beside a shortcut-connected double layer's "
            "latent attention alone, left as they are (norm_topk_prob "
            "false: a scaling factor and a routing bias are then "
            "computed): got "
            f"norm_topk_prob {c.norm_topk_prob}, routed_scaling_factor "
            f"{c.routed_scaling_factor}, a bias {c.router_bias}, "
            f"{c.topk_group} of {c.n_group} groups")
    width = c.router_experts or c.n_routed_experts
    object.__setattr__(c, "router_experts", width)
    if width % c.n_group or not (
            0 <= c.first_expert <= width - c.n_routed_experts):
        raise ValueError(
            f"experts {c.first_expert}.."
            f"{c.first_expert + c.n_routed_experts - 1} "
            f"held of {width} in {c.n_group} groups")


def _check_layer_types(c, mixers) -> set:
    """What a per-layer ``layer_types`` may ask for and is computed;
    returns the kinds it names."""
    object.__setattr__(c, "layer_types", tuple(c.layer_types))
    kinds = set(c.layer_types)
    if (len(c.layer_types) != c.num_hidden_layers
            or not kinds <= set(mixers)):
        raise ValueError(
            f"layer_types needs one of {sorted(mixers)} for "
            f"each of the {c.num_hidden_layers} layers, got "
            f"{len(c.layer_types)} entries of {sorted(kinds)}")
    check_capacity(c)
    return kinds


# the kinds of ``LlamaConfig.cache_plan`` whose buffers grow with the
# capacity: a row a position, or a summary row for every few
_CAPACITY_KINDS = ("rows", "summary")


def check_capacity(c):
    """Whether the cache the fields describe can say how many positions
    it holds: asked of ``cache_plan``, which answers with ``rows`` (a row
    a position of the full layers) or ``summary`` (a row for every
    ``chunk_size`` positions). A model of window, state or tail layers
    alone has neither: nothing here allocates or retires by a capacity no
    buffer has."""
    plan = c.cache_plan
    if not set(plan) & set(_CAPACITY_KINDS):
        raise ValueError(
            "layer_types without a full_attention layer is not wired "
            f"(the cache would hold {sorted(plan)} and nothing that grows "
            "with the capacity: the capacity is asked of cache_plan's "
            f"{' or '.join(_CAPACITY_KINDS)})")


def _expert_share(d: dict, held: int) -> dict:
    """This chip's share of an ep deployment's experts (config.json
    ``expert_share``): the router's width and the first held expert."""
    share = d.get("expert_share")
    if not share:
        return {}
    return {"router_experts": share["n_routed_experts"],
            "first_expert": share["rank"] * held}


def _default_rope(name: str, rope: dict, scaling=None, where="") -> dict:
    """The file's rope parameters, which may ask for the default rotation
    and no scaling."""
    kind = rope.get("rope_type", rope.get("type", "default"))
    if kind != "default" or scaling:
        raise ValueError(
            f"{name}: rope type {kind!r} is not wired (default rotation, "
            f"no scaling{where})")
    return rope


def _only_served(name: str, d: dict, fixed: dict):
    """What a config.json may ask for that nothing here computes: key ->
    the only value served."""
    for key, only in fixed.items():
        if d.get(key, only) != only:
            raise ValueError(
                f"{name}: {key} = {d[key]!r} is not wired (only {only!r})")


def _entries(name: str, d: dict) -> list:
    types = list(d["layer_types"])
    if len(types) != d["num_hidden_layers"]:
        raise ValueError(
            f"{name}: layer_types has {len(types)} entries for "
            f"{d['num_hidden_layers']} layers")
    return types


_EXPERT_FIELDS = (
    "first_k_dense_replace", "moe_intermediate_size",
    "n_shared_experts", "n_routed_experts", "scoring_func", "n_group",
    "topk_group", "norm_topk_prob", "routed_scaling_factor",
)


# --- the dense and Mixtral-style decoders: one bare stack -------------------

def _gqa_read(d: dict) -> dict:
    """Family defaults not spelled out in the HF config dict: Qwen2's q/k/v
    bias is unconditional in its architecture (the HF config has no
    attention_bias key to read); Gemma's (1+w) RMSNorm, GeGLU, and
    sqrt(hidden) embedding scaling are likewise architectural."""
    out = {}
    if d.get("kv_lora_rank"):
        raise ValueError(
            f"model_type {d.get('model_type')!r} has latent-attention "
            f"keys but is not one of {sorted(LATENT.model_types)}")
    if d.get("model_type") == "qwen2" and "attention_bias" not in d:
        out["attention_bias"] = True
    if d.get("model_type") == "gemma":
        out["rms_norm_offset"] = d.get("rms_norm_offset", True)
        out["embed_scale"] = d.get("embed_scale", True)
        # HF Gemma spells the activation in `hidden_activation` (newer
        # configs) or `hidden_act`; both default to the tanh gelu
        act = d.get("hidden_activation") or d.get("hidden_act")
        if act not in (None, "gelu", "gelu_pytorch_tanh"):
            raise ValueError(f"unsupported gemma activation {act!r}")
        out["hidden_act"] = "gelu_tanh"
    # Qwen2 configs ship a sliding_window VALUE with the feature gated
    # off (`use_sliding_window: false`); honoring the value alone would
    # force windowed masking (and forfeit the flash kernels) on a model
    # that attends fully. When the gate is on, HF additionally windows
    # only layers >= max_window_layers — full-depth (0) and no-depth
    # (>= num layers) are uniform and supported; a partial depth would
    # need per-layer masks the stacked scan doesn't carry, so it is
    # rejected rather than silently diverging.
    if "use_sliding_window" in d and d.get("sliding_window") is not None:
        mwl = d.get("max_window_layers", 0)
        layers = d.get("num_hidden_layers", 32)
        if not d["use_sliding_window"] or mwl >= layers:
            out["sliding_window"] = None
        elif mwl > 0:
            raise ValueError(
                f"partial-depth sliding window "
                f"(max_window_layers={mwl} of {layers}) is not "
                "wired for this family's one bare stack; a "
                "window on some layers is read from a per-layer "
                f"layer_types list (model_type "
                f"{WINDOWED.model_types[0]!r})")
    return out


GQA = Family(model_types=(), selects=lambda c: True, read=_gqa_read)


# --- latent attention, shared and routed experts ----------------------------

def _latent_read(d: dict) -> dict:
    """DeepSeek-V3's keys. `topk_method` "noaux_tc" is the choice on
    ``score + e_score_correction_bias`` (``router_bias``: the tensor is
    then asked of the checkpoint); "none", "greedy" and
    "group_limited_greedy" are the group-limited choice n_group/topk_group
    describe with no bias tensor. ``hc_mult`` > 1 widens the residual
    stream (``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max``:
    ops/hyper.py). ``num_nextn_predict_layers`` (a next-token prediction
    block, the layer past ``num_hidden_layers`` or ``mtp.*``) is read and
    ignored: the block takes no part in the model's own logits and the
    loaders skip its tensors. ``index_topk`` > 0 (`model_type`
    "glm_moe_dsa") is a learned sparse attention over the latent cache
    (ops/dsa.py): such a file nests ``rope_theta`` in ``rope_parameters``
    (the default rotation alone) and says how the indexer's rotated slice
    pairs up (``indexer_rope_interleave``: only interleaved pairs, the
    latent tables' own, are computed)."""
    method = d.get("topk_method", "none")
    if method not in ("none", "greedy", "group_limited_greedy", "noaux_tc"):
        raise ValueError(f"topk_method {method!r} is not wired")
    if d.get("moe_layer_freq", 1) != 1:
        raise ValueError("moe_layer_freq != 1 is not wired")
    out = {"router_bias": method == "noaux_tc",
           **_expert_share(d, d.get("n_routed_experts", 0))}
    if d.get("hc_mult", 1) > 1:
        out["hc_res_clamp"] = (float(d.get("mhc_h_res_clamp_min", -30.0)),
                               float(d.get("mhc_h_res_clamp_max", 30.0)))
    name = f"model_type {d.get('model_type')!r}"
    if "rope_parameters" in d:  # where the file nests its rope_theta
        rope = _default_rope(name, d["rope_parameters"] or {},
                             d.get("rope_scaling"))
        if "rope_theta" in rope:
            out["rope_theta"] = float(rope["rope_theta"])
    if "index_topk" in d:
        if not d.get("indexer_rope_interleave", True):
            raise ValueError(
                f"{name}: indexer_rope_interleave false (an indexer that "
                "rotates half against half) is not wired: the sparse "
                "attention's indexer rotates interleaved pairs, with the "
                "latent attention's own tables")
        limit = d.get("max_position_embeddings")
        if d["index_topk"] < 1 or (limit and d["index_topk"] > limit):
            raise ValueError(
                f"{name}: index_topk {d['index_topk']} is no count of rows "
                "a sparse attention's indexer can choose (1 to "
                f"max_position_embeddings {limit})")
    return out


def _latent_write(c, d: dict):
    if d.pop("router_bias"):
        d["topk_method"] = "noaux_tc"
    if c.index_topk:  # the sparse family's spelling
        d["rope_parameters"] = {"rope_theta": d.pop("rope_theta"),
                                "rope_type": "default"}
        d["indexer_rope_interleave"] = True
    else:
        for f in _INDEXER_FIELDS:
            d.pop(f)
    if c.hc_mult == 1:  # the plain residual: none of its keys
        for f in _HC_FIELDS:
            d.pop(f)
    else:
        (d["mhc_h_res_clamp_min"],
         d["mhc_h_res_clamp_max"]) = d.pop("hc_res_clamp")


def _latent_check(c):
    if not (c.qk_rope_head_dim and c.v_head_dim):
        raise ValueError(
            "latent attention (kv_lora_rank > 0) needs "
            "qk_rope_head_dim and v_head_dim")
    if c.attn_gate not in (None, "head_wise"):
        raise ValueError(
            f"attn_gate {c.attn_gate!r} is not wired (a "
            "head-wise output gate only)")
    _check_told_share(c)


def check_indexer(c):
    """What a sparse attention's indexer may ask for, and who may carry
    one (``LlamaConfig.__post_init__``, every family): ``LATENT`` alone,
    whose cache then keeps an index key beside the latent row."""
    if not (c.index_topk or c.index_n_heads or c.index_head_dim):
        if c.model_type == "glm_moe_dsa":
            raise ValueError(
                "model_type 'glm_moe_dsa' is latent attention UNDER a "
                "learned sparse attention: index_topk 0 names no indexer")
        return
    if c.family is not LATENT:
        raise ValueError(
            f"index_topk {c.index_topk} / index_n_heads {c.index_n_heads} "
            f"/ index_head_dim {c.index_head_dim} (a learned sparse "
            "attention's indexer over the cache) is wired for the "
            f"latent-attention family alone ({sorted(LATENT.model_types)}),"
            f" not for the layers of model_type {c.model_type!r}")
    if not (c.index_topk > 0 and c.index_n_heads > 0
            and c.index_head_dim > 0 and c.q_lora_rank):
        raise ValueError(
            f"a sparse attention's indexer (index_topk {c.index_topk}) "
            f"needs index_n_heads ({c.index_n_heads}) heads of "
            f"index_head_dim ({c.index_head_dim}) over a query latent "
            f"(q_lora_rank {c.q_lora_rank})")
    if c.index_head_dim < c.qk_rope_head_dim:
        raise ValueError(
            f"index_head_dim {c.index_head_dim} is narrower than the "
            f"{c.qk_rope_head_dim} channels the indexer rotates "
            "(qk_rope_head_dim: the first channels of each index head, "
            "under the latent attention's tables)")
    if c.hc_mult > 1 or c.attn_gate:
        raise ValueError(
            "a sparse attention's indexer (index_topk > 0) is wired over "
            "plain latent attention alone, not beside a wide residual "
            "stream (hc_mult) or an output gate (attn_gate)")


def check_residual_path(c):
    """What a residual stream ``hc_mult`` hidden vectors wide may ask for
    (``LlamaConfig.__post_init__``, every family): the latent family's
    two sub-layers alone are wrapped in its mixes."""
    if c.hc_mult == 1:
        return
    lo, hi = c.hc_res_clamp
    if c.hc_mult < 1 or c.hc_sinkhorn_iters < 1 or not lo < hi:
        raise ValueError(
            f"hc_mult {c.hc_mult} / hc_sinkhorn_iters {c.hc_sinkhorn_iters}"
            f" / clamp ({lo}, {hi}) is no residual stream of one or more "
            "hidden vectors mixed by one or more Sinkhorn rounds")
    if c.family is not LATENT:
        raise ValueError(
            f"hc_mult = {c.hc_mult} (a residual stream several hidden "
            "vectors wide, mixed round every sub-layer) is wired for the "
            f"latent-attention family alone ({sorted(LATENT.model_types)}),"
            f" not beside the layers of model_type {c.model_type!r}")
    object.__setattr__(c, "hc_res_clamp", (float(lo), float(hi)))


_LATENT_FIELDS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim") + _EXPERT_FIELDS
_HC_FIELDS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp")
_INDEXER_FIELDS = ("index_n_heads", "index_head_dim", "index_topk")

LATENT = Family(
    model_types=("deepseek_v3", "axk1", "xing4_0", "glm_moe_dsa"),
    selects=lambda c: c.kv_lora_rank > 0,
    fields=_LATENT_FIELDS + _HC_FIELDS + _INDEXER_FIELDS,
    read=_latent_read, write=_latent_write, check=_latent_check,
    tensor_names={**_LATENT_MAP, **_LATENT_DENSE_MAP, **_LATENT_MOE_MAP,
                  **_HYBRID_EXTRA_MAP, **_LATENT_BIAS, **_HC_MAP,
                  **_INDEXER_MAP},
    expert_names=_LATENT_EXPERT_MAP,
    probe=".self_attn.kv_a_proj_with_mqa.weight",
    what="a latent-attention model", shard_axes=frozenset(("ep",)),
    shard_why="one cache row for all heads or a recurrent state",
    linear_tiers=("int8",),
    cache_tiers=(),
    cache_why=(
        "an int8 cache is not wired for latent attention (the "
        "latent row is already 1/35 of per-head keys and values)"),
    counts_held_experts=True)


# --- delta-rule linear attention beside latent attention (Ling-3.0's keys) --

_HYBRID_FIELDS = ("layer_group_size", "short_conv_kernel_size",
                  "kda_lower_bound", "attn_gate")
# what this family's config.json may ask for that nothing here computes:
# key -> the only value served
_HYBRID_FIXED = {
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "linear_silu": True, "use_qk_norm": True, "num_kv_heads_for_linear_attn": 0,
    "rope_interleave": True, "use_mla_nope": False, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "use_bias": False, "use_qkv_bias": False, "group_norm_size": 1,
    "rope_scaling": None,
}


def _hybrid_read(d: dict) -> dict:
    """`LlamaConfig` fields from a "bailing_hybrid" config.json (its own
    spelling of the expert keys)."""
    name = HYBRID.model_types[0]
    _only_served(name, d, _HYBRID_FIXED)
    layers = d["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(d.get(key, ())[:layers]):
            raise ValueError(
                f"{name}: a nonzero {key} entry inside the "
                f"{layers} served layers (a clamp on the experts' SwiGLU "
                "whose form the config does not give) is not wired")
    held = d["num_experts"]
    if d.get("moe_shared_expert_intermediate_size",
             d["moe_intermediate_size"]) != d["moe_intermediate_size"]:
        raise ValueError(
            f"{name}: a shared expert of another width than "
            "the routed ones is not wired")
    bias = bool(d.get("moe_router_enable_expert_bias"))
    if (d.get("topk_method", "noaux_tc") == "noaux_tc") != bias:
        raise ValueError(
            f"{name}: topk_method and "
            "moe_router_enable_expert_bias disagree about a routing "
            "correction bias")
    return {
        "n_routed_experts": held,
        "n_shared_experts": d.get("num_shared_experts", 0),
        "scoring_func": d.get("score_function",
                              d.get("scoring_func", "sigmoid")),
        "router_bias": bias,
        "attn_gate": d.get("gated_attention_proj_granularity_type"),
        "layer_group_size": d["layer_group_size"],
        **_expert_share(d, held),
    }


def _hybrid_write(c, d: dict):
    d["num_experts"] = d.pop("n_routed_experts")
    d["num_shared_experts"] = d.pop("n_shared_experts")
    d["score_function"] = d.pop("scoring_func")
    d["gated_attention_proj_granularity_type"] = d.pop("attn_gate")
    d["moe_router_enable_expert_bias"] = d.pop("router_bias")
    d["topk_method"] = "noaux_tc" if c.router_bias else "none"
    d["moe_shared_expert_intermediate_size"] = c.moe_intermediate_size


def _hybrid_check(c):
    if not c.kv_lora_rank:
        raise ValueError(
            "layer_group_size > 0 (delta-rule layers beside latent "
            "ones) needs the latent-attention keys (kv_lora_rank > 0)")
    _latent_check(c)


HYBRID = dataclasses.replace(
    LATENT, model_types=("bailing_hybrid",),
    selects=lambda c: c.layer_group_size > 0,
    fields=_LATENT_FIELDS + _HYBRID_FIELDS,
    read=_hybrid_read, write=_hybrid_write, check=_hybrid_check,
    tensor_names={**_LATENT_MAP, **_LATENT_DENSE_MAP, **_LATENT_MOE_MAP,
                  **_HYBRID_EXTRA_MAP, **_HYBRID_BIAS, **_KDA_MAP},
    recurrent_mixer="kda")


# --- state-space (Mamba-1) layers beside attention (AI21's Jamba keys) ------

_STATE_SPACE_FIELDS = ("attn_layer_period", "attn_layer_offset",
                       "mamba_d_state", "mamba_d_conv", "mamba_expand",
                       "mamba_dt_rank", "mamba_conv_bias")
# what this family's config.json may ask for that nothing here computes:
# key -> the only value served (experts in alternate layers, a window on
# the attention layers, a bias on the mixer's projections)
_STATE_SPACE_FIXED = {"num_experts": 1, "mamba_proj_bias": False}


def _state_space_read(d: dict) -> dict:
    """`LlamaConfig` fields from a "jamba" config.json."""
    from cake_tpu.models.config import LlamaConfig

    name = STATE_SPACE.model_types[0]
    _only_served(name, d, _STATE_SPACE_FIXED)
    if d.get("sliding_window") is not None:
        raise ValueError(
            f"{name}: sliding_window = "
            f"{d['sliding_window']!r} is not wired beside state-space "
            "layers (their attention layers are full; a window a layer, "
            f"by layer_types, is model_type {WINDOWED.model_types[0]!r}'s)")
    rank = d.get("mamba_dt_rank", "auto")
    return {
        # one expert is the dense SwiGLU: its choice of 1 selects nothing
        "num_experts_per_tok": LlamaConfig.num_experts_per_tok,
        "mamba_dt_rank": 0 if rank == "auto" else rank,
        # the family's defaults where the file leaves them out
        "attn_layer_period": d.get("attn_layer_period", 8),
        "attn_layer_offset": d.get("attn_layer_offset", 4),
    }


def _state_space_check(c):
    if c.kv_lora_rank or c.num_local_experts or (
            c.sliding_window is not None):
        raise ValueError(
            "attn_layer_period > 0 (state-space layers beside "
            "grouped-query attention) is wired with full "
            "grouped-query attention and a dense feed-forward "
            "only: no latent keys, no experts, no sliding_window")
    if not 0 <= c.attn_layer_offset < c.attn_layer_period:
        raise ValueError(
            f"attn_layer_offset {c.attn_layer_offset} outside "
            f"the period of {c.attn_layer_period}")
    if not c.mamba_dt_rank:
        object.__setattr__(c, "mamba_dt_rank", -(-c.hidden_size // 16))


_NO_INT8_MIXER = "its mixer's projections have no int8 form yet"
_REST_IS_SMALL = (
    "an int8 cache is not wired for a model whose layers hold "
    "a recurrent state or a convolution's tail (its few "
    "layers of rows are the smaller part of the cache)")

STATE_SPACE = Family(
    model_types=("jamba",),
    selects=lambda c: c.attn_layer_period > 0,
    fields=_STATE_SPACE_FIELDS, read=_state_space_read,
    # the family's other keys, at the only values served
    write=lambda c, d: d.update(_STATE_SPACE_FIXED),
    check=_state_space_check, recurrent_mixer="mamba",
    tensor_names={**_STATE_SPACE_MAP, **_MAMBA_MAP},
    final_norm="model.final_layernorm.weight",
    probe=".mamba.in_proj.weight",
    what="a state-space model", shard_axes=frozenset(),
    shard_why=("a recurrent state a channel: tp over d_inner is not "
               "wired"),
    linear_tiers=(), linear_why=_NO_INT8_MIXER,
    cache_tiers=(), cache_why=_REST_IS_SMALL)


# --- window and full attention mixed by layer (K-EXAONE's, Mellum's keys) ---

# the rope types a layer kind's rotation may ask for (ops/rope.py)
_LAYER_ROPE_TYPES = ("default", "yarn")


def _freeze_layer_rope(by_kind) -> tuple:
    """``{layer_types entry: rope parameters, or None for a kind that
    rotates nothing}`` as the hashable value ``LlamaConfig.layer_rope``
    holds: ``((kind, ((key, value), ..) or None), ..)``, both levels
    sorted (``LlamaConfig.rotation`` gives a kind's dict back)."""
    items = by_kind.items() if isinstance(by_kind, dict) else by_kind
    return tuple(sorted(
        (kind, None if rope is None else tuple(sorted(dict(rope).items())))
        for kind, rope in items))


def _kind_rope(name: str, kind: str, rope: dict) -> dict:
    """One layer kind's ``rope_parameters`` entry, as far as it is
    computed: the default rotation, or YaRN with the keys ops/rope.py
    reads (``attention_factor`` where the file gives one)."""
    rope_type = rope.get("rope_type", rope.get("type", "default"))
    if rope_type not in _LAYER_ROPE_TYPES:
        raise ValueError(
            f"{name}: rope type {rope_type!r} on {kind} layers is not wired "
            f"(one of {', '.join(_LAYER_ROPE_TYPES)})")
    if "rope_theta" not in rope:
        raise ValueError(f"{name}: rope_parameters of {kind} layers name no "
                         "rope_theta")
    return dict(rope, rope_type=rope_type)


def _windowed_entries(name: str, d: dict) -> tuple[list, list]:
    """``(layer_types, mlp_layer_types)`` of a file of this family, the
    per-layer windows held to them: dense layers lead, sparse ones
    follow."""
    layers, window = d["num_hidden_layers"], d.get("sliding_window")
    types = _entries(name, d)
    want = [window if t == "sliding_attention" else 0 for t in types]
    if [w or 0 for w in d.get("sliding_windows", want)] != want:
        raise ValueError(
            f"{name}: sliding_windows {d['sliding_windows']} disagrees "
            f"with layer_types and sliding_window {window} (a window of "
            "its own a layer is not wired)")
    dense = d.get("first_k_dense_replace")
    ffn = list(d.get("mlp_layer_types") or (
        ["dense"] * (dense or 0) + ["sparse"] * (layers - (dense or 0))))
    lead = ffn.count("dense")
    if (len(ffn) != layers or ffn != ["dense"] * lead + ["sparse"]
            * (layers - lead) or dense not in (None, lead)):
        raise ValueError(
            f"{name}: mlp_layer_types {ffn} with first_k_dense_replace "
            f"{dense} is not wired (dense layers lead, sparse ones follow)")
    return types, ffn


def _exaone_read(d: dict) -> dict:
    """`LlamaConfig` fields from an "exaone_moe" config.json (its own
    spelling: ``num_experts``, ``num_shared_experts``, ``layer_types``,
    ``mlp_layer_types``, ``sliding_windows``, ``rope_parameters``). ONE
    flat ``rope_parameters`` (the default rotation, no scaling), which is
    the WINDOW layers': a full layer of this model carries no position
    embedding (``layer_rope``: None for ``full_attention``).
    ``num_nextn_predict_layers`` (a next-token prediction block, ``mtp.*``
    tensors) is read and ignored: the block takes no part in the model's
    own logits and the loaders skip its tensors. Read into the file:
    pre-norm sublayers, a routing bias that enters the choice."""
    name = "exaone_moe"
    types, ffn = _windowed_entries(name, d)
    rope = _default_rope(name, d.get("rope_parameters") or {},
                         d.get("rope_scaling"), ", on the window layers only")
    _only_served(name, d, {"scoring_func": "sigmoid"})
    groups, kept = d.get("n_group", 1), d.get("topk_group", 1)
    if not 1 <= kept <= groups:
        raise ValueError(
            f"{name}: topk_group {kept} of n_group {groups} is not a "
            "group-limited choice")
    held = d["num_experts"] if "sparse" in ffn else 0
    theta = float(rope.get("rope_theta", d.get("rope_theta", 10000.0)))
    return {
        "layer_types": tuple(types),
        "layer_rope": _freeze_layer_rope({
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": theta},
            "full_attention": None}),
        "qk_norm": True,
        "first_k_dense_replace": ffn.count("dense"),
        "n_routed_experts": held,
        "n_shared_experts": d.get("num_shared_experts", 0),
        "scoring_func": "sigmoid",
        "router_bias": bool(held),
        "rope_theta": theta,
        **_expert_share(d, held),
    }


def _mellum_read(d: dict) -> dict:
    """`LlamaConfig` fields from a "mellum" config.json (Qwen3-MoE's key
    set: ``num_experts``, ``moe_intermediate_size``, ``norm_topk_prob``,
    ``max_window_layers``, ``use_sliding_window``, beside ``layer_types``,
    ``mlp_layer_types`` and a ``rope_parameters`` KEYED BY LAYER KIND: a
    rotation of its own for ``sliding_attention`` and for
    ``full_attention`` layers, ``layer_rope``). No shared expert, no
    routing bias, no ``first_k_dense_replace``, no group limit, no scaling
    factor. Read into the file, by that key set's convention: a per-head
    RMSNorm on q and k (``qk_norm``), softmax scoring over all experts
    with the chosen shares renormalised (``norm_topk_prob`` true is the
    one form wired), pre-norm sublayers. ``max_window_layers`` selects
    nothing: ``layer_types`` names each layer's kind."""
    name = "mellum"
    _only_served(name, d, {
        "use_sliding_window": True, "attention_bias": False,
        "num_shared_experts": 0, "scoring_func": "softmax", "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": 1.0, "rope_scaling": None})
    types, ffn = _windowed_entries(name, d)
    by_kind = d.get("rope_parameters") or {}
    if set(by_kind) != set(types):
        raise ValueError(
            f"{name}: rope_parameters is keyed by layer kind and names "
            f"{sorted(by_kind)} where layer_types has {sorted(set(types))}")
    held = d["num_experts"] if "sparse" in ffn else 0
    return {
        "layer_types": tuple(types),
        "layer_rope": _freeze_layer_rope(
            {kind: _kind_rope(name, kind, rope)
             for kind, rope in by_kind.items()}),
        "qk_norm": True,
        "first_k_dense_replace": ffn.count("dense"),
        "n_routed_experts": held,
        "n_shared_experts": 0,
        "scoring_func": "softmax",
        "router_bias": False,
        **_expert_share(d, held),
    }


_WINDOWED_READS = {"exaone_moe": _exaone_read, "mellum": _mellum_read}


def _windowed_read(d: dict) -> dict:
    return _WINDOWED_READS[d["model_type"]](d)


def _windowed_write(c, d: dict):
    d.pop("router_bias")
    d["layer_types"] = list(c.layer_types)
    d["mlp_layer_types"] = [
        "sparse" if ffn == "moe" else "dense" for _, ffn in c.layer_kinds]
    d["num_experts"] = d.pop("n_routed_experts")
    by_kind = {kind: c.rotation(kind) for kind in dict(d.pop("layer_rope"))}
    if c.model_type == "mellum":  # Qwen3-MoE's key set
        d["rope_parameters"] = by_kind
        d["use_sliding_window"] = True
        d["max_window_layers"] = 0
        for f in ("rope_theta", "n_shared_experts", "first_k_dense_replace",
                  "scoring_func", "n_group", "topk_group",
                  "routed_scaling_factor"):
            d.pop(f)
        return
    d["sliding_windows"] = [
        c.sliding_window if t == "sliding_attention" else 0
        for t in c.layer_types]
    d["num_shared_experts"] = d.pop("n_shared_experts")
    d.pop("rope_theta")
    d["rope_parameters"] = by_kind["sliding_attention"]


def _windowed_check(c):
    kinds = _check_layer_types(c, WINDOWED.layer_mixers)
    if "sliding_attention" in kinds and not (
            c.sliding_window and c.sliding_window >= 8):
        raise ValueError(
            "layer_types names sliding_attention layers: they need a "
            f"sliding_window of 8 or more, got {c.sliding_window!r}")
    if c.kv_lora_rank or c.attn_layer_period or (
            c.num_local_experts or c.attention_bias):
        raise ValueError(
            "layer_types (window and full grouped-query attention "
            "mixed by layer) is wired with the shared-expert "
            "feed-forward only: no latent keys, no state-space "
            "layers, no Mixtral-style experts, no projection bias")
    # the rotation a layer KIND reads from its file; a configuration that
    # names none rotates every kind by its flat rope_theta / rope_scaling
    flat = {"rope_type": "default", "rope_theta": c.rope_theta,
            **(c.rope_scaling or {})}
    by_kind = dict(c.layer_rope or {k: flat for k in kinds})
    if set(by_kind) != kinds:
        raise ValueError(
            f"layer_rope names {sorted(by_kind)} where layer_types has "
            f"{sorted(kinds)}: a rotation, or None, for each layer kind")
    object.__setattr__(c, "layer_rope", _freeze_layer_rope(
        {kind: None if rope is None else _kind_rope(c.model_type, kind,
                                                    dict(rope))
         for kind, rope in by_kind.items()}))
    # the flat field says what the kinds' own parameters say (so that a
    # file read back is the configuration that wrote it)
    object.__setattr__(c, "rope_theta", next(
        (float(dict(rope)["rope_theta"]) for _, rope in c.layer_rope if rope),
        c.rope_theta))
    _check_told_share(c, ("sigmoid", "softmax"))


WINDOWED = Family(
    model_types=("exaone_moe", "mellum"),
    selects=lambda c: c.layer_types is not None,
    fields=("layer_types", "layer_rope") + _EXPERT_FIELDS,
    read=_windowed_read, write=_windowed_write, check=_windowed_check,
    layer_mixers={"sliding_attention": "swa", "full_attention": "gqa"},
    tensor_names=_WINDOWED_MAP, expert_names=_LATENT_EXPERT_MAP,
    probe=".self_attn.q_norm.weight",
    what="a model of window and full attention layers",
    shard_axes=frozenset(("ep",)),
    shard_why=("a ring of rows beside the full layers' cache: window "
               "layers under tp or stages are not wired"),
    linear_tiers=(),
    linear_why=("its q, k and v projections are not among the "
                "shared-expert family's int8 linears"),
    cache_tiers=(),
    cache_why=(
        "an int8 cache is not wired for a model whose window "
        "layers hold a ring (the ring is already a fraction of the "
        "rows; its few full layers are the rest)"),
    counts_held_experts=True, expert_periods=False)


# --- gated short convolutions beside attention (LFM2-MoE's keys) ------------

def _short_conv_read(d: dict) -> dict:
    """`LlamaConfig` fields from an "lfm2_moe" config.json (its own
    spelling: ``num_dense_layers``, ``num_experts``, ``norm_eps``,
    ``use_expert_bias``, ``conv_L_cache``). Read into the file: the chunk
    order ``B | C | x``, no activation in the mixer, a tied head where the
    file names none."""
    name = SHORT_CONV.model_types[0]
    layers = d["num_hidden_layers"]
    types = _entries(name, d)
    rope = _default_rope(
        name, d.get("rope_parameters") or d.get("rope_scaling") or {})
    _only_served(name, d, {"num_shared_experts": 0, "n_group": 1,
                           "topk_group": 1, "scoring_func": "sigmoid"})
    lead = d.get("num_dense_layers", 0)
    return {
        "layer_types": tuple(types),
        "qk_norm": True,
        "rms_norm_eps": d.get("norm_eps", d.get("rms_norm_eps", 1e-5)),
        "rope_theta": float(rope.get("rope_theta",
                                     d.get("rope_theta", 1000000.0))),
        "rope_scaling": None,
        "first_k_dense_replace": lead,
        "n_routed_experts": d["num_experts"] if lead < layers else 0,
        "n_shared_experts": 0,
        "scoring_func": "sigmoid",
        "router_bias": bool(d.get("use_expert_bias", False)),
        # Lfm2MoeConfig's default where the file names none
        "tie_word_embeddings": bool(d.get("tie_word_embeddings", True)),
    }


def _short_conv_write(c, d: dict):
    d["layer_types"] = list(c.layer_types)
    d["num_dense_layers"] = d.pop("first_k_dense_replace")
    d["num_experts"] = d.pop("n_routed_experts")
    d["norm_eps"] = d.pop("rms_norm_eps")
    d["use_expert_bias"] = d.pop("router_bias")
    for f in ("n_shared_experts", "scoring_func", "n_group", "topk_group"):
        d.pop(f)


def _short_conv_check(c):
    kinds = _check_layer_types(c, SHORT_CONV.layer_mixers)
    if "conv" not in kinds:
        raise ValueError(
            "layer_types without a conv layer is not wired for "
            f"model_type {SHORT_CONV.model_types[0]!r} (every layer a "
            "full_attention one is a dense decoder's stack)")
    if c.conv_L_cache < 2 or c.conv_bias:
        raise ValueError(
            f"conv_L_cache {c.conv_L_cache} / conv_bias "
            f"{c.conv_bias} is not wired (a depthwise convolution "
            "of 2 or more taps, no bias)")
    if c.kv_lora_rank or c.attn_layer_period or (
            c.num_local_experts or c.attention_bias
            or c.sliding_window is not None
            or c.n_shared_experts):
        raise ValueError(
            "layer_types with conv layers (gated short convolutions "
            "beside full grouped-query attention) is wired with the "
            "routed-expert feed-forward only: no latent keys, no "
            "state-space layers, no Mixtral-style experts, no "
            "projection bias, no sliding_window, no shared expert")
    _check_told_share(c)


SHORT_CONV = Family(
    model_types=("lfm2_moe",),
    selects=lambda c: (c.layer_types is not None
                       and c.model_type in SHORT_CONV.model_types),
    fields=("layer_types", "conv_L_cache", "conv_bias") + _EXPERT_FIELDS,
    read=_short_conv_read, write=_short_conv_write, check=_short_conv_check,
    layer_mixers={"conv": "conv", "full_attention": "gqa"},
    recurrent_mixer="conv",
    tensor_names=_SHORT_CONV_MAP, expert_names=_SHORT_CONV_EXPERT_MAP,
    final_norm="model.embedding_norm.weight", probe=".conv.in_proj.weight",
    what="a model of short-convolution and attention layers",
    shard_axes=frozenset(),
    shard_why=("a convolution's tail beside the attention layers' rows: "
               "its tail under stages, tp or sp is not wired, and every "
               "expert is held: no share is cut over ep"),
    linear_tiers=(), linear_why=_NO_INT8_MIXER,
    cache_tiers=(), cache_why=_REST_IS_SMALL,
    counts_held_experts=True, expert_periods=False, topk_norm_eps=1e-6)


# --- scalar-gated delta-rule layers beside gated attention (Qwen3-Next) -----

_GATED_DELTA_FIELDS = (
    "layer_types", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    "rope_fraction", "attn_gate", "shared_expert_gate")
# what this family's config.json may ask for that nothing here computes:
# key -> the only value served
_GATED_DELTA_FIXED = {
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_scaling": None,
    "attention_bias": False, "use_sliding_window": False,
    "norm_topk_prob": True,
}


def _interval_types(layers: int, interval: int) -> list:
    """Hugging Face's rule where the file gives no ``layer_types``: layer
    ``i`` attends fully where ``(i + 1) % full_attention_interval == 0``."""
    return ["full_attention" if (i + 1) % interval == 0
            else "linear_attention" for i in range(layers)]


def _gated_delta_read(d: dict) -> dict:
    """`LlamaConfig` fields from a "qwen3_next" config.json (its own
    spelling: ``num_experts``, ``shared_expert_intermediate_size``,
    ``full_attention_interval``, ``partial_rotary_factor``, the
    ``linear_*`` keys). Read into the file, by its modules' convention:
    every layer sparse (``decoder_sparse_step`` 1, no ``mlp_only_layers``:
    ``intermediate_size`` names no tensor), q and k heads normed, the gate
    a channel in ``q_proj``, ONE shared expert weighted by a sigmoid gate,
    softmax scoring over all experts with the chosen shares renormalised.
    The multi-token-prediction block (``mtp.*``) is never asked for."""
    name = GATED_DELTA.model_types[0]
    _only_served(name, d, _GATED_DELTA_FIXED)
    layers = d["num_hidden_layers"]
    types = _interval_types(layers, d.get("full_attention_interval", 4))
    if d.get("layer_types") and _entries(name, d) != types:
        if "full_attention_interval" in d:
            raise ValueError(
                f"{name}: layer_types disagrees with full_attention_interval "
                f"{d['full_attention_interval']}")
        types = _entries(name, d)
    width = d["moe_intermediate_size"]
    if d.get("shared_expert_intermediate_size", width) != width:
        raise ValueError(
            f"{name}: a shared expert of another width "
            f"({d['shared_expert_intermediate_size']}) than the routed ones "
            f"({width}) is not wired")
    held = d["num_experts"]
    return {
        "layer_types": tuple(types),
        "qk_norm": True,
        "attn_gate": "elementwise",
        "rope_fraction": float(d.get("partial_rotary_factor", 1.0)),
        "first_k_dense_replace": 0,
        "n_routed_experts": held,
        "n_shared_experts": 1,
        "shared_expert_gate": True,
        "scoring_func": "softmax",
        "router_bias": False,
        **_expert_share(d, held),
    }


def _gated_delta_write(c, d: dict):
    d.pop("router_bias")
    d.pop("attn_gate")
    d.pop("shared_expert_gate")
    d.pop("layer_types")
    interval = c.layer_types.index("full_attention") + 1
    if list(c.layer_types) == _interval_types(c.num_hidden_layers, interval):
        d["full_attention_interval"] = interval
    else:
        d["layer_types"] = list(c.layer_types)
    d["partial_rotary_factor"] = d.pop("rope_fraction")
    d["num_experts"] = d.pop("n_routed_experts")
    d["shared_expert_intermediate_size"] = c.moe_intermediate_size
    d.update(_GATED_DELTA_FIXED)
    for f in ("n_shared_experts", "first_k_dense_replace", "scoring_func",
              "n_group", "topk_group", "routed_scaling_factor"):
        d.pop(f)


def _gated_delta_check(c):
    kinds = _check_layer_types(c, GATED_DELTA.layer_mixers)
    if "linear_attention" not in kinds:
        raise ValueError(
            "layer_types without a linear_attention layer is not wired for "
            f"model_type {GATED_DELTA.model_types[0]!r}")
    hk, hv, dk, dv, taps = c.delta_rule
    if not (hk and hv and dk and dv) or hv % hk or taps < 2:
        raise ValueError(
            f"linear_num_key_heads {hk} / linear_num_value_heads {hv} / "
            f"linear_key_head_dim {dk} / linear_value_head_dim {dv} / "
            f"linear_conv_kernel_dim {taps} is no delta rule of whole "
            "groups of value heads a key head under a convolution of 2 or "
            "more taps")
    rotated = c.head_dim * c.rope_fraction
    if not 0 < c.rope_fraction <= 1 or rotated != int(rotated) or (
            int(rotated) % 2):
        raise ValueError(
            f"partial_rotary_factor {c.rope_fraction} of head_dim "
            f"{c.head_dim} is no even number of leading channels to rotate")
    if (c.attn_gate != "elementwise" or not c.qk_norm
            or not c.shared_expert_gate or c.n_shared_experts != 1
            or c.first_k_dense_replace or c.router_bias):
        raise ValueError(
            "linear_attention layers are wired beside gated, QK-normed "
            "attention (attn_gate 'elementwise', qk_norm) and ONE shared "
            "expert under a sigmoid gate (n_shared_experts 1, "
            "shared_expert_gate), every layer sparse, no routing bias")
    if c.kv_lora_rank or c.attn_layer_period or (
            c.num_local_experts or c.attention_bias
            or c.sliding_window is not None or c.rope_scaling):
        raise ValueError(
            "layer_types with linear_attention layers (a scalar-gated "
            "delta rule beside full grouped-query attention) is wired with "
            "the shared-expert feed-forward only: no latent keys, no "
            "state-space layers, no Mixtral-style experts, no projection "
            "bias, no sliding_window, no rope_scaling")
    _check_told_share(c, ("softmax",))


def check_gated_keys(c):
    """What only ``GATED_DELTA`` computes (``LlamaConfig.__post_init__``,
    every family): a rotation over part of a grouped-query head, a gate a
    channel between attention and ``wo``, a weighted shared expert."""
    if c.family is GATED_DELTA:
        return
    asked = [f"{key} = {getattr(c, key)!r}" for key, plain in (
        ("rope_fraction", 1.0), ("shared_expert_gate", False))
        if getattr(c, key) != plain]
    if c.attn_gate == "elementwise":
        asked.append("attn_gate = 'elementwise'")
    if asked:
        raise ValueError(
            f"{', '.join(asked)} is wired for model_type "
            f"{GATED_DELTA.model_types[0]!r} alone, not beside the layers "
            f"of model_type {c.model_type!r}")


GATED_DELTA = Family(
    model_types=("qwen3_next",),
    selects=lambda c: (c.layer_types is not None
                       and c.model_type in GATED_DELTA.model_types),
    fields=_GATED_DELTA_FIELDS + _EXPERT_FIELDS,
    read=_gated_delta_read, write=_gated_delta_write,
    check=_gated_delta_check,
    layer_mixers={"linear_attention": "gdn", "full_attention": "gqa"},
    recurrent_mixer="gdn",
    tensor_names=_GATED_DELTA_MAP, expert_names=_LATENT_EXPERT_MAP,
    final_norm_fold=_ONE_PLUS,
    probe=".linear_attn.in_proj_qkvz.weight",
    what="a model of gated delta-rule and gated attention layers",
    shard_axes=frozenset(("ep",)),
    shard_why=("a recurrent state a value head beside the attention "
               "layers' rows: heads of a delta-rule mixer under tp, its "
               "state under stages or sp are not wired"),
    linear_tiers=(), linear_why=(
        "its fused projections (q, k, v and the gate in one, b and a in "
        "one) have no int8 form yet"),
    cache_tiers=(), cache_why=_REST_IS_SMALL,
    counts_held_experts=True, expert_periods=False)


# --- a shortcut-connected double layer, zero-compute experts (LongCat-Flash) --

# `model_type` "longcat_flash" (that the omni release's own `model_type`
# names the same decoder, and the names below, are ASSUMED: the benchmark
# configuration lists them): a layer's two attentions under
# `self_attn.{0,1}.` with DeepSeek-V3's names inside, its two dense
# feed-forwards `mlps.{0,1}.`, its four norms `input_layernorm.{0,1}` and
# `post_attention_layernorm.{0,1}`, the router `mlp.router.classifier`
# with its bias, the experts `mlp.experts.{e}.*` by global id. Ours: a
# sub-layer's tensors under the latent family's names behind `s0_` / `s1_`
# (`models/llama.py` ``sub_layer`` strips it), the expert block's under the
# shared-expert family's.


def _lora_scale(flag: str, rank: str) -> Fold:
    """``(hidden / rank)^0.5`` on a latent's norm weight where the file's
    ``flag`` is true (``mla_scale_q_lora`` / ``mla_scale_kv_lora``: a
    constant on the norm's output ahead of a linear), nothing where it is
    false: the program's latent attention multiplies by no factor of its
    own, and the cached row is the scaled latent."""
    def factor(c):
        return ((c.hidden_size / getattr(c, rank)) ** 0.5
                if getattr(c, flag) else 1.0)

    return Fold(lambda c, w: w * factor(c), lambda c, w: w / factor(c))


_LORA_SCALES = {"q_norm": _lora_scale("mla_scale_q_lora", "q_lora_rank"),
                "kv_norm": _lora_scale("mla_scale_kv_lora", "kv_lora_rank")}


def _sub_layer_names(j: int) -> dict:
    names = {}
    for ours, (suffix, transpose) in _LATENT_MAP.items():
        head, _, rest = suffix.partition(".")  # a module, then its tensor
        stored = (f"{head}.{j}.{rest}" if head == "self_attn"
                  else f"{head}.{j}.weight")
        names[f"s{j}_{ours}"] = (stored, transpose) + (
            (_LORA_SCALES[ours],) if ours in _LORA_SCALES else ())
    for ours, (suffix, transpose) in _LATENT_DENSE_MAP.items():
        names[f"s{j}_{ours}"] = (
            suffix.replace("mlp.", f"mlps.{j}.", 1), transpose)
    return names


_SHORTCUT_MAP = {
    **_sub_layer_names(0), **_sub_layer_names(1),
    "router": ("mlp.router.classifier.weight", True),
    "b_router": ("mlp.router.e_score_correction_bias", False),
}
_SHORTCUT_FIELDS = ("zero_expert_num", "zero_expert_type",
                    "mla_scale_q_lora", "mla_scale_kv_lora")


def _shortcut_read(d: dict) -> dict:
    """`LlamaConfig` fields from a "longcat_flash" config.json (its own
    spelling: ``num_layers``, ``ffn_hidden_size``,
    ``expert_ffn_hidden_size``, ``moe_topk``, ``zero_expert_num``,
    ``zero_expert_type``, ``mla_scale_q_lora``, ``mla_scale_kv_lora``,
    ``attention_method``). Read into the file, by the family's published
    modelling code: softmax over ALL the router's outputs (the experts and
    the zero-compute ones), the choice on ``share +
    e_score_correction_bias``, the chosen shares NOT renormalised (the file
    has no ``norm_topk_prob``; the code's default is false) times
    ``routed_scaling_factor``; no shared expert, no leading dense layer, no
    groups; interleaved rope pairs, no scaling."""
    name = SHORTCUT.model_types[0]
    _only_served(name, d, {"attention_method": "MLA", "rope_scaling": None,
                           "attention_bias": False, "norm_topk_prob": False})
    held = d.get("n_routed_experts", 0)
    return {
        "num_hidden_layers": d["num_layers"],
        "intermediate_size": d["ffn_hidden_size"],
        "moe_intermediate_size": d["expert_ffn_hidden_size"],
        "num_experts_per_tok": d["moe_topk"],
        "num_key_value_heads": d["num_attention_heads"],
        "first_k_dense_replace": 0,
        "n_shared_experts": 0,
        "scoring_func": "softmax",
        "norm_topk_prob": False,
        "router_bias": True,
        "n_group": 1,
        "topk_group": 1,
        **_expert_share(d, held),
    }


def _shortcut_write(c, d: dict):
    d["num_layers"] = d.pop("num_hidden_layers")
    d["ffn_hidden_size"] = d.pop("intermediate_size")
    d["expert_ffn_hidden_size"] = d.pop("moe_intermediate_size")
    d["moe_topk"] = d.pop("num_experts_per_tok")
    d["attention_method"] = "MLA"
    for f in ("router_bias", "scoring_func", "norm_topk_prob", "n_group",
              "topk_group", "first_k_dense_replace", "n_shared_experts",
              "num_key_value_heads", "head_dim"):
        d.pop(f)


def _shortcut_check(c):
    if c.zero_expert_type != "identity":
        raise ValueError(
            f"zero_expert_type {c.zero_expert_type!r} is not wired: a "
            "zero-compute expert returns its input (only 'identity')")
    if not (c.kv_lora_rank and c.q_lora_rank and c.qk_rope_head_dim
            and c.v_head_dim):
        raise ValueError(
            "model_type 'longcat_flash' is two latent attentions a layer: "
            "it needs q_lora_rank, kv_lora_rank, qk_rope_head_dim and "
            "v_head_dim")
    if (c.zero_expert_num < 0 or not c.n_routed_experts or c.attn_gate
            or c.first_k_dense_replace or c.n_shared_experts
            or not c.router_bias or c.rope_scaling or c.layer_group_size
            or c.num_local_experts or c.attention_bias):
        raise ValueError(
            "a shortcut-connected double layer (model_type 'longcat_flash')"
            " is wired with routed experts and zero-compute ones chosen on "
            "share + bias in every layer and plain latent attention: no "
            "leading dense layer, no shared expert, no output gate, no rope "
            "scaling, no projection bias, no delta-rule layers")
    _check_told_share(c, ("softmax",), unnormalised=True)


def check_zero_experts(c):
    """What only ``SHORTCUT`` computes (``LlamaConfig.__post_init__``,
    every family): router outputs that are no experts, and the latent
    attention's two ``mla_scale_*`` factors."""
    if c.family is SHORTCUT:
        return
    asked = [f"{key} = {getattr(c, key)!r}" for key in (
        "zero_expert_num", "mla_scale_q_lora", "mla_scale_kv_lora")
        if getattr(c, key)]
    if asked:
        raise ValueError(
            f"{', '.join(asked)} is wired for model_type "
            f"{SHORTCUT.model_types[0]!r} alone, not beside the layers of "
            f"model_type {c.model_type!r}")


_TWO_PLANES = (
    "two cache planes a layer (the page pool, the snapshot and the sharded "
    "programs count a plane a layer)")

SHORTCUT = Family(
    model_types=("longcat_flash",),
    selects=lambda c: c.model_type in SHORTCUT.model_types,
    fields=_LATENT_FIELDS + _SHORTCUT_FIELDS,
    read=_shortcut_read, write=_shortcut_write, check=_shortcut_check,
    tensor_names=_SHORTCUT_MAP, expert_names=_LATENT_EXPERT_MAP,
    probe=".mlp.router.classifier.weight",
    what="a shortcut-connected double-layer model",
    shard_axes=frozenset(("ep",)),
    shard_why=("one cache row for all heads, " + _TWO_PLANES + ": a double "
               "layer under stages, tp or sp is not wired"),
    linear_tiers=(),
    linear_why=("no comparison with the reference has been made of an "
                "int8 linear behind a folded mla_scale factor"),
    cache_tiers=(),
    cache_why=(
        "an int8 cache is not wired for latent attention (the "
        "latent row is already 1/35 of per-head keys and values)"),
    counts_held_experts=True, planes_a_layer=2)


# --- one set of layers run several times a token (Ouro's keys) --------------

def _looped_read(d: dict) -> dict:
    """`LlamaConfig` fields from an "ouro" config.json. Refused, not
    guessed: a layer type other than ``full_attention``, a window, a rope
    scaling, fewer than two passes, and (``_looped_check``, as for a
    preset) an ``early_exit_threshold`` under 1: a token that leaves the
    loop early is a step that does unequal work a row; at 1 the exit gate
    changes no logit. ``max_window_layers`` selects nothing while
    ``use_sliding_window`` is false."""
    name = LOOPED.model_types[0]
    kinds = set(_entries(name, d)) if d.get("layer_types") else set()
    if kinds - {"full_attention"}:
        raise ValueError(
            f"{name}: layer_types entries {sorted(kinds - {'full_attention'})} "
            "are not wired (every layer of the loop attends fully)")
    _only_served(name, d, {"use_sliding_window": False,
                           "sliding_window": None, "rope_scaling": None})
    passes = d.get("total_ut_steps", 4)
    if passes < 2:  # else the bare stack would take it, less its norms
        raise ValueError(
            f"{name}: total_ut_steps = {passes!r}: this family runs its "
            "layers 2 or more times a token")
    return {"total_ut_steps": passes,
            "early_exit_threshold": float(d.get("early_exit_threshold", 1))}


def _looped_write(c, d: dict):
    d["layer_types"] = ["full_attention"] * c.num_hidden_layers
    d["use_sliding_window"] = False
    d["sliding_window"] = d["rope_scaling"] = None
    d["max_window_layers"] = c.num_hidden_layers
    d["hidden_act"] = "silu"


def _looped_check(c):
    if c.early_exit_threshold < 1:
        raise ValueError(
            f"early_exit_threshold = {c.early_exit_threshold} is not wired "
            f"(only 1: every token takes all {c.total_ut_steps} passes; a "
            "token that leaves the loop early needs a scheduler and a "
            "block decode that follow unequal work a row)")
    if (c.kv_lora_rank or c.attn_layer_period or c.layer_types is not None
            or c.num_local_experts or c.n_routed_experts
            or c.attention_bias or c.sliding_window is not None
            or c.rope_scaling or c.tie_word_embeddings):
        raise ValueError(
            "total_ut_steps > 1 (one set of layers run several times a "
            "token) is wired with full grouped-query attention and a "
            "dense feed-forward only: no latent keys, no state-space "
            "layers, no layer_types, no experts, no projection bias, no "
            "sliding_window, no rope_scaling, no tied head")


_A_PLANE_A_PASS = (
    "a cache plane a layer AND a pass (the page pool, the snapshot and "
    "the sharded programs count a plane a layer)")

LOOPED = Family(
    model_types=("ouro",),
    selects=lambda c: c.total_ut_steps > 1,
    fields=("total_ut_steps", "early_exit_threshold"),
    read=_looped_read, write=_looped_write, check=_looped_check,
    tensor_names=_LOOPED_MAP, extra_tensors={"exit_gate": _EXIT_GATE},
    probe=".input_layernorm_2.weight",
    what="a looped model", shard_axes=frozenset(),
    shard_why=(_A_PLANE_A_PASS + ": a pass loop under stages (a pass "
               "would go round the stage ring), tp or sp is not wired"),
    linear_tiers=(),
    linear_why=("an int8 linear's rounding is met once a pass and no "
                "comparison with the reference has been made"),
    cache_tiers=(),
    cache_why=("an int8 cache is not wired for a looped model ("
               + _A_PLANE_A_PASS + "; no comparison with the reference "
               "has been made)"),
    loops=True)


# --- EVA attention: an exact window and the summaries of those before it
# (EvaByte's keys) -------------------------------------------------------------

# a head's learned vector stored ``[1, heads, 1, 1, head_dim]``, held flat
_PER_HEAD = Fold(
    lambda c, w: w.reshape(-1),
    lambda c, w: w.reshape(1, c.num_attention_heads, 1, 1, c.head_dim))

# Llama's names, every norm stored as ``w - 1`` (``norm_add_unit_offset``),
# and the two learned vectors a head (the names are ASSUMED: the benchmark
# configuration lists them)
_EVA_MAP = {
    **{k: _LAYER_MAP[k] for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                                  "w_down")},
    "attn_norm": (*_LAYER_MAP["attn_norm"], _ONE_PLUS),
    "mlp_norm": (*_LAYER_MAP["mlp_norm"], _ONE_PLUS),
    "eva_phi": ("self_attn.adaptive_phi", False, _PER_HEAD),
    "eva_mu": ("self_attn.adaptive_mu_k", False, _PER_HEAD),
}

# keys of an "evabyte" config.json that only one value of is computed
_EVA_FIXED = {"attention_class": "eva", "num_chunks": None,
              "rope_scaling": None, "attention_bias": False,
              "norm_add_unit_offset": True, "tie_word_embeddings": False}


def _eva_read(d: dict) -> dict:
    """`LlamaConfig` fields from an "evabyte" config.json
    (``attention_class``, ``window_size``, ``chunk_size``,
    ``num_pred_heads`` are fields as they stand). Refused, not guessed:
    another ``attention_class``, a ``num_chunks`` (a fixed number of
    summaries in place of a chunk length), a rope scaling, a projection
    bias, norms stored plainly, a tied head, fewer key/value heads than
    query heads (``_eva_check``, as for a preset). Read and computed as
    the serving type computes everything else, a departure the benchmark
    configuration's ``assumed`` names: ``fp32_skip_add`` (the published
    residual additions are float32; the program's residual stream is the
    serving type's), ``fp32_ln`` and ``mixedp_attn`` (norm and softmax
    statistics are float32 here in any case), ``fp32_logits`` (the head's
    product is the serving type's with float32 accumulation, returned as
    float32)."""
    _only_served(EVA.model_types[0], d, _EVA_FIXED)
    return {}


def _eva_write(c, d: dict):
    d.update(num_chunks=None, rope_scaling=None, norm_add_unit_offset=True,
             fp32_logits=True, hidden_act="silu",
             max_position_embeddings=c.max_seq_len)


def _eva_check(c):
    w, chunk = c.window_size, c.chunk_size
    if chunk < 1 or w < chunk or w % chunk:
        raise ValueError(
            f"attention_class 'eva' needs a window_size that is a whole "
            f"number of chunks of chunk_size >= 1, got window_size {w} and "
            f"chunk_size {chunk}")
    if c.num_key_value_heads != c.num_attention_heads:
        raise ValueError(
            "attention_class 'eva' is wired for as many key/value heads as "
            f"query heads (a learned phi and mu a head), got "
            f"{c.num_key_value_heads} under {c.num_attention_heads}: "
            "grouped-query heads beside EVA are not wired")
    if c.num_pred_heads < 1:
        raise ValueError(
            f"num_pred_heads = {c.num_pred_heads}: the head holds one or "
            "more blocks of vocab_size rows")
    if (c.kv_lora_rank or c.attn_layer_period or c.layer_types is not None
            or c.num_local_experts or c.n_routed_experts or c.attention_bias
            or c.sliding_window is not None or c.rope_scaling
            or c.tie_word_embeddings or c.total_ut_steps > 1):
        raise ValueError(
            "attention_class 'eva' is wired with a dense feed-forward and "
            "the default rotation only: no latent keys, no state-space "
            "layers, no layer_types, no experts, no projection bias, no "
            "sliding_window (its window resets: window_size), no "
            "rope_scaling, no tied head, no loop of passes")
    check_capacity(c)


def check_attention_class(c):
    """EVA's keys on a configuration of another family, or a class of
    attention nothing here computes."""
    if c.attention_class not in (None, "eva"):
        raise ValueError(
            f"attention_class = {c.attention_class!r} is not wired (only "
            "'eva': an exact window and the summaries of those before it)")
    if c.attention_class == "eva" and c.family is not EVA:
        raise ValueError(
            "attention_class 'eva' beside the keys of "
            f"{c.family.what or 'another family'} is not wired")
    if c.attention_class is None and (
            c.window_size or c.chunk_size or c.num_pred_heads != 1):
        raise ValueError(
            "window_size, chunk_size and num_pred_heads are the keys of "
            "attention_class 'eva' (model_type "
            f"{EVA.model_types[0]!r}); this configuration names no "
            "attention_class")


_NO_ROW_A_POSITION = (
    "a ring that resets and a plane of one summary row for every "
    "chunk_size positions, and no row a position")

EVA = Family(
    model_types=("evabyte",),
    selects=lambda c: c.attention_class == "eva",
    fields=("attention_class", "window_size", "chunk_size",
            "num_pred_heads"),
    read=_eva_read, write=_eva_write, check=_eva_check,
    tensor_names=_EVA_MAP, final_norm_fold=_ONE_PLUS,
    probe=".self_attn.adaptive_phi",
    what="a model of EVA attention layers", shard_axes=frozenset(),
    shard_why=("its cache is " + _NO_ROW_A_POSITION + ": the summaries "
               "under tp, sp or stages are not wired"),
    linear_tiers=(),
    linear_why=("no comparison with the reference has been made over "
                "int8 linears ahead of a learned summary"),
    cache_tiers=(),
    cache_why=("an int8 cache is not wired for EVA attention (its cache "
               "is " + _NO_ROW_A_POSITION + "; an int8 window or summary "
               "has not been compared with the reference)"))


# The first record that selects a configuration is its family: the
# families that read `layer_types` before the ones a single key names, the
# bare stack last.
FAMILIES = (LOOPED, SHORT_CONV, GATED_DELTA, WINDOWED, STATE_SPACE, SHORTCUT,
            HYBRID, LATENT, EVA, GQA)
# every field some family's config.json alone carries
FIELDS = frozenset(f for family in FAMILIES for f in family.fields)
# the record that reads a config.json, by its `model_type` (the bare
# stack's reads every type no other family names)
BY_MODEL_TYPE = {t: f for f in FAMILIES for t in f.model_types}
