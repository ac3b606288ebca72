"""Architecture ``gdn_gqa_moe``: a decoder whose layers are a scalar-gated
delta rule (Gated DeltaNet: a float32 state ``[d_k, d_v]`` a VALUE head and
a stream, whatever the stream's length, ``linear_num_key_heads`` key heads
under ``linear_num_value_heads`` value heads, a decay a head) but every
``full_attention_interval``-th, which is gated grouped-query attention
(QK-normed heads of ``head_dim``, the first ``partial_rotary_factor`` of
whose channels rotate, a sigmoid gate a channel between attention and the
output projection); every layer's feed-forward routes over ``num_experts``
softmax-scored experts, the ``num_experts_per_tok`` largest shares
renormalised over their sum, beside ONE shared expert weighted by a
sigmoid gate of the token; every norm but the delta rule's output norm
scales by ``1 + w``: Qwen3-Next's ``config.json`` keys (``model_type``
``qwen3_next``). A configuration may hold a chip's share of an
expert-parallel deployment, as ``arch/mla_moe.py`` says: ``num_experts``
experts are HELD here, global experts ``rank * num_experts ..`` of the
``expert_share.n_routed_experts`` the router scores.

Numpy and the standard library only (the parent of a chip run never
imports JAX). The routing channels and the generator a tensor is drawn
from are ``arch/mla_moe.py``'s, loaded by path. The writer puts the
tensors under the names the program's loader reads; they are ASSUMED (the
configuration's ``assumed.tensor_names``): Hugging Face's Qwen3Next
modules (``linear_attn.in_proj_qkvz`` and ``in_proj_ba`` fused a key head's
group at a time, ``linear_attn.conv1d`` as torch depthwise ``[C, 1, K]``,
``A_log``, ``dt_bias``, ``norm``, ``out_proj``; ``self_attn.q_proj`` a
head's ``[q | gate]`` side by side, ``q_norm``, ``k_norm``;
``mlp.gate``, ``mlp.experts.{e}.*``, ``mlp.shared_expert.*``,
``mlp.shared_expert_gate``).

The reference is written from the equations ISSUE 57 states (Motivation):
the delta rule token by token from a zero state, all value heads at once;
attention over the whole sequence under an explicit mask, the scores a
block of ``QUERY_ROWS`` query rows and a key/value head at a time so that
5000 tokens fit the host; softmax over ALL experts and then the
renormalised top-k (the long form; the program takes softmax over the
chosen logits); a loop over the experts: see ``_delta_net``, ``_attention``
and ``_feed_forward``.

A random router must not hang on rounding (``weights.py`` says why): the
first ``num_experts`` (published) channels of the residual stream belong
to the router, the embedding marks ``num_experts_per_tok`` of them per
token id, no linear writes to them, the router's row ``e`` reads channel
``e`` alone. A marked channel's logit is ``ROUTE_MARK`` over the token's
root mean square times the norm's ``1 + w`` there (0.875-1.25), an unmarked
one's exactly 0.

Random heads must not average their keys (``HEAD_NORM_GAIN``, as
``arch/swa_yarn_gqa_moe.py``): the heads' q and k norms scale by 1.75-2.5,
so that attention is as peaked as a trained model's and ``correct`` can
tell a missing gate or a whole-head rotation from the model. And a random
state must REMEMBER (``A_LOG``, ``DT_BIAS``, the builder's own ranges and
no published initialisation's): a head's decay lies between 0.8 and 0.9998
a token. What a state holds is bounded by the delta rule's own overwriting
besides: a token takes ``beta (2 - beta) / d_k`` of the state's energy out
along its key, so under random 128-wide keys and ``beta`` around a half a
state's content fades over a few hundred tokens however slow its decay.
That is memory enough for ``correct`` to see the rule (the decay left out,
the state dropped at a chunk's boundary, or key heads mis-grouped read 2-4
nats; the configuration's ``margin_tol_why``), and not enough for it to see
the state's PRECISION: see there.
"""

from __future__ import annotations

import importlib.util
import math
import sys
from pathlib import Path

import numpy as np

from reference import Layer, rms_norm, score_pairs, silu, swiglu
from shapes import PLAIN_BYTES, linear_bytes
from weights import (Checkpoint, File, hf_config, linear, norm, plain, rngs,
                     small, write_files)


def _sibling(name: str):
    """``arch/<name>.py``, loaded by path as the harness loads this file."""
    key = f"bench_arch_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, Path(__file__).with_name(f"{name}.py"))
        sys.modules[key] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[key])
    return sys.modules[key]


_mla = _sibling("mla_moe")

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "attention_bias", "decoder_sparse_step",
    "full_attention_interval", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "linear_conv_kernel_dim", "linear_key_head_dim",
    "linear_num_key_heads", "linear_num_value_heads",
    "linear_value_head_dim", "max_position_embeddings", "mlp_only_layers",
    "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "partial_rotary_factor", "rms_norm_eps",
    "rope_scaling", "rope_theta", "shared_expert_intermediate_size",
    "tie_word_embeddings", "use_sliding_window", "vocab_size", "torch_dtype",
    "expert_share", "bos_token_id", "eos_token_id",
)

QUERY_ROWS = 512  # query rows a block of the reference's scores
MODEL_TYPE = "qwen3_next"
L2_EPS = 1e-6  # in the L2 norm of a head of q and k
# what the heads' q and k norm scales (0.875-1.25 in eighths, as every
# norm's) are multiplied by (arch/swa_yarn_gqa_moe.py says why, PR 55)
HEAD_NORM_GAIN = 2.0
# A_log in 0 .. 1 in eighths (rates exp(A_log) 1-2.7) and dt_bias in -8.5 ..
# -2.5 in eighths, a head each (exact in bfloat16). The builder's choice:
# the published initialisation draws rates 1-16 and steps 0.001-0.1, the
# program's own ``_gdn_init`` too. With a decay input of std ~1 a token
# decays a head's state by exp(-[1, 2.7] x softplus(dt_bias +- 1)): 0.8-0.95
# at -2.5, 0.9995-0.9998 at -8.5. The range was -3.5 .. -2.5 in my first
# checkpoint (chip calls 1-2, PR 57) and was widened after call 2, when a
# state rounded to bfloat16 chose the program's own 32 probe tokens, to see
# whether slower decays would show the rounding: they did not (the module's
# docstring says what bounds a state's memory; the file's margin_tol_why has
# both checkpoints' readings)
A_LOG = (0, 9)
DT_BIAS = (-5.5, 24)  # the middle, and how many eighths either side


def require_family(checkout: Path) -> None:
    """Refuse a checkout whose program does not name this family. A
    program from before it reads ``model_type`` "qwen3_next" as a bare
    stack of plain grouped-query layers and finds none of its tensors:
    whatever it would make of the checkpoint is not this model. Such a
    checkout cannot run this configuration, and a run on it fails here, at
    once, and measures nothing under the cell's name. Asked of the source
    (the parent of a chip run imports neither JAX nor ``cake_tpu``): a
    family is declared by its ``model_type`` under ``cake_tpu/models/``."""
    models = checkout / "cake_tpu" / "models"
    if not any(f'"{MODEL_TYPE}"' in path.read_text()
               for path in sorted(models.glob("*.py"))):
        raise RuntimeError(
            f"no module under {models} declares model_type {MODEL_TYPE!r}: "
            "this program has no scalar-gated delta-rule layer, no gate "
            "and no part rotation on grouped-query attention and no gated "
            "shared expert; the cell needs the program's family record "
            "(cake_tpu/models/families.py)")


require_family(Path(__file__).resolve().parents[2])


# -- sizes -----------------------------------------------------------------------

def _as_mla(cfg: dict) -> dict:
    """The configuration under the keys ``mla_moe``'s helpers read."""
    return dict(cfg, n_routed_experts=cfg["num_experts"], n_shared_experts=1)


def router_width(cfg: dict) -> int:
    return _mla.router_width(_as_mla(cfg))


def held_experts(cfg: dict) -> range:
    """Global ids of the experts held here."""
    return _mla.held_experts(_as_mla(cfg))


def is_full_layer(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def expert_layers(cfg: dict) -> int:
    """Layers that route: every one."""
    return cfg["num_hidden_layers"]


def delta_layers(cfg: dict) -> int:
    return sum(not is_full_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def _delta(cfg: dict) -> tuple[int, int, int, int, int]:
    """``(key heads, value heads, d_k, d_v, taps)`` of a delta-rule layer."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"])


def conv_width(cfg: dict) -> int:
    """Channels of the one convolution: all heads' q, then k, then v."""
    hk, hv, dk, dv, _ = _delta(cfg)
    return 2 * hk * dk + hv * dv


def cache_row_values(cfg: dict) -> int:
    """Values a full layer's cache holds for one token: keys and values
    of every KV head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def state_bytes_per_stream(cfg: dict, cache_dtype: str = "bf16") -> int:
    """Bytes of recurrent state a stream holds, whatever its length: a
    float32 ``[Hv, d_k, d_v]`` state and the last ``taps - 1`` inputs of
    the convolution, a delta-rule layer."""
    _, hv, dk, dv, taps = _delta(cfg)
    tail = (taps - 1) * conv_width(cfg)
    return delta_layers(cfg) * (hv * dk * dv * 4
                                + tail * PLAIN_BYTES[cache_dtype])


def _mlp(prefix: str, h: int, f: int) -> dict[str, tuple[int, int]]:
    return {f"{prefix}gate_proj.weight": (h, f),
            f"{prefix}up_proj.weight": (h, f),
            f"{prefix}down_proj.weight": (f, h)}


def layer_linears(cfg: dict, i: int) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of layer ``i``'s linears."""
    h = cfg["hidden_size"]
    if is_full_layer(cfg, i):
        nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        a = "self_attn."
        lin = {a + "q_proj.weight": (h, 2 * nh * d),  # a head's [q | gate]
               a + "k_proj.weight": (h, nkv * d),
               a + "v_proj.weight": (h, nkv * d),
               a + "o_proj.weight": (nh * d, h)}
    else:
        _, hv, _, dv, _ = _delta(cfg)
        a = "linear_attn."
        lin = {a + "in_proj_qkvz.weight": (h, conv_width(cfg) + hv * dv),
               a + "in_proj_ba.weight": (h, 2 * hv),
               a + "out_proj.weight": (hv * dv, h)}
    f = cfg["moe_intermediate_size"]
    lin.update(_mlp("mlp.shared_expert.", h, f))
    lin["mlp.shared_expert_gate.weight"] = (h, 1)
    for e in held_experts(cfg):
        lin.update(_mlp(f"mlp.experts.{e}.", h, f))
    return lin


def _plain_values(cfg: dict, i: int) -> int:
    """Unquantized values of layer ``i``: its two norms, the router, a
    full layer's q and k norms, a delta-rule layer's taps, rates, decay
    biases and output norm."""
    n = 2 * cfg["hidden_size"] + router_width(cfg) * cfg["hidden_size"]
    if is_full_layer(cfg, i):
        return n + 2 * cfg["head_dim"]
    _, hv, _, dv, taps = _delta(cfg)
    return n + taps * conv_width(cfg) + 2 * hv + dv


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits)."""
    per = 1 if layout == "q8" else 2
    unq = 4 if layout == "q8" else 2  # an unquantized tensor's bytes
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    total = v * h * unq + h * unq + v * h * per + (
        4 * v if layout == "q8" else 0)
    for i in range(cfg["num_hidden_layers"]):
        total += _plain_values(cfg, i) * unq + sum(
            a * b * per + (4 * b if layout == "q8" else 0)
            for a, b in layer_linears(cfg, i).values())
    return total


# -- the checkpoint --------------------------------------------------------------

def _offset(scale: np.ndarray) -> np.ndarray:
    """A norm's scale as this family stores it: ``w`` of ``1 + w`` (exact
    in bfloat16 for scales in eighths or quarters around one)."""
    return scale - np.float32(1.0)


def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    layers, width = cfg["num_hidden_layers"], router_width(cfg)
    _, hv, _, dv, taps = _delta(cfg)
    if width > h // 2:
        raise ValueError(f"{width} routing channels of {h}")

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        plain(f, layout, p + "input_layernorm.weight",
              _offset(norm(next(r), h)))
        plain(f, layout, p + "post_attention_layernorm.weight",
              _offset(norm(next(r), h)))
        if is_full_layer(cfg, i):
            for n in "qk":
                plain(f, layout, p + f"self_attn.{n}_norm.weight", _offset(
                    norm(next(r), d) * np.float32(HEAD_NORM_GAIN)))
        else:
            a = p + "linear_attn."
            # torch depthwise conv1d: [C, 1, K]
            plain(f, layout, a + "conv1d.weight",
                  small(next(r), (conv_width(cfg), 1, taps), 0.5))
            plain(f, layout, a + "A_log", next(r).integers(
                *A_LOG, size=hv).astype(np.float32) / 8)
            plain(f, layout, a + "dt_bias", np.float32(DT_BIAS[0]) + next(
                r).integers(-DT_BIAS[1], DT_BIAS[1] + 1, size=hv).astype(
                    np.float32) / 8)
            plain(f, layout, a + "norm.weight", norm(next(r), dv))  # PLAIN
        # row e reads routing channel e alone
        plain(f, layout, p + "mlp.gate.weight",
              np.eye(width, h, dtype=np.float32))
        for suffix, (fan_in, out) in layer_linears(cfg, i).items():
            # each tensor's generator is named by its place in the layer of
            # the UNCUT model, so that a share's experts are the same
            # tensors whichever share holds them
            writes_residual = suffix.endswith(
                ("o_proj.weight", "out_proj.weight", "down_proj.weight"))
            linear(f, _mla._tensor_rng(seed, i, suffix), layout, p + suffix,
                   fan_in, out, zero_rows=width if writes_residual else 0)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        embed = small(next(r), (v, h), 1.0 / math.sqrt(h))
        _mla.routing_embed(embed, _as_mla(cfg))
        plain(f, layout, "model.embed_tokens.weight", embed)
        plain(f, layout, "model.norm.weight", _offset(norm(next(r), h)))
        linear(f, next(r), layout, "lm_head.weight", h, v)
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def _sigmoid(x: np.ndarray) -> np.ndarray:
    return (1.0 / (1.0 + np.exp(-x))).astype(np.float32)


def _softplus(x: np.ndarray) -> np.ndarray:
    return np.logaddexp(np.float32(0.0), x).astype(np.float32)


def _norm(x: np.ndarray, w: np.ndarray, eps: float) -> np.ndarray:
    """The family's zero-centred RMS norm: ``(1 + w)``."""
    return rms_norm(x, np.float32(1.0) + w, eps)


def _delta_net(cfg: dict, ck: Layer, p: str, x: np.ndarray,
               state_dtype=None) -> np.ndarray:
    """Gated DeltaNet over one sequence, a token at a time from a zero
    state, float32. ``state_dtype``: round the state to it after every
    token (a control that must FAIL)."""
    t = x.shape[0]
    hk, hv, dk, dv, taps = _delta(cfg)
    r = hv // hk
    a = p + "linear_attn."
    # a key head's group at a time: [q | k | v (r heads) | z (r heads)]
    qkvz = (x @ ck.f32(a + "in_proj_qkvz.weight").T).reshape(
        t, hk, 2 * dk + 2 * r * dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, hv, dv)
    mixed = np.concatenate(
        [qkvz[..., :dk].reshape(t, -1), qkvz[..., dk:2 * dk].reshape(t, -1),
         qkvz[..., 2 * dk:2 * dk + r * dv].reshape(t, -1)], axis=-1)
    del qkvz
    ba = (x @ ck.f32(a + "in_proj_ba.weight").T).reshape(t, hk, 2 * r)
    beta = _sigmoid(ba[..., :r].reshape(t, hv))
    g = -np.exp(ck.f32(a + "A_log")) * _softplus(
        ba[..., r:].reshape(t, hv) + ck.f32(a + "dt_bias"))
    decay = np.exp(g).astype(np.float32)  # [t, Hv], a scalar a head
    # ONE causal depthwise convolution over [all q | all k | all v]
    w = ck.f32(a + "conv1d.weight")[:, 0, :]  # [C, K]
    padded = np.concatenate([np.zeros((taps - 1, mixed.shape[1]),
                                      np.float32), mixed])
    mixed = silu(sum(padded[j:j + t] * w[:, j] for j in range(taps)))
    del padded

    def l2(y: np.ndarray) -> np.ndarray:
        return y / np.sqrt((y * y).sum(-1, keepdims=True)
                           + np.float32(L2_EPS))

    q = l2(mixed[:, :hk * dk].reshape(t, hk, dk)) * np.float32(dk ** -0.5)
    k = l2(mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    v = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    q, k = np.repeat(q, r, axis=1), np.repeat(k, r, axis=1)  # h -> h // r
    s = np.zeros((hv, dk, dv), np.float32)
    o = np.empty((t, hv, dv), np.float32)
    for i in range(t):
        s *= decay[i][:, None, None]
        ks = np.einsum("hk,hkv->hv", k[i], s)
        s += (beta[i][:, None] * k[i])[:, :, None] * (v[i] - ks)[:, None, :]
        if state_dtype is not None:
            s = _round_to(s, state_dtype)
        o[i] = np.einsum("hk,hkv->hv", q[i], s)
    # the output norm's weight is PLAIN (not 1 + w)
    o = rms_norm(o, ck.f32(a + "norm.weight"), cfg["rms_norm_eps"])
    return (o * silu(z)).reshape(t, hv * dv) @ ck.f32(
        a + "out_proj.weight").T


def _round_to(x: np.ndarray, dtype: str) -> np.ndarray:
    """``x`` rounded to bfloat16 (round to nearest even) and back."""
    if dtype != "bfloat16":
        raise ValueError(dtype)
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.view(np.float32)


def rotate(x: np.ndarray, theta: float, width: int) -> np.ndarray:
    """``x [heads, t, d]``: the first ``width`` channels rotated, pairs
    ``(c, c + width / 2)`` of position ``t`` by ``t * theta^(-2c /
    width)``; the rest left as they are."""
    t = x.shape[1]
    inv = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos, sin = (np.cos(angle).astype(np.float32),
                np.sin(angle).astype(np.float32))
    x1, x2 = x[..., :width // 2], x[..., width // 2:width]
    return np.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., width:]], -1)


def _attention(cfg: dict, ck: Layer, p: str, x: np.ndarray, gate=True,
               rotate_all=False) -> np.ndarray:
    """Gated attention over one whole sequence: q and k normed a head, the
    first ``partial_rotary_factor`` of the head rotated, causal scores a
    block of query rows and a key/value head at a time, the heads' output
    times ``sigmoid(gate)``. ``gate`` / ``rotate_all``: controls that must
    FAIL."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, a = cfg["rms_norm_eps"], p + "self_attn."
    qg = (x @ ck.f32(a + "q_proj.weight").T).reshape(t, nh, 2 * d)
    gamma = qg[..., d:]  # a head's [q | gate]
    q = _norm(qg[..., :d], ck.f32(a + "q_norm.weight"), eps)
    k = _norm((x @ ck.f32(a + "k_proj.weight").T).reshape(t, nkv, d),
              ck.f32(a + "k_norm.weight"), eps)
    v = (x @ ck.f32(a + "v_proj.weight").T).reshape(t, nkv, d)
    width = d if rotate_all else int(d * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_theta"])
    q = rotate(np.ascontiguousarray(q.transpose(1, 0, 2)), theta, width)
    k = rotate(np.ascontiguousarray(k.transpose(1, 0, 2)), theta, width)
    v = np.ascontiguousarray(v.transpose(1, 0, 2))
    grp = nh // nkv
    out = np.empty((t, nh, d), np.float32)
    at = np.arange(t)
    for lo in range(0, t, QUERY_ROWS):
        rows = at[lo:lo + QUERY_ROWS]
        seen = rows[:, None] >= at[None, :lo + len(rows)]
        for kh in range(nkv):
            s = (q[kh * grp:(kh + 1) * grp, rows]
                 @ k[kh, :lo + len(rows)].T) * np.float32(d ** -0.5)
            s = np.where(seen[None], s, np.float32(-np.inf))
            s = s - s.max(-1, keepdims=True)
            w = np.exp(s)
            w /= w.sum(-1, keepdims=True)
            out[rows, kh * grp:(kh + 1) * grp] = (
                w @ v[kh, :lo + len(rows)]).transpose(1, 0, 2)
    if gate:
        out = out * _sigmoid(gamma)
    return out.reshape(t, nh * d) @ ck.f32(a + "o_proj.weight").T


def route(cfg: dict, logits: np.ndarray):
    """``logits [t, E]`` -> (chosen ``[t, k]``, weights ``[t, k]``, margin
    ``[t]``): softmax over ALL experts, the ``k`` largest shares (ties to
    the lower index), each over the chosen ones' sum. The margin is how
    far the last expert chosen lies above the first one left out, in
    units of the token's logits' spread."""
    k = cfg["num_experts_per_tok"]
    z = logits - logits.max(-1, keepdims=True)
    share = np.exp(z)
    share /= share.sum(-1, keepdims=True)
    ranked = np.argsort(-share, axis=-1, kind="stable")
    idx = ranked[:, :k]
    by_rank = np.take_along_axis(logits, ranked, -1)
    margin = (by_rank[:, k - 1] - by_rank[:, k]) / (logits.std(-1) + 1e-9)
    w = np.take_along_axis(share, idx, -1)
    return idx, w / w.sum(-1, keepdims=True), margin


def _feed_forward(cfg: dict, ck: Layer, p: str, m: np.ndarray,
                  margins: list, shared_gate=True) -> np.ndarray:
    """The sum over the chosen experts HELD here of ``w_e expert_e(m)``, a
    loop over them, plus ``sigmoid(m w_sg) * shared(m)``; ``margins``
    gains each token's routing margin. ``shared_gate``: a control that
    must FAIL."""
    def mlp(prefix: str, rows: np.ndarray) -> np.ndarray:
        return swiglu(rows, ck.f32(prefix + "gate_proj.weight"),
                      ck.f32(prefix + "up_proj.weight"),
                      ck.f32(prefix + "down_proj.weight"))

    idx, weight, margin = route(cfg, m @ ck.f32(p + "mlp.gate.weight").T)
    margins.append(margin)
    out = np.zeros_like(m)
    for e in held_experts(cfg):
        rows, slot = np.nonzero(idx == e)
        if len(rows):
            out[rows] += weight[rows, slot][:, None] * mlp(
                f"{p}mlp.experts.{e}.", m[rows])
    shared = mlp(p + "mlp.shared_expert.", m)
    if shared_gate:
        shared = shared * _sigmoid(
            m @ ck.f32(p + "mlp.shared_expert_gate.weight").T)
    return out + shared


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple],
                    **controls) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place (``reference.score_pairs``), given
    the same share of the experts as the server. A layer at a time, so
    that the published widths fit the host. ``controls`` (``gate``,
    ``rotate_all``, ``shared_gate``, ``state_dtype``): the mechanisms'
    controls, for the builder's scratch runs."""
    ck = Checkpoint(model_dir)
    eps = cfg["rms_norm_eps"]
    embed = ck.f32("model.embed_tokens.weight")
    xs = [embed[np.asarray(list(prompt) + list(chosen[:-1]), np.int64)]
          for prompt, chosen in pairs]
    del embed
    attend = {k: controls[k] for k in ("gate", "rotate_all")
              if k in controls}
    margins: list[list] = [[] for _ in pairs]
    for i in range(cfg["num_hidden_layers"]):
        p, layer = f"model.layers.{i}.", Layer(ck)
        for n, x in enumerate(xs):
            u = _norm(x, layer.f32(p + "input_layernorm.weight"), eps)
            if is_full_layer(cfg, i):
                x = x + _attention(cfg, layer, p, u, **attend)
            else:
                x = x + _delta_net(cfg, layer, p, u,
                                   controls.get("state_dtype"))
            xs[n] = x + _feed_forward(cfg, layer, p, _norm(
                x, layer.f32(p + "post_attention_layernorm.weight"), eps),
                margins[n], controls.get("shared_gate", True))
    # the last norm is (1 + w) too: hand score_pairs a checkpoint that
    # reads it so
    return score_pairs(_OnePlusNorm(ck), eps, pairs, xs, margins)


class _OnePlusNorm:
    """A checkpoint whose ``model.norm.weight`` reads as ``1 + w``."""

    def __init__(self, ck: Checkpoint):
        self.ck = ck

    def f32(self, name: str) -> np.ndarray:
        w = self.ck.f32(name)
        return np.float32(1.0) + w if name == "model.norm.weight" else w


# -- bytes a decode step must move ---------------------------------------------

def held_experts_hit(cfg: dict, rows: float) -> float:
    """How many of the experts held here some row is routed to."""
    return _mla.held_experts_hit(_as_mla(cfg), rows)


def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams: the
    mixers, shared experts and routers once, of the HELD experts those
    some row is routed to, the head's slice, an embedding row a stream),
    or with ``rows=None`` all the weights the device holds, embedding
    included: the number a parameter count checks."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    held = cfg["num_experts"]
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        total += _plain_values(cfg, i) * plain_b
        for suffix, (fan_in, out) in layer_linears(cfg, i).items():
            b = linear_bytes(fan_in, out, layout)
            if ".experts." in suffix and rows is not None:
                b *= held_experts_hit(cfg, rows) / held
            total += b
    embed_rows = v if rows is None else rows
    return (total + embed_rows * h * plain_b + h * plain_b
            + linear_bytes(h, v, layout))


def kda_decode_bytes(cfg: dict, rows: float) -> float:
    """Bytes one delta-rule layer's decode step must move for ``rows``
    streams (the kernel ``kda_decode``, its scalar case): one read and one
    write of each VALUE head's float32 state, its v in and o out, ONE decay
    and one beta a value head, and k and q a KEY head (``d_k`` wide; all
    float32)."""
    hk, hv, dk, dv, _ = _delta(cfg)
    return 4.0 * rows * (hv * (2 * dk * dv + 2 * dv + 2) + 2 * hk * dk)


def kda_decode_flops(cfg: dict, rows: float) -> float:
    """Operations of the same step: decay, ``k^T S``, the rank-one update
    and ``q^T S``, two a state element each."""
    _, hv, dk, dv, _ = _delta(cfg)
    return 8.0 * rows * hv * dk * dv


def state_bytes(cfg: dict, rows: float, cache_dtype: str = "bf16") -> float:
    """Bytes of recurrent state ``rows`` streams move in one step: every
    delta-rule layer's state read and written once, its convolution tail
    read and written once."""
    return 2.0 * rows * state_bytes_per_stream(cfg, cache_dtype)


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of cached rows ``rows`` streams at a mean position of
    ``context`` read in one step: ``context`` rows a FULL layer."""
    full = cfg["num_hidden_layers"] - delta_layers(cfg)
    return (rows * context * full * cache_row_values(cfg)
            * PLAIN_BYTES[cache_dtype])


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step moves: the weights for ``rows`` live
    streams, the full layers' rows at a mean position of ``context``, and
    the delta-rule layers' state once in and once out."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype)
            + state_bytes(cfg, rows, serve_dtype))
