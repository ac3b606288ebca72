"""Architecture ``looped_gqa``: a dense decoder whose ``num_hidden_layers``
sandwich-normed layers run ``total_ut_steps`` times a token over ONE set of
weights, the model's last norm closing every pass and none standing before
the head: Ouro-2.6B's ``config.json`` keys (``model_type`` ``ouro``).

- ``h = E[tokens]``; for ``u`` in ``0 .. total_ut_steps - 1``: the layers in
  order, the same weights every pass; then ``h = RMS(h; model.norm)``. The
  normed state is pass ``u``'s output and pass ``u + 1``'s input; the last
  one goes through ``lm_head`` (untied) as it is.
- Layer ``i``: ``a = Attn_i(RMS(h; input_layernorm))``, ``h += RMS(a;
  input_layernorm_2)``, ``m = SwiGLU_i(RMS(h; post_attention_layernorm))``,
  ``h += RMS(m; post_attention_layernorm_2)``: the second norm of each pair
  norms the sub-layer's OUTPUT before the residual adds it.
- Attention: q, k, v, o with no bias, as many key/value heads as the file
  says, rotate-half RoPE over the whole head at the token's position (the
  same in every pass), scale ``d^-0.5``, full causal. A pass attends over
  the keys and values THAT pass computed: what the program keeps in a cache
  plane a (pass, layer) pair.
- ``model.early_exit_gate`` (a linear of one output on each pass's output)
  gives the exit distribution; at the published ``early_exit_threshold`` 1
  every token takes every pass and the gate changes no logit: the writer
  writes its tensors, the program loads and keeps them, and the
  ``jax.numpy`` reference (``cake_tpu/testing/reference_ouro.py``) reads
  them; this reference, which decides ``correct`` from logits, does not.

Numpy and the standard library only (the parent of a chip run never
imports JAX). The writer puts the tensors under the names the program's
loader reads; they are ASSUMED (the configuration's ``assumed``): Llama's,
a sub-layer's second norm under its first one's name with ``_2``. The
reference is written from the equations ISSUE 47 states (Motivation), not
from the program: the whole sequence at once, no cache, the passes a
Python loop over a Python loop over the layers, attention a block of
``QUERY_ROWS`` query rows and a head at a time. The linears' shapes and
what architectures share come from ``arch/gqa.py``, ``reference`` and
``weights``.

What a step reads: the layers' weights once a PASS (``step_weight_reads``),
the head once, and the live rows of every plane (``kv_bytes``: the LIVE
rows, as the other architectures count them; a program that sweeps the
whole reservation pays for it in its share of the roofline).
"""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from arch import gqa
from reference import Layer, rms_norm, rope, swiglu
from shapes import PLAIN_BYTES, linear_bytes
from weights import (Checkpoint, File, hf_config, linear, norm, plain, rngs,
                     small, write_files)

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "vocab_size", "hidden_size",
    "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "hidden_act", "layer_types",
    "max_position_embeddings", "max_window_layers", "rms_norm_eps",
    "rope_scaling", "rope_theta", "sliding_window", "use_sliding_window",
    "tie_word_embeddings", "total_ut_steps", "early_exit_threshold",
    "torch_dtype", "bos_token_id", "eos_token_id",
)

QUERY_ROWS = 512  # query rows a block of the reference's scores
MODEL_TYPE = "ouro"


def require_family(checkout: Path) -> None:
    """Refuse a checkout whose program does not name this family. The
    tensors carry Llama's names, so a program from before the family reads
    ``model_type`` "ouro" as a plain decoder: it skips the second norms and
    the gate as tensors that are no part of the model, runs the layers
    ONCE, and serves that, four times as fast and not correct. Such a
    checkout cannot run this configuration, and a run on it fails here, at
    once, and measures nothing under the cell's name. Asked of the source
    (the parent of a chip run imports neither JAX nor ``cake_tpu``): a
    family is declared by its ``model_type`` under ``cake_tpu/models/``."""
    models = checkout / "cake_tpu" / "models"
    if not any(f'"{MODEL_TYPE}"' in path.read_text()
               for path in sorted(models.glob("*.py"))):
        raise RuntimeError(
            f"no module under {models} declares model_type {MODEL_TYPE!r}: "
            "this program would serve a looped decoder's checkpoint as a "
            "plain decoder, one pass of "
            "total_ut_steps and no sandwich norms; the cell needs the "
            "program's looped family (cake_tpu/models/families.py)")


require_family(Path(__file__).resolve().parents[2])

NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")
GATE = "model.early_exit_gate."


# -- sizes -----------------------------------------------------------------------

def passes(cfg: dict) -> int:
    return cfg["total_ut_steps"]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (
        cfg["hidden_size"] // cfg["num_attention_heads"])


def planes(cfg: dict) -> int:
    """Cache planes: one a layer AND a pass."""
    return cfg["num_hidden_layers"] * passes(cfg)


def _plain_values(cfg: dict) -> int:
    """Unquantized values beside the layers' linears, the embedding and the
    head: four norms a layer, the last norm, the gate and its bias."""
    h = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * len(NORMS) * h + h + h + 1


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits)."""
    per = 1 if layout == "q8" else 2
    unq = 4 if layout == "q8" else 2  # an unquantized tensor's bytes
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layer = sum(i * o * per + (4 * o if layout == "q8" else 0)
                for i, o in gqa.layer_linears(cfg).values())
    head = v * h * per + (4 * v if layout == "q8" else 0)
    return (cfg["num_hidden_layers"] * layer + (_plain_values(cfg) + v * h)
            * unq + head)


# -- the checkpoint --------------------------------------------------------------

def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    linears = gqa.layer_linears(cfg)

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        for name in NORMS:
            plain(f, layout, f"{p}{name}.weight", norm(next(r), h))
        for suffix, (fan_in, out) in linears.items():
            linear(f, next(r), layout, p + suffix, fan_in, out)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        plain(f, layout, "model.embed_tokens.weight",
              small(next(r), (v, h), 1.0 / math.sqrt(h)))
        plain(f, layout, "model.norm.weight", norm(next(r), h))
        linear(f, next(r), layout, "lm_head.weight", h, v)
        plain(f, layout, GATE + "weight",
              small(next(r), (1, h), 1.0 / math.sqrt(h)))
        plain(f, layout, GATE + "bias", small(next(r), (1,), 0.25))
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def _attention(cfg: dict, ck: Layer, p: str, x: np.ndarray) -> np.ndarray:
    """Layer ``p``'s attention over one whole sequence: q and k rotated,
    scores under the explicit causal mask, a block of query rows and a
    key/value head at a time."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    a = p + "self_attn."

    def heads(name: str, n: int) -> np.ndarray:
        y = (x @ ck.f32(a + f"{name}_proj.weight").T).reshape(t, n, d)
        return np.ascontiguousarray(y.transpose(1, 0, 2))  # [n, t, d]

    theta = float(cfg["rope_theta"])
    q, k, v = rope(heads("q", nh), theta), rope(heads("k", nkv), theta), heads(
        "v", nkv)
    g = nh // nkv
    out = np.empty((t, nh, d), np.float32)
    at = np.arange(t)
    for lo in range(0, t, QUERY_ROWS):
        rows = at[lo:lo + QUERY_ROWS]
        seen = at[None, :] <= rows[:, None]
        for kh in range(nkv):
            s = (q[kh * g:(kh + 1) * g, rows] @ k[kh].T) * np.float32(
                d ** -0.5)  # [g, rows, t]
            s = np.where(seen[None], s, np.float32(-np.inf))
            s = s - s.max(-1, keepdims=True)
            w = np.exp(s)
            w /= w.sum(-1, keepdims=True)
            out[rows, kh * g:(kh + 1) * g] = (w @ v[kh]).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ ck.f32(a + "o_proj.weight").T


def last_pass(cfg: dict, ck: Checkpoint, xs: list) -> list:
    """The last pass's normed output of every sequence in ``xs`` (embedded
    tokens, ``[t, hidden]`` each): what the head reads. A layer's tensors
    are read once a pass for all the sequences."""
    eps = cfg["rms_norm_eps"]
    last = ck.f32("model.norm.weight")
    for _ in range(passes(cfg)):
        for i in range(cfg["num_hidden_layers"]):
            p, layer = f"model.layers.{i}.", Layer(ck)

            def normed(x, name):
                return rms_norm(x, layer.f32(f"{p}{name}.weight"), eps)

            for n, x in enumerate(xs):
                a = _attention(cfg, layer, p, normed(x, NORMS[0]))
                x = x + normed(a, NORMS[1])
                m = swiglu(normed(x, NORMS[2]),
                           layer.f32(p + "mlp.gate_proj.weight"),
                           layer.f32(p + "mlp.up_proj.weight"),
                           layer.f32(p + "mlp.down_proj.weight"))
                xs[n] = x + normed(m, NORMS[3])
        xs = [rms_norm(x, last, eps) for x in xs]
    return xs


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place: ``{"logprob", "best",
    "best_logprob", "routing_margin"}`` (the last None everywhere: nothing
    routes). Every token takes every pass (``early_exit_threshold`` 1),
    and the last pass's normed output goes through the head with NO
    further norm, so this is not ``reference.score_pairs``, which norms."""
    if cfg.get("early_exit_threshold", 1) < 1:
        raise ValueError("this reference takes every pass (threshold 1)")
    ck = Checkpoint(model_dir)
    embed = ck.f32("model.embed_tokens.weight")
    xs = [embed[np.asarray(list(prompt) + list(chosen[:-1]), np.int64)]
          for prompt, chosen in pairs]
    del embed
    xs = last_pass(cfg, ck, xs)
    head = ck.f32("lm_head.weight")
    out = []
    for (prompt, chosen), x in zip(pairs, xs):
        logits = (x[len(prompt) - 1:] @ head.T).astype(np.float64)
        logits -= logits.max(-1, keepdims=True)
        logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        best = logp.argmax(-1)
        at = np.arange(len(chosen))
        out.append({"logprob": [float(v) for v in logp[at, chosen]],
                    "best": [int(b) for b in best],
                    "best_logprob": [float(v) for v in logp[at, best]],
                    "routing_margin": [None] * len(chosen)})
    return out


# -- bytes a decode step must read ---------------------------------------------

def _layer_bytes(cfg: dict, layout: str, serve_dtype: str) -> int:
    """Bytes of one layer's weights: its linears and its four norms."""
    return len(NORMS) * cfg["hidden_size"] * PLAIN_BYTES[serve_dtype] + sum(
        linear_bytes(fan_in, out, layout)
        for fan_in, out in gqa.layer_linears(cfg).values())


def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of the weights one decode step touches (``rows`` live streams:
    the layers, the last norm, the head, an embedding row a stream; the
    exit gate not at all: at threshold 1 it changes no logit), each
    counted ONCE, or with ``rows=None`` all the weights the device holds:
    the number a parameter count checks. How often a step READS them is
    :func:`step_weight_reads`: the harness holds ``weight_bytes`` with
    ``rows`` to what the device holds, which a loop's reads pass."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    layers = cfg["num_hidden_layers"] * _layer_bytes(cfg, layout, serve_dtype)
    head = linear_bytes(h, v, layout)
    if rows is None:
        return layers + (v * h + h + h + 1) * plain_b + head
    return layers + (h + rows * h) * plain_b + head


def step_weight_reads(cfg: dict, layout: str, rows: float,
                      serve_dtype: str = "bf16") -> float:
    """Bytes of weights one decode step READS: the layers and the norm
    that closes a pass once a PASS (a step's activations are a few rows:
    nothing of a 4.9 GB stack stays on the chip from one pass to the
    next), the head once, an embedding row a stream."""
    h = cfg["hidden_size"]
    once = (cfg["num_hidden_layers"] * _layer_bytes(cfg, layout, serve_dtype)
            + h * PLAIN_BYTES[serve_dtype])
    return weight_bytes(cfg, layout, serve_dtype, rows) + (
        passes(cfg) - 1) * once


def token_bytes(cfg: dict, cache_dtype: str = "bf16") -> int:
    """Bytes of cache one token of one stream holds: keys and values of
    every key/value head in every plane."""
    return (planes(cfg) * 2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * PLAIN_BYTES[cache_dtype])


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of keys and values ``rows`` streams at a mean position of
    ``context`` read in one step: the LIVE rows of every plane (each pass
    of each layer reads its own)."""
    return rows * context * token_bytes(cfg, cache_dtype)


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step reads: the weights for ``rows`` live
    streams, the layers once a pass, and their keys and values at a mean
    position of ``context`` in every plane."""
    return (step_weight_reads(cfg, layout, rows, serve_dtype)
            + kv_bytes(cfg, context, rows, serve_dtype))
