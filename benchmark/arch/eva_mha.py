"""Architecture ``eva_mha``: a dense multi-head decoder whose attention is
EVA (EvaByte's ``config.json`` keys, ``model_type`` ``evabyte``): exact
softmax attention inside a window that RESETS every ``window_size``
positions, one learned summary row for every ``chunk_size`` positions of
the windows completed before it, ONE softmax over both; a byte vocabulary
under ``num_pred_heads`` prediction heads, of which head 0 is the model's
own next token.

- ``RMS(x; w) = x / rms(x) * (1 + w)`` (``norm_add_unit_offset``; eps
  ``rms_norm_eps``). 32 identical pre-norm layers: ``x += EVA(RMS(x;
  input_layernorm)) W_o``; ``x += W_down(silu(W_gate h) * W_up h)``, ``h =
  RMS(x; post_attention_layernorm)``. No biases, untied embedding and head.
- ``q_n, k_n, v_n`` = heads of ``(h W_q, h W_k, h W_v)``, as many key/value
  heads as query heads, q and k rotated over the whole head (half-split
  pairs, base ``rope_theta``, no scaling), ``s = head_dim^-0.5``.
- With ``W = window_size``, ``C = chunk_size`` and two learned vectors a
  head, ``phi_h`` (``self_attn.adaptive_phi``) and ``mu_h``
  (``self_attn.adaptive_mu_k``, both stored ``[1, heads, 1, 1,
  head_dim]``), chunk ``c`` = positions ``[cC, (c + 1)C)``::

      a_m  = softmax over m in c of (s * phi_h . k_m)
      v~_c = sum_m a_m v_m             k~_c = mean_m k_m + mu_h
      L_n  = {m : m // W == n // W, m <= n}        (exact, at most W rows)
      R_n  = {c : (c + 1) C <= (n // W) W}         (completed windows' chunks)
      EVA_n = softmax over L_n and R_n TOGETHER of (s q_n . k_m | s q_n . k~_c)
              applied to (v_m | v~_c)

- ``lm_head.weight [num_pred_heads * vocab_size, hidden]``: head ``j`` at
  rows ``vocab_size j .. vocab_size (j + 1) - 1``; the model's own
  next-token logits are head 0's (heads 1.. predict further tokens for the
  release's self-speculative decoding and take no part here).

READINGS, not keys (the configuration's ``assumed`` lists each): that the
summary's weights come from the learned ``phi_h`` with no random draw and
no ``-|k|^2 / 2`` term (the EVA paper's final estimator, arXiv:2302.04542,
self-normalised over the chunk: under a softmax the term is this reading's
to keep or drop, and it is dropped), that keys are pooled uniformly and
AFTER the rotation (they are cached rotated), that a chunk becomes visible
when its WINDOW completes and not when the chunk does, the rope's pairing,
the head's row order, the tensor names.

Numpy and the standard library only (the parent of a chip run never
imports JAX). The reference is written from the equations above (ISSUE 66,
Motivation), not from the program: the whole sequence at once, no cache,
each layer's summaries made a chunk at a time from the sequence's own
keys and values, attention a block of ``QUERY_ROWS`` query rows of ONE
window at a time over that window's keys and the summaries before it, so
that 16k positions fit. What it shares with the bare stack (linear shapes,
SwiGLU, norm, rope) comes from ``arch/gqa.py``, ``reference`` and
``weights``.

The CONTROLS (``WRONG``; ``chosen_logprobs(..., wrong=form)``,
``write_rounded``): the same reference with ONE piece of the mathematics
changed, which the comparison that decides ``correct`` has to tell from
the program (``benchmark/eva_controls.py`` holds served ids to each and
serves two of them; the configuration's ``margin_tol_why`` has the
readings). ``float8`` alone needs ``ml_dtypes`` (numpy's extension types:
no JAX).

What a step reads (``decode_step_bytes``): the weights once, and of every
layer the LIVE window rows and the VISIBLE summary rows of every stream
(``eva_decode_bytes``): what the model attends, not what the buffers hold.
"""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from arch import gqa
from reference import Layer, rope, swiglu
from shapes import PLAIN_BYTES, linear_bytes
from weights import (Checkpoint, File, bf16_bits, hf_config, linear, norm,
                     plain, rngs, small, write_files)

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "attention_class", "attention_bias",
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "hidden_act",
    "window_size", "chunk_size", "num_chunks", "num_pred_heads",
    "norm_add_unit_offset", "fp32_ln", "fp32_logits", "fp32_skip_add",
    "mixedp_attn", "max_position_embeddings", "max_seq_length",
    "rms_norm_eps", "rope_scaling", "rope_theta", "tie_word_embeddings",
    "init_fn", "init_std", "init_cutoff_factor", "lazy_init", "torch_dtype",
    "bos_token_id", "eos_token_id",
)

QUERY_ROWS = 512  # query rows a block of the reference's scores
MODEL_TYPE = "evabyte"
NORMS = ("input_layernorm", "post_attention_layernorm")
PHI, MU = "self_attn.adaptive_phi", "self_attn.adaptive_mu_k"
# What the two learned vectors are sized for (the configuration's
# ``assumed``; the constants were sized with this reference before any chip
# time): ``phi`` of std 1 (as written 1.15: a uniform int8 times the nearest
# power of two; a chunk's ``s phi . k`` is then a logit of std ~1.2, so the
# summary's softmax leans on a few of its 16 positions and pooling them
# uniformly shows) and ``mu`` of std 1 (1.15; a summary's key is the mean
# of 16 keys, a quarter of a key's spread: ``s q . mu`` lifts or lowers ALL
# of a head's summaries together for a query, by about a local score's
# spread, so that half the (head, query) pairs lean on the summaries and
# half on the window: the comparison SEES the mechanism). Every norm keeps
# the usual 0.875-1.25: with ``input_layernorm`` half again as large a
# head's scores spread 2.25 times as wide, its softmax hangs on ONE row,
# and bfloat16's rounding of the scores flips that row: the served
# program's own log-probabilities then moved by 0.5-0.9 nats and its worst
# probe margin read 0.67 (my chip runs, PR 66), where this sizing moves the
# controls as far and the program's rounding an eighth as far.
PHI_STD, MU_STD = 1.0, 1.0


def require_family(checkout: Path) -> None:
    """Refuse a checkout whose program does not name this family. The
    linears and norms carry Llama's names, so a program from before the
    family reads ``model_type`` "evabyte" as a plain decoder: it skips
    ``adaptive_phi`` and ``adaptive_mu_k`` as tensors that are no part of
    the model, attends every row of a full cache under norms without
    their one, and serves that: not correct, and 133 MiB a layer a stream
    that no chip holds. Such a checkout cannot run this configuration,
    and a run on it fails here, at once, and measures nothing under the
    cell's name. Asked of the source (the parent of a chip run imports
    neither JAX nor ``cake_tpu``): a family is declared by its
    ``model_type`` under ``cake_tpu/models/``."""
    models = checkout / "cake_tpu" / "models"
    if not any(f'"{MODEL_TYPE}"' in path.read_text()
               for path in sorted(models.glob("*.py"))):
        raise RuntimeError(
            f"no module under {models} declares model_type {MODEL_TYPE!r}: "
            "this program would serve an EVA decoder's checkpoint as a "
            "plain decoder (full attention, no summaries, norms without "
            "their unit offset); the cell needs the program's EVA family "
            "(cake_tpu/models/families.py)")


require_family(Path(__file__).resolve().parents[2])


# -- sizes -----------------------------------------------------------------------

def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _per_head(cfg: dict) -> int:
    """Values of one of a layer's two learned vectors a head."""
    return cfg["num_attention_heads"] * head_dim(cfg)


def _layer_plain(cfg: dict) -> int:
    """Unquantized values of one layer: two norms, ``phi`` and ``mu``."""
    return len(NORMS) * cfg["hidden_size"] + 2 * _per_head(cfg)


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits): the
    head with ALL its prediction blocks."""
    per = 1 if layout == "q8" else 2
    unq = 4 if layout == "q8" else 2  # an unquantized tensor's bytes
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    rows = v * cfg["num_pred_heads"]
    layer = sum(i * o * per + (4 * o if layout == "q8" else 0)
                for i, o in gqa.layer_linears(cfg).values())
    head = rows * h * per + (4 * rows if layout == "q8" else 0)
    return (cfg["num_hidden_layers"] * (layer + _layer_plain(cfg) * unq)
            + (v * h + h) * unq + head)


# -- the checkpoint --------------------------------------------------------------

def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}. Norms are
    stored as ``w - 1`` (``norm_add_unit_offset``)."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    linears = gqa.layer_linears(cfg)
    per_head = (1, cfg["num_attention_heads"], 1, 1, head_dim(cfg))

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        for name in NORMS:
            plain(f, layout, f"{p}{name}.weight", norm(next(r), h) - 1.0)
        plain(f, layout, p + PHI, small(next(r), per_head, PHI_STD))
        plain(f, layout, p + MU, small(next(r), per_head, MU_STD))
        for suffix, (fan_in, out) in linears.items():
            linear(f, next(r), layout, p + suffix, fan_in, out)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        plain(f, layout, "model.embed_tokens.weight",
              small(next(r), (v, h), 1.0 / math.sqrt(h)))
        plain(f, layout, "model.norm.weight", norm(next(r), h) - 1.0)
        linear(f, next(r), layout, "lm_head.weight", h,
               v * cfg["num_pred_heads"])
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def rms_norm(x: np.ndarray, w: np.ndarray, eps: float) -> np.ndarray:
    """``x / rms(x) * (1 + w)``: the weight is stored as an offset from
    one."""
    var = np.mean(x * x, axis=-1, keepdims=True)
    return x / np.sqrt(var + np.float32(eps)) * (np.float32(1.0) + w)


def _softmax(s: np.ndarray) -> np.ndarray:
    w = np.exp(s - s.max(-1, keepdims=True))
    return w / w.sum(-1, keepdims=True)


# The controls: the reference with ONE piece of the mathematics changed
# (ISSUE 66, item 3), which ``correct`` has to refuse. ``window_only``: no
# summaries, the window alone. ``sliding``: a window that slides (the last
# ``W`` positions, the summaries of the chunks wholly before them) instead
# of resetting. ``early_chunk``: a chunk visible as soon as it is made (so
# a query sees the chunks of its own window twice). ``uniform_v``: ``v~``
# the chunk's uniform mean, ``phi`` unused. ``float8``: every linear
# rounded through float8 (e4m3) on load.
WRONG = ("window_only", "sliding", "early_chunk", "uniform_v", "float8")


def float8(w: np.ndarray) -> np.ndarray:
    """``w`` rounded through float8 (e4m3, no scale), as float32."""
    import ml_dtypes

    return np.asarray(w, np.float32).astype(
        ml_dtypes.float8_e4m3fn).astype(np.float32)


def write_rounded(model_dir: Path, out_dir: Path) -> None:
    """The bf16 checkpoint of ``model_dir`` again in ``out_dir``, every
    linear rounded through float8: the control ``float8`` on the PROGRAM's
    side (a server loads these; the reference reads the true ones)."""
    ck = Checkpoint(model_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    files: dict[str, list[str]] = {}
    for name, fname in ck.files.items():
        files.setdefault(fname, []).append(name)
    for fname, names in files.items():
        f = File(out_dir / fname)
        for name in names:
            stored, dtype = ck.raw(name)
            if dtype != "BF16":
                raise ValueError(f"{name} is {dtype}: the bf16 layout only")
            linear_ = name.endswith("_proj.weight") or name == "lm_head.weight"
            f.add(name, dtype, stored.shape,
                  bf16_bits(float8(ck.f32(name))) if linear_
                  else np.asarray(stored))
        f.write()
    for name in ("model.safetensors.index.json", "config.json"):
        (out_dir / name).write_bytes((Path(model_dir) / name).read_bytes())


def _linear(ck, wrong: str | None):
    """How a linear's weight is read: as stored, or through float8."""
    if wrong == "float8":
        return lambda name: float8(ck.f32(name))
    return ck.f32


def _sees(wrong: str | None, rows: np.ndarray, first: int, last: int,
          w: int, c: int):
    """What a block of query ``rows [n, 1]`` of the window ``[first,
    last)`` sees: ``(key range, summaries, local mask, remote mask)``: the
    positions ``[lo, hi)`` whose keys enter the buffer, how many summary
    rows follow them, and which of both each query row may attend. As
    published: its window's positions at or before it, and every chunk of
    every window before (no mask: ``None``)."""
    top = int(rows[-1, 0]) + 1
    lo, hi, seen, remote = first, last, first // c, None
    if wrong == "window_only":
        seen = 0
    elif wrong == "early_chunk":
        seen = top // c
        remote = (np.arange(seen)[None, :] + 1) * c <= rows + 1
    elif wrong == "sliding":
        lo, hi, seen = max(0, int(rows[0, 0]) - w + 1), top, max(
            0, top - w) // c
        remote = (np.arange(seen)[None, :] + 1) * c <= rows - w + 1
    at = np.arange(lo, hi)[None, :]
    local = at <= rows
    if wrong == "sliding":
        local &= at > rows - w
    return (lo, hi), seen, local, remote


def _attention(cfg: dict, ck: Layer, p: str, x: np.ndarray,
               wrong: str | None = None) -> np.ndarray:
    """Layer ``p``'s EVA attention over one whole sequence ``x [t,
    hidden]``: the summaries of every COMPLETE chunk a chunk at a time,
    then a window at a time, a block of query rows at a time, one softmax
    over the window's keys at or before the query and the summaries of
    the windows before (``wrong``: one of ``WRONG`` in its place).
    (Batched ``@`` throughout: numpy's ``einsum`` takes no BLAS path for
    these and a 6,000-token probe would take an hour.)"""
    t = x.shape[0]
    nh, d = cfg["num_attention_heads"], head_dim(cfg)
    w, c = cfg["window_size"], cfg["chunk_size"]
    a = p + "self_attn."
    scale = np.float32(d ** -0.5)
    lin = _linear(ck, wrong)

    def heads(name: str) -> np.ndarray:
        y = (x @ lin(a + f"{name}_proj.weight").T).reshape(t, nh, d)
        return np.ascontiguousarray(y.transpose(1, 0, 2))  # [nh, t, d]

    theta = float(cfg["rope_theta"])
    q, k, v = rope(heads("q"), theta), rope(heads("k"), theta), heads("v")
    phi = ck.f32(p + PHI).reshape(nh, d)
    mu = ck.f32(p + MU).reshape(nh, d)
    # a query sees a chunk only once its WINDOW is complete, so the
    # chunks of the last, incomplete window need no summary (two of the
    # controls see them all the same)
    chunks = (t // c if wrong in ("early_chunk", "sliding")
              else (t // w) * (w // c))
    kc = k[:, :chunks * c].reshape(nh, chunks, c, d)
    vc = v[:, :chunks * c].reshape(nh, chunks, c, d)
    share = _softmax((kc @ phi[:, None, :, None])[..., 0] * scale)
    if wrong == "uniform_v":
        share = np.full_like(share, 1.0 / c)
    v_sum = (share[:, :, None, :] @ vc)[:, :, 0]  # [nh, chunks, d]
    k_sum = kc.mean(2) + mu[:, None]
    out = np.empty((t, nh, d), np.float32)
    held = None  # the buffer of the block before, where it is this one's
    for first in range(0, t, w):  # the query's window
        last = min(first + w, t)
        for lo in range(first, last, QUERY_ROWS):
            rows = np.arange(lo, min(lo + QUERY_ROWS, last))
            span, seen, local, remote = _sees(
                wrong, rows[:, None], first, last, w, c)
            if held != (span, seen):
                held = (span, seen)
                keys = np.concatenate(
                    [k[:, span[0]:span[1]], k_sum[:, :seen]], 1)
                vals = np.concatenate(
                    [v[:, span[0]:span[1]], v_sum[:, :seen]], 1)
            ok = np.concatenate(
                [local, np.ones((len(rows), seen), bool)
                 if remote is None else remote], 1)
            s = (q[:, rows] @ keys.transpose(0, 2, 1)) * scale
            s = np.where(ok[None], s, np.float32(-np.inf))
            out[rows] = (_softmax(s) @ vals).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ lin(a + "o_proj.weight").T


def _logprobs(cfg: dict, model_dir, pairs: list[tuple],
              wrong: str | None = None) -> list[np.ndarray]:
    """For each (prompt, chosen) pair the reference's log-probabilities
    ``[len(chosen), vocab_size]`` (float64) at the places the chosen
    tokens were predicted from: head 0 of the stored head."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown control {wrong!r}: one of {WRONG}")
    ck = Checkpoint(model_dir)
    eps = cfg["rms_norm_eps"]
    embed = ck.f32("model.embed_tokens.weight")
    xs = [embed[np.asarray(list(prompt) + list(chosen[:-1]), np.int64)]
          for prompt, chosen in pairs]
    del embed
    for i in range(cfg["num_hidden_layers"]):
        p, layer = f"model.layers.{i}.", Layer(ck)
        lin = _linear(layer, wrong)
        for n, x in enumerate(xs):
            x = x + _attention(cfg, layer, p, rms_norm(
                x, layer.f32(f"{p}{NORMS[0]}.weight"), eps), wrong)
            xs[n] = x + swiglu(
                rms_norm(x, layer.f32(f"{p}{NORMS[1]}.weight"), eps),
                lin(p + "mlp.gate_proj.weight"),
                lin(p + "mlp.up_proj.weight"),
                lin(p + "mlp.down_proj.weight"))
    last = ck.f32("model.norm.weight")
    head = _linear(ck, wrong)("lm_head.weight")[:cfg["vocab_size"]]  # head 0
    out = []
    for (prompt, _), x in zip(pairs, xs):
        logits = (rms_norm(x[len(prompt) - 1:], last, eps)
                  @ head.T).astype(np.float64)
        logits -= logits.max(-1, keepdims=True)
        out.append(logits - np.log(np.exp(logits).sum(-1, keepdims=True)))
    return out


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple],
                    wrong: str | None = None) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place: ``{"logprob", "best",
    "best_logprob", "routing_margin"}`` (the last None everywhere: nothing
    routes). ``wrong``: one of ``WRONG``, the control's answers in the
    reference's place."""
    out = []
    for (_, chosen), logp in zip(pairs, _logprobs(cfg, model_dir, pairs,
                                                  wrong)):
        best = logp.argmax(-1)
        at = np.arange(len(chosen))
        out.append({"logprob": [float(v) for v in logp[at, chosen]],
                    "best": [int(b) for b in best],
                    "best_logprob": [float(v) for v in logp[at, best]],
                    "routing_margin": [None] * len(chosen)})
    return out


# -- bytes a decode step must read ---------------------------------------------

def _layer_bytes(cfg: dict, layout: str, serve_dtype: str) -> int:
    """Bytes of one layer's weights: its linears, two norms, phi and mu."""
    return _layer_plain(cfg) * PLAIN_BYTES[serve_dtype] + sum(
        linear_bytes(fan_in, out, layout)
        for fan_in, out in gqa.layer_linears(cfg).values())


def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of the weights one decode step reads (``rows`` live streams:
    the layers, the last norm, head 0, an embedding row a stream), or with
    ``rows=None`` all the weights the device holds for decoding: the
    number a parameter count checks. The device holds HEAD 0 of the stored
    head: the other prediction blocks are never loaded."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    embed_rows = v if rows is None else rows
    return (cfg["num_hidden_layers"] * _layer_bytes(cfg, layout, serve_dtype)
            + (embed_rows * h + h) * plain_b + linear_bytes(h, v, layout))


def row_bytes(cfg: dict, cache_dtype: str = "bf16") -> int:
    """Bytes of ONE row of ONE layer, a window's or a summary's alike:
    keys and values of every head."""
    return 2 * _per_head(cfg) * PLAIN_BYTES[cache_dtype]


def token_bytes(cfg: dict, cache_dtype: str = "bf16") -> int:
    """Bytes of cache one MORE position of one stream costs beyond the
    window: a summary row for every ``chunk_size`` positions, a layer."""
    return (cfg["num_hidden_layers"] * row_bytes(cfg, cache_dtype)
            // cfg["chunk_size"])


def rows_attended(cfg: dict, position: float) -> tuple[float, float]:
    """``(window rows, summary rows)`` a query at ``position`` attends a
    layer: the rows of its own window at or before it, and every chunk of
    every window completed before."""
    w, c = cfg["window_size"], cfg["chunk_size"]
    done = math.floor(position / w)
    return position - done * w + 1, done * (w // c)


def eva_decode_bytes(cfg: dict, window_rows: float, summary_rows: float,
                     cache_dtype: str = "bf16") -> float:
    """The least the step's attention must read: ``window_rows`` live ring
    rows and ``summary_rows`` visible summary rows (each summed over
    layers, steps and streams), keys and values of every head, once. (The
    kernel reads whole blocks of 128 rows of each buffer: the share reads
    under 100 by as much.)"""
    return (window_rows + summary_rows) * row_bytes(cfg, cache_dtype)


def eva_trace_ops(cfg: dict) -> dict[str, str]:
    """Patterns (``re.match`` on a reduced trace's operation names) of the
    EVA path's operations, as the program names them: ``decode`` (the
    step's kernel over ring and summary plane: one call a layer and
    step), ``prefill`` (an admission's window: the flash prefill kernel
    under this name, one call a window, layer and dispatch)."""
    return {"decode": r"eva_decode\b", "prefill": r"eva_prefill\b"}


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of cache ``rows`` streams at a position of ``context`` read
    in one step: what a query there attends (:func:`rows_attended`), every
    layer. (Taken AT the mean position the harness hands over: a window's
    live rows are a sawtooth of the position, so a mix's mean over
    positions is the counters' to give, ``kernel.eva_decode_hbm_share``.)"""
    window, summaries = rows_attended(cfg, context)
    return rows * cfg["num_hidden_layers"] * eva_decode_bytes(
        cfg, window, summaries, cache_dtype)


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step reads: the weights for ``rows`` live
    streams and what their queries attend at a position of ``context``."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype))
