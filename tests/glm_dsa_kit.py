"""What the two files of GLM-5's tests share (``tests/test_glm_dsa.py``,
``tests/test_glm_dsa_engine.py``: two files so that the driver's workers
share them): the tiny configuration, seeded parameters and the reference's
logits (module-scoped fixtures, built once a file), the step program and
the engine helpers. A plain module the parts import, not a conftest
plugin. The family's account:

Latent attention under a learned sparse attention (``model_type``
``glm_moe_dsa``: an indexer of ``index_n_heads`` heads scores every row at
or before a query, which attends the ``index_topk`` best): the program
against the plain reference (``cake_tpu/testing/reference_glm_dsa.py``) on
seeded weights, tiny sizes, CPU, float32, on LOGITS.

``index_topk`` is 8 here and the test contexts run to 40 and more rows, so
a query past the eighth row really drops rows: most of them.
"""

from __future__ import annotations

import os
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny_glm_dsa
from cake_tpu.ops import dsa
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.testing import reference_glm_dsa as ref
from cake_tpu.utils.weights import latent_hf_tensors

# float32 program against a float32 reference of another summation order
# (absorbed against expanded, gathered rows against a token-by-token loop)
# through three layers: measured 3e-6 on logits of magnitude 3; 1e-4 leaves
# thirty times of room and is a thousandth of what the nearest control
# moves (TIGHT x WIDE)
TIGHT = 1e-4
WIDE = 1000  # every control moves some logit by more than TIGHT x WIDE
TOPK = 8
CFG = tiny_glm_dsa(max_seq_len=128, eos_token_id=-1, dtype="float32")
assert CFG.index_topk == TOPK
TOKENS = np.random.default_rng(61).integers(3, 250, 40).astype(np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)
ROOT = Path(__file__).resolve().parent.parent


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales are not all ones (a norm applied
    twice or not at all shows), the indexer's LayerNorm among them."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        name = path[-1].key
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name == "norm_f":
            return leaf * (1.0 + 0.25 * jax.random.uniform(
                k, leaf.shape, minval=-1.0))
        return leaf

    return jax.tree_util.tree_map_with_path(jitter, params)


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def tensors(params):
    return latent_hf_tensors(params, CFG)


@pytest.fixture(scope="module")
def want(tensors):
    """The reference's logits at every position of TOKENS."""
    return np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS))


@pytest.fixture(params=["sweep", "gather"])
def form(request, monkeypatch):
    """Both forms of a decode step's choice and attention
    (``ops.dsa.attend_form_choice``), by the shape the choice reads: the
    tests' 64- and 128-row buffers lie under ``SWEEP_MAX_ROWS`` as it is
    and over one of a row."""
    if request.param == "gather":
        monkeypatch.setattr(dsa, "SWEEP_MAX_ROWS", 1)
    assert dsa.attend_form_choice(64, TOPK) == request.param
    return request.param


_STEPS: dict = {}  # (LlamaConfig holds a dict: no static argument)


def _STEP(params, tokens, cache, pos, cfg):
    """``llama.forward`` jitted, one function a configuration."""
    # what a trace asks
    key = repr(cfg), os.environ.get("CAKE_PALLAS"), dsa.SWEEP_MAX_ROWS
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda p, t, c, at: llama.forward(p, t, c, at, cfg))
    return _STEPS[key](params, jnp.asarray(tokens), cache, pos)


def _decode_all(params, cfg, tokens, prefill: int, max_seq: int = 64):
    """Logits at positions ``prefill - 1 ..`` through the cache: a prefill
    of ``prefill`` tokens from position 0, then one step a token."""
    cache = init_cache(cfg, batch=1, max_seq=max_seq)
    logits, cache = _STEP(params, jnp.asarray(tokens[None, :prefill]), cache,
                          0, cfg)
    out = [logits[0]]
    for i in range(prefill, len(tokens)):
        logits, cache = _STEP(params, jnp.asarray(tokens[None, i:i + 1]),
                              cache, jnp.asarray([i], jnp.int32), cfg)
        out.append(logits[0])
    return np.stack(out), cache


def _engine(params, prompts, ids=None, cfg=CFG, **kw):
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kw.setdefault("block_size", 4)
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY),
                        max_seq=128, **kw)
    bg.set_prompts(prompts, stream_ids=ids)
    return bg


def _run(bg, events=(), steps=40):
    """Step the engine; ``events``: ``{step: callable(bg)}``. Returns every
    stream's generated ids by stream id."""
    events = dict(events)
    out: dict[int, list[int]] = {}
    for i in range(steps):
        if i in events:
            events[i](bg)
        bg.step()
        for s in bg.streams:
            if s.active and s.stream_id >= 0:
                out[s.stream_id] = list(s.generated)
    return out


def _is_the_references_argmax(tensors, prompt, out, cfg=CFG):
    """Every token of ``out`` is the single-stream reference's own best
    continuation of what came before it, to ``TIGHT``."""
    full = np.array(list(prompt) + list(out))
    logits = np.asarray(ref.logits(cfg.to_hf_dict(), tensors, full))
    for j, tok in enumerate(out):
        at = logits[len(prompt) - 1 + j]
        assert at.max() - at[tok] <= TIGHT, (len(prompt), j)


_RNG = np.random.default_rng(7)
# under, at and several times index_topk
PROMPTS = [[int(t) for t in _RNG.integers(3, 250, n)]
           for n in (5, 37, 8, 21, 60, 12)]
