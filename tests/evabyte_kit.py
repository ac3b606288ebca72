"""What the two files of EvaByte's tests share (``tests/test_evabyte.py``,
``tests/test_evabyte_engine.py``: two files so that the driver's workers
share them): the tiny configuration, seeded parameters and the reference's
logits (module-scoped fixtures, built once a file), the step program and
the engine helpers. A plain module the parts import, not a conftest plugin.
The family's account:

EVA attention (``model_type`` ``evabyte``: an exact window that RESETS
every ``window_size`` positions, one learned summary row for every
``chunk_size`` positions of the windows completed before it, one softmax
over both; a cache with no row a position; norm weights stored as ``w -
1``; a head of ``num_pred_heads`` blocks of which block 0 is served): the
program against the plain reference
(``cake_tpu/testing/reference_evabyte.py``) on seeded weights, tiny sizes
(a window of 32 in chunks of 4), CPU, float32, on LOGITS.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny_evabyte
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.testing import reference_evabyte as ref
from cake_tpu.utils.weights import latent_hf_tensors

from glm_dsa_kit import _params

# float32 program against a float32 reference of another summation order
# (two buffers merged by their statistics against one softmax over a
# concatenation, a summary made from a ring's rows against one made from
# the whole sequence, a norm weight with its one folded in against ``1 +
# w`` applied) through three layers: measured 6.4e-6 on logits of magnitude
# 4; 1e-4 leaves fifteen times of room and is a hundredth of what the
# nearest control moves (TIGHT x WIDE)
TIGHT = 1e-4
WIDE = 100  # every control moves some logit by more than TIGHT x WIDE
CFG = tiny_evabyte(max_seq_len=128, eos_token_id=-1, dtype="float32")
# 90 tokens: three windows of 32 less a few, so that a prompt of 27 ends
# mid-chunk and mid-window and the answer behind it crosses two window
# resets (positions 32 and 64) and fifteen chunk ends
TOKENS = np.random.default_rng(66).integers(3, 250, 90).astype(np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


@pytest.fixture(scope="module")
def tensors(params):
    return latent_hf_tensors(params, CFG)


@pytest.fixture(scope="module")
def want(tensors):
    """The reference's logits at every position of TOKENS."""
    return np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS))


_STEPS: dict = {}  # (LlamaConfig holds a dict: no static argument)


def _STEP(params, tokens, cache, pos, cfg=CFG):
    """``llama.forward`` jitted, one function a configuration."""
    key = repr(cfg)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda p, t, c, at: llama.forward(p, t, c, at, cfg))
    return _STEPS[key](params, jnp.asarray(tokens), cache, pos)


def _decode_all(params, cfg, tokens, prefill: int, max_seq: int = 128):
    """Logits at positions ``prefill - 1 ..`` through the cache: a prefill
    of ``prefill`` tokens from position 0, then one step a token."""
    cache = init_cache(cfg, batch=1, max_seq=max_seq)
    logits, cache = _STEP(params, tokens[None, :prefill], cache, 0, cfg)
    out = [logits[0]]
    for i in range(prefill, len(tokens)):
        logits, cache = _STEP(params, tokens[None, i:i + 1], cache,
                              jnp.asarray([i], jnp.int32), cfg)
        out.append(logits[0])
    return np.stack(out), cache


def _admit(params, tokens, true: int, cfg=CFG, max_seq: int = 128):
    """The cache a bucketed admission leaves: ``tokens`` (a whole bucket,
    ``true`` of them the prompt's) through the layer loop from position 0,
    told the true length as the engine tells it."""
    from cake_tpu.ops.rope import rope_tables_for

    cos, sin = rope_tables_for(cfg, max_seq)
    cache = init_cache(cfg, batch=1, max_seq=max_seq)
    x = llama.embed_tokens(params, jnp.asarray(tokens)[None], cfg)
    return llama.forward_layers(
        params["layers"], x, cache, cos, sin, 0, cfg,
        valid=jnp.asarray([true], jnp.int32))[1]


def _steps(params, cfg, tokens, cache, first: int):
    """Logits of one step a token from position ``first`` on."""
    out = []
    for i in range(first, len(tokens)):
        logits, cache = _STEP(params, tokens[None, i:i + 1], cache,
                              jnp.asarray([i], jnp.int32), cfg)
        out.append(np.asarray(logits[0]))
    return np.stack(out)


def _engine(params, prompts, ids=None, cfg=CFG, **kw):
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kw.setdefault("block_size", 4)
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY),
                        max_seq=128, **kw)
    bg.set_prompts(prompts, stream_ids=ids)
    return bg


def _is_the_references_argmax(tensors, prompt, out, cfg=CFG):
    """Every token of ``out`` is the single-stream reference's own best
    continuation of what came before it, to ``TIGHT``."""
    full = np.array(list(prompt) + list(out))
    logits = np.asarray(ref.logits(cfg.to_hf_dict(), tensors, full))
    for j, tok in enumerate(out):
        at = logits[len(prompt) - 1 + j]
        assert at.max() - at[tok] <= TIGHT, (len(prompt), j)


_RNG = np.random.default_rng(7)
# 5 (one bucket of 16, no chunk complete but one), 37 (past a window, padded
# to 64), 30 (the answer meets the first reset two tokens in), 61 (two
# tokens short of a second window: the padding fills a chunk of it), 12
PROMPTS = [[int(t) for t in _RNG.integers(3, 250, n)]
           for n in (5, 37, 30, 61, 12)]
