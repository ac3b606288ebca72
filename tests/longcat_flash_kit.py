"""What the two files of LongCat-Flash's tests share
(``tests/test_longcat_flash.py``, ``tests/test_longcat_flash_engine.py``:
two files so that the driver's workers share them): the tiny configuration,
seeded parameters and the reference's logits (module-scoped fixtures, built
once a file), the step program and the engine helpers. A plain module the
parts import, not a conftest plugin. The family's account:

A shortcut-connected double layer (``model_type`` ``longcat_flash``: two
latent attentions and two dense feed-forwards a layer around ONE expert
block whose result lands a sub-layer late, a router that scores
zero-compute identities behind its experts by softmax over all of them,
two cache planes a layer, the two ``mla_scale_*`` factors folded into norm
weights on load): the program against the plain reference
(``cake_tpu/testing/reference_longcat_flash.py``) on seeded weights, tiny
sizes, CPU, float32, on LOGITS.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny_longcat_flash
from cake_tpu.ops import mla
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.testing import reference_longcat_flash as ref
from cake_tpu.utils.weights import latent_hf_tensors

from glm_dsa_kit import _params

# float32 program against a float32 reference of another summation order
# (absorbed against expanded attention, sorted pairs against a loop over
# the experts, a norm weight with a factor folded in against the factor
# applied to the norm's output) through three double layers: measured 5e-6
# on logits of magnitude 4; 1e-4 leaves twenty times of room and is a
# hundredth of what the nearest control moves (TIGHT x WIDE)
TIGHT = 1e-4
WIDE = 100  # every control moves some logit by more than TIGHT x WIDE
CFG = tiny_longcat_flash(max_seq_len=128, eos_token_id=-1, dtype="float32")
TOKENS = np.random.default_rng(64).integers(3, 250, 40).astype(np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)
ROOT = Path(__file__).resolve().parent.parent


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
    """``llama.forward`` jitted, one function a configuration and a floor
    of the blocked admission."""
    key = (repr(cfg), os.environ.get("CAKE_PALLAS"),
           mla.LATENT_ADMIT_BLOCK_MIN_T)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda p, t, c, at: llama.forward(p, t, c, at, cfg))
    return _STEPS[key](params, jnp.asarray(tokens), cache, pos)


def _decode_all(params, cfg, tokens, prefill: int, max_seq: int = 64):
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
PROMPTS = [[int(t) for t in _RNG.integers(3, 250, n)]
           for n in (5, 37, 8, 21, 60, 12)]
