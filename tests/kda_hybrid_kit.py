"""What the two files of the delta-rule hybrid's tests share (PR 59 split
``tests/test_kda_hybrid.py`` along its section headings: its engine
section is ``tests/test_kda_hybrid_engine.py``, so that no one file sets
tier-1's wall clock): the tiny configuration, seeded parameters and the
reference's logits (module-scoped fixtures, built once a part), the
decode helpers, and the engine builder, which the loader's section uses
too. A plain module the parts import, not a conftest plugin. The
family's account and tolerances:

The delta-rule + latent-attention hybrid (Ling-3.0's keys: KDA layers
that hold a recurrent state beside one latent (MLA) layer in
``layer_group_size``, a direct query projection, a head-wise gate, bias-
corrected sigmoid group routing over a told share of the experts beside a
shared one) against the plain reference
``cake_tpu/testing/reference_kda_mla_moe.py``, on seeded random weights at
tiny widths that keep the published family's ratios
(``models.config.tiny_kda_hybrid``: K K M K, one leading dense layer).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls
are full precision. Program and reference differ in the order of sums
(the chunked WY form against the token-by-token recurrence, absorbed
against expanded attention, one einsum against a loop of experts); a KDA
layer then passes its outputs through two normalisations (L2 on q and k,
RMS on o; a head's output is tiny while its state is young, and the RMS
norm multiplies the rounding of a tiny vector): measured 2e-5 to 8e-5 on
logits of magnitude ~4 through four layers over 24 tokens, and 3.2e-4 at
one logit of 38,400 over 150 tokens. ``TIGHT`` is 1e-3, three times the
worst, and thirty times under what computing in bfloat16 costs (checked
below), so a lowered precision fails. The recurrence itself is held to
1e-5 (``test_kda_chunk_is_the_recurrence``).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny_kda_hybrid
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.testing import reference_kda_mla_moe as ref
from cake_tpu.utils.weights import latent_hf_tensors

TIGHT = 1e-3
CFG = tiny_kda_hybrid(max_seq_len=256, eos_token_id=-1)
TOKENS = np.array([3, 5, 7, 9, 11, 200, 100, 50, 25, 12, 6, 1, 99, 42, 17, 8,
                   33, 64, 128, 255, 2, 4, 77, 31], np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales are not all ones, whose decay
    rates differ by head and whose routing bias is large enough to change
    choices: what is applied twice, not at all or to the wrong thing
    shows."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        name = path[-1].key
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name == "norm_f":
            return leaf * (1.0 + 0.25 * jax.random.uniform(
                k, leaf.shape, minval=-1.0))
        if name == "b_router":
            return 0.3 * jax.random.normal(k, leaf.shape)
        if name in ("a_log", "dt_bias"):
            return jax.random.normal(k, leaf.shape)
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


def _decode_all(params, cfg, tokens, prefill: int, chunk: int | None = None):
    """Logits at positions ``prefill - 1 ..`` through the cache: a prefill
    of ``prefill`` tokens (in chunks of ``chunk``), then one step a token."""
    cache = init_cache(cfg, batch=1, max_seq=64)
    step = jax.jit(lambda p, t, c, pos: llama.forward(p, t, c, pos, cfg))
    chunk = chunk or prefill
    for lo in range(0, prefill, chunk):
        logits, cache = step(params, jnp.asarray(tokens[None, lo:lo + chunk]),
                             cache, lo)
    out = [logits[0]]
    for i in range(prefill, len(tokens)):
        logits, cache = step(params, jnp.asarray(tokens[None, i:i + 1]),
                             cache, i)
        out.append(logits[0])
    return np.stack(out), cache


def _all_logits(params, cfg, tokens, max_seq=256, valid=None):
    """Logits at every position of one prefill, and the cache it leaves."""
    cos, sin = rope_tables_for(cfg, max_seq)
    x = llama.embed_tokens(params, jnp.asarray(tokens)[None], cfg)
    x, cache = llama.forward_layers(
        params["layers"], x, init_cache(cfg, 1, max_seq), cos, sin, 0, cfg,
        valid=valid)
    x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return np.asarray(x[0] @ params["lm_head"]), cache


# -- the engine builder (the engine section's; the loader's section uses it) ----

def _engine(params, prompts, ids=None, cfg=CFG, **kw):
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kw.setdefault("block_size", 4)
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY),
                        max_seq=64, **kw)
    bg.set_prompts(prompts, stream_ids=ids)
    return bg
