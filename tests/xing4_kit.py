"""What the files of Xing4's tests share (PR 59 split
``tests/test_xing4.py`` along its section headings into
``test_xing4.py``, ``test_xing4_engine.py`` and ``test_xing4_latent.py``,
so that no one file sets tier-1's wall clock): the tiny configuration,
seeded parameters and the reference's logits (module-scoped fixtures,
built once a part), the step program, and the engine helpers of section
(c), which sections (e) and the latent family's use too. A plain module
the parts import, not a conftest plugin. The family's account:

A residual stream four hidden vectors wide over the latent family
(``model_type`` ``xing4_0``, manifold-constrained hyper-connections): the
program against the plain reference
(``cake_tpu/testing/reference_mhc_mla_moe.py``) on seeded weights, tiny
sizes, CPU, float32.

- ``ops/hyper.py``: the doubly stochastic ``H_res``, the clamp, the forms
  against the reference's ``sum(axis)`` rounds;
- a prompt's logits, then decode through the cache token by token;
- the same through ``BatchGenerator``: block decode, admissions in buckets
  and in bands, streams of different length, a slot reused;
- the tie to the shared code: with coefficients that make stream 0 the
  plain residual the logits are the plain latent model's;
- the configuration (the catalog's keys, the round trip, every refusal),
  the loader (float32 ``hc`` tensors, a written prediction block skipped
  and counted), the engine's gauges, the benchmark's copy of the reference.
"""

from __future__ import annotations

import dataclasses
import os
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny_xing4
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.testing import reference_mhc_mla_moe as ref
from cake_tpu.utils.weights import latent_hf_tensors

TIGHT = 1e-4
# three rounds here (XLA's CPU backend takes 36 s to compile ONE program
# of two stacks at 20 rounds, 2 s at 3; the reference takes the rounds
# from the file too); the 20 published rounds in the tests of ops/hyper.py
CFG = tiny_xing4(max_seq_len=256, eos_token_id=-1, hc_sinkhorn_iters=3)
CFG20 = dataclasses.replace(CFG, hc_sinkhorn_iters=20)
TOKENS = np.random.default_rng(51).integers(3, 250, 48).astype(np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)
ROOT = Path(__file__).resolve().parent.parent


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales are not all ones (a norm applied
    twice or not at all shows) and whose three ``hc`` gains differ from 1
    and from each other (a gain on the wrong columns shows)."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        name = path[-1].key
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name == "norm_f":
            return leaf * (1.0 + 0.25 * jax.random.uniform(
                k, leaf.shape, minval=-1.0))
        if name.endswith("_scale") and name.startswith("hc_"):
            return leaf * jnp.asarray([0.8, 1.25, 1.1], leaf.dtype)
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


_STEPS: dict = {}  # (LlamaConfig holds a dict: no static argument)


def _STEP(params, tokens, cache, pos, cfg):
    """``llama.forward`` jitted, one function a configuration."""
    key = repr(cfg), os.environ.get("CAKE_PALLAS")  # what a trace asks
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda p, t, c, at: llama.forward(p, t, c, at, cfg))
    return _STEPS[key](params, jnp.asarray(tokens), cache, pos)


def _decode_all(params, cfg, tokens, prefill: int, chunk: int | None = None):
    """Logits at positions ``prefill - 1 ..`` through the cache: a prefill
    of ``prefill`` tokens (in chunks of ``chunk``), then one step a token."""
    cache = init_cache(cfg, batch=1, max_seq=64)
    chunk = chunk or prefill
    for lo in range(0, prefill, chunk):
        logits, cache = _STEP(params, jnp.asarray(tokens[None, lo:lo + chunk]),
                              cache, lo, cfg)
    out = [logits[0]]
    for i in range(prefill, len(tokens)):
        logits, cache = _STEP(params, jnp.asarray(tokens[None, i:i + 1]),
                              cache, i, cfg)
        out.append(logits[0])
    return np.stack(out), cache


# -- the engine's helpers (section (c)'s; (e) and the latent section use them) ----

def _engine(params, prompts, ids=None, cfg=CFG, **kw):
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kw.setdefault("block_size", 4)
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY),
                        max_seq=256, **kw)
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
PROMPTS = [[int(t) for t in _RNG.integers(3, 250, n)]
           for n in (5, 37, 70, 21, 100, 12)]
