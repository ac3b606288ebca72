"""What the latent family has beyond the slot layout, under Xing4's wide
residual stream (a section of ``tests/test_xing4.py``, in a file of its
own since PR 59): the single-stream generators, speculation in the
engine, the ``ep`` axis, int8 linears beside float32 ``hc`` tensors.
Shared: ``tests/xing4_kit.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models import llama
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.testing import reference_mhc_mla_moe as ref
from cake_tpu.utils.weights import latent_hf_tensors

from xing4_kit import (  # noqa: F401
    CFG, GREEDY, PROMPTS, TIGHT, TOKENS, _decode_all, _engine,
    _is_the_references_argmax, params, tensors, want,
)


# -- what the latent family has beyond the slot layout, under the wide stream ------

def test_the_single_stream_generators_match_reference(params, tensors):
    """``LlamaGenerator`` (bucketed prefill, block decode) and
    ``SpeculativeGenerator`` (n-gram proposals verified in one forward
    pass, ``head_norm`` over every position's streams) give the
    reference's argmax."""
    from cake_tpu.runtime.generator import LlamaGenerator
    from cake_tpu.runtime.speculative import SpeculativeGenerator

    prompt = PROMPTS[3]
    for make in (
            lambda: LlamaGenerator(CFG, params, settings=SamplerSettings(
                **GREEDY), max_seq=256, block_size=4),
            lambda: SpeculativeGenerator(CFG, params, settings=SamplerSettings(
                **GREEDY), max_seq=256, spec_k=3)):
        gen = make()
        gen.set_prompt(prompt)
        out = [gen.next_token(i).id for i in range(12)]
        _is_the_references_argmax(tensors, prompt, out)


def test_speculation_in_the_engine_matches_reference(params, tensors):
    """``spec_k`` in the engine: the per-row verify program takes the
    streams' sum at every fed position."""
    bg = _engine(params, [PROMPTS[0], PROMPTS[3]], spec_k=2)
    outs = bg.generate(10)
    for prompt, out in zip((PROMPTS[0], PROMPTS[3]), outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:10])


def test_ep_axis_splits_the_held_experts(params):
    """Under a real ep axis the expert block's psum sits inside the
    sub-layer the mixes wrap: the mesh stream is the single-device one."""
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1, 5]]
    outs = []
    for ep in (1, 2):
        bg = _engine(params, prompts, block_size=2, ep=ep)
        outs.append(bg.generate(6))
    assert outs[0] == outs[1]


def test_int8_linears_leave_the_hc_tensors_float32(params):
    """``--quantize int8`` at the small size: every latent linear through
    ``quant.dense``, the ``hc`` tensors as they are (float32, never
    quantised), against the reference over the explicitly dequantized
    weights."""
    from cake_tpu.ops.quant import (QuantizedLinear, dequantize_linear,
                                    quantize_params)

    q = quantize_params(params, bits=8)
    stack = q["layers"]["moe"]
    assert isinstance(stack["wkv_b"], QuantizedLinear)
    for name in llama.HC_TENSORS:
        assert stack[name].dtype == jnp.float32, name
        np.testing.assert_array_equal(stack[name],
                                      params["layers"]["moe"][name])
    deq = jax.tree.map(
        lambda a: dequantize_linear(a, jnp.float32)
        if isinstance(a, QuantizedLinear) else a, q,
        is_leaf=lambda a: isinstance(a, QuantizedLinear))
    got, _ = _decode_all(q, CFG, TOKENS[:12], prefill=8)
    want = np.asarray(ref.logits(CFG.to_hf_dict(),
                                 latent_hf_tensors(deq, CFG), TOKENS[:12]))
    np.testing.assert_allclose(got, want[7:], atol=TIGHT, rtol=0)
