"""The cli's single-stream path (``runtime/generator.py`` ``prefill_fn``)
over every family whose cache holds more than rows: a prompt is prefilled
in its bucket, and the bucket's padding may neither advance a recurrent
state, shift a convolution's tail nor enter a ring, and a second prompt
starts from what an empty cache holds. The oracle is the serving engine on
the same weights (held to each family's reference in that family's own
tests): greedy ids, float32, token for token. The scalar-gated delta rule's
case is ``tests/test_qwen3_next_engine.py``'s, against its reference.
"""

from __future__ import annotations

import jax
import pytest

from cake_tpu.models.config import (tiny_exaone_moe, tiny_jamba,
                                    tiny_kda_hybrid, tiny_lfm2_moe,
                                    tiny_mellum)
from cake_tpu.models.llama import init_params
from cake_tpu.ops.sampling import SamplerSettings

GREEDY = dict(temperature=0.0, repeat_penalty=1.0)
# 7 and 11 tokens in a 16-row bucket: 9 and 5 rows of padding
PROMPTS = ([5, 17, 42, 99, 7, 3, 88], [9, 8, 7, 6, 5, 4, 3, 2, 11, 12, 13])
NEW = 5
PRESETS = [tiny_kda_hybrid, tiny_jamba, tiny_lfm2_moe, tiny_exaone_moe,
           tiny_mellum]


def _model(preset):
    cfg = preset(max_seq_len=64, eos_token_id=-1)
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


@pytest.mark.parametrize("preset", PRESETS, ids=lambda p: p.__name__)
def test_single_stream_ids_are_the_engines_under_padding(preset):
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.runtime.generator import LlamaGenerator

    cfg, params = _model(preset)
    settings = SamplerSettings(**GREEDY)
    bg = BatchGenerator(cfg, params, settings=settings, max_seq=64,
                        block_size=NEW)
    bg.set_prompts([list(p) for p in PROMPTS])
    for _ in range(3):
        bg.step()
    want = [list(s.generated)[:NEW] for s in bg.streams[:len(PROMPTS)]]
    gen = LlamaGenerator(cfg, params, tokenizer=None, settings=settings,
                         max_seq=64)
    for prompt, ids in zip(PROMPTS, want):  # the second after the first
        gen.set_prompt(list(prompt))
        assert [gen.next_token(i).id for i in range(NEW)] == ids
        assert len(ids) == NEW


@pytest.mark.parametrize("preset", PRESETS, ids=lambda p: p.__name__)
def test_single_stream_speculation_is_refused_beside_rows(preset):
    from cake_tpu.runtime.speculative import SpeculativeGenerator

    cfg, params = _model(preset)
    with pytest.raises(ValueError, match="speculation is not wired"):
        SpeculativeGenerator(cfg, params, tokenizer=None,
                             settings=SamplerSettings(**GREEDY), max_seq=64,
                             spec_k=2)
