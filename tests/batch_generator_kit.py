"""What the three files of the engine's own tests share (PR 59 split
``tests/test_batch_generator.py`` into ``test_batch_generator.py``,
``test_batch_generator_spec.py`` and ``test_batch_generator_blocks.py``,
so that no one file sets tier-1's wall clock): the tiny configuration,
its parameters (a module-scoped fixture, built once a part), and the two
runners every case compares. A plain module the parts import, not a
conftest plugin.
"""

import jax
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.runtime.generator import LlamaGenerator

CFG = tiny(max_seq_len=64)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)
PROMPTS = [[5, 9, 2, 11], [3, 1, 4, 1, 5, 9], [7, 7, 2]]


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(5))


def _single_stream(params, prompt, n, settings):
    g = LlamaGenerator(CFG, params, settings=settings)
    g.set_prompt(prompt)
    out = []
    for i in range(n):
        t = g.next_token(i)
        out.append(t.id)
        if t.is_end_of_stream:
            break
    return out


def _batch_run(params, prompts, n, settings, stream_ids=None, **kw):
    g = BatchGenerator(CFG, params, settings=settings, **kw)
    g.set_prompts(prompts, stream_ids=stream_ids)
    return g.generate(n)
