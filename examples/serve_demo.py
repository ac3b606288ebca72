"""Runnable serving demo — no checkpoint required (tiny random weights).

Shows the serving plane the reference has no equivalent of
(SURVEY.md §0: strictly single-request): N concurrent streams over one
model instance, continuous admission of arrivals mid-run, the adaptive
decode-block ladder, and the next block enqueued before a block's rows are
handed out. Runs on CPU in a few seconds:

    python examples/serve_demo.py

Swap ``tiny()`` + ``init_params`` for ``LlamaConfig.from_hf_json`` + the
checkpoint loaders (see README "Multi-stream serving") to serve a real
model the same way; every call below is the production API.
"""

import jax

from cake_tpu.models.config import tiny
from cake_tpu.models.llama import init_params
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator


def main() -> None:
    cfg = tiny(max_seq_len=128, eos_token_id=-1)
    params = init_params(cfg, jax.random.PRNGKey(0))

    gen = BatchGenerator(
        cfg, params,
        settings=SamplerSettings(temperature=0.8, top_k=40, seed=7),
        block_size=2,        # fused decode steps per dispatch (base)
        block_size_max=8,    # ...doubling while no arrival waits
        admit_chunk=32,      # admission prefill chunk per step
    )

    # four concurrent prompts (token ids; pass strings with a tokenizer)
    gen.set_prompts([[5, 9, 2, 11], [3, 1, 4, 1, 5], [7, 7, 2],
                     [2, 8, 1, 6]])
    for _ in range(10):
        gen.step()

    # continuous batching: retire a stream, admit an arrival in its slot —
    # the running batch never stalls behind the new prompt's prefill
    gen.finish(stream_id=0)
    gen.enqueue([4, 4, 2, 9, 1, 3], stream_id=99)
    for _ in range(14):
        gen.step()
    gen.drain()  # record what the in-flight block already computed

    for s in gen.streams:
        print(f"stream {s.stream_id}: prompt {s.prompt} -> "
              f"{len(s.generated)} tokens {s.generated[:10]}...")
    st = gen.stats()
    recorded = sum(len(s.generated) for s in gen.streams)
    print(f"\n{recorded} tokens recorded ({st['tokens_emitted']} of them "
          f"handed out by step()) in {st['decode_dispatches']} "
          f"decode + {st['admit_dispatches']} admission dispatches "
          f"({st['tokens_per_dispatch']} tokens/dispatch, "
          f"busy {st['busy_s']}s of {st['wall_s']}s wall)")


if __name__ == "__main__":
    main()
