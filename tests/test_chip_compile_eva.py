"""The chip's compiler on EvaByte's two serving programs at the cell's
sizes (``tests/test_chip_compile.py`` says what these compiles are; a file
of its own so that no one file sets tier-1's wall clock): EVA attention's
ring and summary plane carried through the layer scan, the step's kernel
over both, the 16,384-row admission a window at a time. Shared:
``tests/chip_compile_kit.py``.
"""

import re

from chip_compile_kit import (  # noqa: F401
    GIB, _cache_sized_moves, _donated_bytes, _family_programs, as_on_chip,
    no_compile_cache, topo,
)

LAYERS, SLOTS, CAPACITY = 8, 16, 16384


def _config():
    from cake_tpu.models.config import evabyte_6p5b

    config = evabyte_6p5b(num_hidden_layers=LAYERS, max_seq_len=CAPACITY)
    assert config.cache_plan == {"ring": (8, 32, 2048, 128, 128),
                                 "summary": (8, 32, 16, 128, 128)}
    return config


def _buffers(batch: int) -> tuple[str, str]:
    """The carried ring and summary plane of ``batch`` streams, as the
    compiled text spells their shapes."""
    return (f"bf16[{LAYERS},{batch},32,2048,128]",
            f"bf16[{LAYERS},{batch},32,{CAPACITY // 16},128]")


def test_the_cells_decode_block_fits_and_reads_both_buffers_in_place(
        topo, as_on_chip):
    """``evabyte-6p5b-cut.agent-long``'s block decode as the cell serves
    it: published widths, 8 layers, 16 slots x 16,384 positions. The
    chip's compiler takes the 8-step block; the rings and the summary
    planes are carried through the ONE scanned segment and written in
    place (nothing of either buffer's shape is allocated or copied: not
    by the row's write, not by the chunk's gather, not by the summary's
    write); the step's attention is the kernel ``eva_decode``, once in
    the scan's body, on the carried buffers; weights, 4 GiB of rings and
    2 GiB of summaries fit 15.75 GiB with the temporaries."""
    config = _config()
    (decode,) = _family_programs(topo, config, SLOTS, CAPACITY)
    for shape in _buffers(SLOTS):
        assert _cache_sized_moves(decode, shape) == [], shape
    text = decode.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call".*'
                          r'eva_decode', text)) == 1
    args, temps = _donated_bytes(decode)
    cache = SLOTS * config.stream_bytes(CAPACITY)
    print("decode args", args / GIB, "temps", temps / GIB, "cache",
          cache / GIB)
    assert cache == 6 * GIB
    # 3.04 GiB of weights (8 layers, embedding, head 0) + the 6 GiB cache
    assert 9.0 * GIB < args < 9.2 * GIB, args / GIB
    assert args + temps < 15.75 * GIB


def test_the_16384_row_admission_fits_beside_the_live_cache(
        topo, as_on_chip):
    """The longest bucket's admission into the batch-1 staging cache: its
    eight windows are eight calls of the flash prefill kernel in the
    scan's body (``eva_prefill``: each over the summaries before it and
    its own keys; no ``[1, 32, 16384, ..]`` score array is built), the
    staging row's buffers are written in place, and weights, the staging
    row and the temporaries fit beside the live cache."""
    config = _config()
    _, admit = _family_programs(topo, config, 1, CAPACITY, CAPACITY)
    for shape in _buffers(1):
        assert _cache_sized_moves(admit, shape) == [], shape
    text = admit.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call".*'
                          r'eva_prefill', text)) == 8
    assert not re.search(r"f32\[1,32,16384,\d{4,}\]", text)
    args, temps = _donated_bytes(admit)
    cache = SLOTS * config.stream_bytes(CAPACITY)
    print("admit args", args / GIB, "temps", temps / GIB)
    assert args + temps + cache < 15.75 * GIB - 0.5 * GIB, (
        (args + temps + cache) / GIB)


def test_the_servers_first_batch_prefill_moves_no_plane(topo, as_on_chip):
    """The program a server starts with: one 16-row bucket a slot, all 16
    slots at once, into the LIVE cache. A bucket's one summary row is
    taken into the layer's slab by a select, not written as a row (as a
    ``dynamic_update_slice`` it gave both planes a rows-outermost layout
    for the whole program: two copies in ENTRY, 3.3 GiB of temporaries,
    a peak of 15.0 of 15.75 GiB on the chip): no buffer of the ring's or
    the plane's shape is allocated or copied."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from cake_tpu.models.llama import init_params
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.parallel.mesh import MeshPlan, cache_specs, param_specs
    from cake_tpu.parallel.pipeline import build_sharded_prefill

    config = _config()
    plan = MeshPlan.build(config, devices=topo.devices[:1])

    def placed(shapes, specs):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(plan.mesh, spec)),
            shapes, specs)

    params = jax.eval_shape(lambda k: init_params(config, k),
                            jax.random.PRNGKey(0))
    params = placed(params, param_specs(params))
    cache = placed(
        jax.eval_shape(lambda: init_cache(config, batch=SLOTS,
                                          max_seq=CAPACITY)),
        cache_specs(None, held=config.cache_plan))
    rep = NamedSharding(plan.mesh, PartitionSpec())
    prefill = build_sharded_prefill(config, plan, params_like=params).lower(
        params, jax.ShapeDtypeStruct((SLOTS, 16), jnp.int32, sharding=rep),
        cache, jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=rep)
    ).compile()
    for shape in _buffers(SLOTS):
        assert _cache_sized_moves(prefill, shape) == [], shape
    args, temps = _donated_bytes(prefill)
    print("batch prefill args", args / GIB, "temps", temps / GIB)
    assert args + temps < 11.0 * GIB
