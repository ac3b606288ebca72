"""What the files of AOT compiles for a described v5e share (PR 59 split
``tests/test_chip_compile.py`` into ``test_chip_compile.py`` (kernels,
and the families' lowered text), ``test_chip_compile_engine.py``,
``test_chip_compile_latent.py`` and ``test_chip_compile_state.py``, so
that no one file sets tier-1's wall clock): the topology fixture (inside
a fixture, never at import: only the worker that runs a file loads the
TPU's library), the compile cache switched off around these compiles,
Mistral-7B's widths, the engine's programs at real shapes, and the
readers of a compiled program's text. A plain module the parts import,
not a conftest plugin. What these tests are:

The chip's compiler on the main path's kernels, at real widths.

Interpret mode (every other test here) accepts programs Mosaic refuses:
an unaligned slice, a primitive with no TPU lowering, more VMEM than a
kernel may use. The TPU compiler is installed in the sandbox and compiles
for a v5e that is *described*, not attached
(``/opt/skills/guides/on-chip-measurement`` section 2.3), so each case
here is one AOT ``lower().compile()`` of a kernel the serving path
dispatches, at Mistral-7B widths (32 q / 8 kv heads of 128, hidden 4096,
FFN 14336, vocab 32000). Nothing runs: these prove "the chip's compiler
accepts it", never a result or a time.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.ops import pallas as pk


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """An executable compiled for a described chip is written to the
    persistent cache but cannot be read back without a chip, so the cache
    that tests/conftest.py turns on is off while a file of these tests
    runs. JAX asks the switch once a process; ``reset_cache`` makes it ask
    again."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e topology description: {e}")


H, KVH, D = 32, 8, 128
HID, FFN, VOCAB = 4096, 14336, 32000
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


def _engine_shapes(topo, layers: int, batch: int, sparse: bool = False):
    """Config, one-device plan and the placed shapes of parameters and a
    ``batch``-row cache for ``layers`` layers at Mistral-7B widths, int8
    weights, a 2048 window (chip_smoke.py's and the dense cell's sizes),
    from ``jax.eval_shape``: nothing is allocated. ``sparse``: Mixtral
    8x7B's widths and the sparse cell's 4096 rows instead."""
    from jax.sharding import NamedSharding

    from cake_tpu.models.config import mistral_7b, mixtral_8x7b
    from cake_tpu.models.llama import init_params_int8
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.parallel.mesh import MeshPlan, cache_specs, param_specs

    if sparse:
        config = mixtral_8x7b(max_seq_len=SPARSE_WINDOW,
                              num_hidden_layers=layers)
    else:
        config = mistral_7b(max_seq_len=WINDOW, num_hidden_layers=layers)
    plan = MeshPlan.build(config, devices=topo.devices[:1])

    def placed(shapes, specs):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(plan.mesh, spec)),
            shapes, specs)

    params = jax.eval_shape(lambda k: init_params_int8(config, k),
                            jax.random.PRNGKey(0))
    params = placed(params, param_specs(params))
    cache = placed(
        jax.eval_shape(lambda: init_cache(config, batch=batch)),
        cache_specs(None, batch_replicated=batch == 1))
    rep = NamedSharding(plan.mesh, jax.sharding.PartitionSpec())

    def arg(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    return config, plan, params, cache, arg


SLOTS, WINDOW, SPARSE_WINDOW = 8, 2048, 4096


def _block_decode(topo, layers: int, sparse: bool = False):
    """The engine's fused 8-step per-row block decode -- BatchGenerator's
    ``build_sharded_decode(steps=8, per_row=True)`` -- over 8 slots,
    compiled for one described v5e."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.pipeline import build_sharded_decode

    config, plan, params, cache, arg = _engine_shapes(topo, layers, SLOTS,
                                                      sparse)
    settings = SamplerSettings(temperature=0.0)
    prog = build_sharded_decode(config, settings, plan, params_like=params,
                                steps=8, per_row=True)
    return prog.lower(
        params, arg((SLOTS,)), cache, arg((SLOTS,)),
        arg((SLOTS, 2), jnp.uint32),
        arg((SLOTS, settings.repeat_last_n)), arg((SLOTS,)), arg((SLOTS,)),
    ).compile()


def _admit_prefill(topo, layers: int, bucket: int, sparse: bool = False):
    """The engine's admission program -- ``build_admit_prefill`` -- one
    ``bucket``-token chunk into the batch-1 staging cache."""
    from cake_tpu.parallel.pipeline import build_admit_prefill

    config, plan, params, cache, arg = _engine_shapes(topo, layers, 1,
                                                      sparse)
    prog = build_admit_prefill(config, plan, params_like=params)
    return prog.lower(params, arg((1, bucket)), cache, arg(()),
                      arg((1,))).compile()


def _instructions(compiled):
    """``(computation, name, shape, op, line)`` of every instruction of
    the compiled program's text; ``shape`` without layout, ``bf16[2,8]``."""
    import re

    comp = ""
    for line in compiled.as_text().splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        inst = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+\[[\d,]*\])\S* ([\w\-]+)\(",
            line)
        if inst:
            yield comp, inst.group(1), inst.group(2), inst.group(3), line


def _cache_sized_moves(compiled, stacked: str) -> list[str]:
    """What the program does with a value of the stacked cache's shape
    besides updating it in place: every ``AllocateBuffer`` of that shape
    and every ``copy`` (or asynchronous ``copy-start``) that produces
    one, in whatever computation."""
    return [f"{comp}: {name} ({op})"
            for comp, name, shape, op, line in _instructions(compiled)
            if shape == stacked and (
                op in ("copy", "copy-start")
                or (op == "custom-call" and "AllocateBuffer" in line))]


def _slabs_written(compiled, slabs: tuple[str, ...]) -> list[str]:
    """Instructions that leave one layer's whole keys or values behind as
    a value of their own (not inside a fusion, where a slice of the
    carried cache is just how the consumer addresses it)."""
    return [f"{comp}: {name} ({op})"
            for comp, name, shape, op, _ in _instructions(compiled)
            if shape in slabs and not comp.startswith("fused_computation")
            and op not in ("parameter", "get-tuple-element", "bitcast",
                           "tuple")]


def _expert_stack_moves(compiled, dtype: str, experts: int, k: int,
                        n: int) -> list[str]:
    """Instructions that leave one layer's expert stack behind as a value
    of its own: of the shape ``[1, experts, k, n]``, its transpose, or
    either without the leading 1, anywhere but inside a fusion (where a
    slice of the stacked weights is how the consumer addresses them) and
    other than parameters and bitcasts. What a conditional in the layer
    body cost (PR 28), what the dense form's batched product over a
    scanned int8 stack cost (6.7 ms a layer, my chip run, PR 33), and
    what a kernel on a scan's slice would cost."""
    slabs = {f"{dtype}[{lead}{experts},{a},{b}]"
             for lead in ("1,", "") for a, b in ((k, n), (n, k))}
    return [f"{comp}: {name} ({op}) {shape}"
            for comp, name, shape, op, _ in _instructions(compiled)
            if shape in slabs and not comp.startswith("fused_computation")
            and op not in ("parameter", "get-tuple-element", "bitcast",
                           "tuple")]


def _projection_moves(compiled, dtype: str, k: int, n: int) -> list[str]:
    """Instructions that move a projection's weight before its product
    reads it: outside a fusion, a result of the shape ``[k, n]``, ``[n,
    k]`` or either under a leading axis (a stack's depth, or 1), that is
    a ``copy`` (a re-laying), a ``fusion`` (a slice written out) or an
    asynchronous ``copy-start`` INTO ANOTHER LAYOUT. An asynchronous copy
    that keeps its operand's order of axes is a prefetch into fast memory,
    the read the product needs started early, and no move; parameters,
    tuples and bitcasts move nothing. What the q and k projections cost
    while the compiler fused each product with the per-head operation
    behind it (PR 41): 100 MB read and written a layer and step, then
    transposed."""
    shape = rf"{dtype}\[(?:\d+,)?(?:{k},{n}|{n},{k})\]"
    moves = [f"{comp}: {name} ({op}) {got}"
             for comp, name, got, op, _ in _instructions(compiled)
             if op in ("copy", "fusion") and re.fullmatch(shape, got)
             and not comp.startswith("fused_computation")]
    # an asynchronous copy's result is (destination, source, context)
    prefetch = re.compile(rf"\s*%?([\w.\-]+) = \(({shape})\{{([\d,]*)\S* "
                          rf"{shape}\{{([\d,]*)\S* .*\) copy-start\(")
    for line in compiled.as_text().splitlines():
        m = prefetch.match(line)
        if m and m.group(3) != m.group(4):
            moves.append(f"{m.group(1)} (copy-start) {m.group(2)}")
    return moves


def _moe_calls(compiled, name: str) -> int:
    """The kernel calls whose own name (the result's, left of ``=``)
    holds ``name``: a call's operands carry other kernels' names."""
    return sum("custom-call(" in line and "tpu_custom_call" in line
               and name in line.split("=")[0]
               for line in compiled.as_text().splitlines())


def _grouped_matmul_calls(compiled) -> int:
    """The expert block's grouped products in the program's text: two a
    sorted call (gate, up and the SwiGLU one, ``moe_grouped_swiglu``; down
    the other, ``moe_grouped_matmul``)."""
    return _moe_calls(compiled, "moe_grouped_")


def _live_tile_calls(compiled) -> int:
    """The kernels that gather the live row tiles' rows and sum their
    results (``moe_gather_rows``, ``moe_combine_rows``): two a sorted call
    where the stacks hold a share of the scored experts, none where every
    one is held (``ops.moe.compacts``)."""
    return (_moe_calls(compiled, "moe_gather_rows")
            + _moe_calls(compiled, "moe_combine_rows"))


def _decode_kernel_calls(compiled) -> list[str]:
    """The computations that hold the decode kernel's custom call, by its
    ``op_name``: how deep in the program's loops it sits."""
    import re

    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for _, _, _, op, line in _instructions(compiled)
            if op == "custom-call" and "tpu_custom_call" in line
            and "flash_decode" in line]


def _latent_kernel_held(compiled, slots: int, window: int, heads: int,
                        calls: int) -> None:
    """The block-decode program of a latent cell holds ``calls`` calls of
    the latent decode kernel (one a scanned stretch of latent layers),
    each inside the layer loop (steps, ``one_step``, layers: three
    ``while`` bodies deep), and nothing of what XLA's sweep made: no score
    ``[slots, heads, window]`` (with or without the token axis) and no
    layer's slab of either latent buffer written out. (The kernel's result
    is a triple, which ``_instructions`` does not parse: its calls are
    read off the text's lines.)"""
    got = [line for line in compiled.as_text().splitlines()
           if "custom-call(" in line and "tpu_custom_call" in line
           and "latent_decode" in line]
    assert len(got) == calls, len(got)
    for call in got:
        name = re.search(r'op_name="([^"]*)"', call).group(1)
        assert name.count("while/body") == 3, name
    swept = {f"{t}[{slots},{heads},{one}{window}]"
             for t in ("f32", "bf16") for one in ("", "1,")}
    slabs = {f"bf16[{lead}{slots},{one}{window},{width}]"
             for lead in ("", "1,") for one in ("", "1,")
             for width in (512, 64)}
    assert [f"{comp}: {name} {shape}"
            for comp, name, shape, op, _ in _instructions(compiled)
            if shape in swept or (
                shape in slabs and not comp.startswith("fused_computation")
                and op not in ("parameter", "get-tuple-element", "bitcast",
                               "tuple", "dynamic-update-slice"))] == []


def _donated_bytes(compiled) -> tuple[int, int]:
    """(arguments, temporaries) by the compiler's own memory analysis,
    having checked that the donated cache leaves in the buffers it came
    in (outputs alias arguments)."""
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= m.output_size_in_bytes * 0.99
    return m.argument_size_in_bytes, m.temp_size_in_bytes


GIB = 2**30


@pytest.fixture
def as_on_chip(monkeypatch):
    """Code under trace asks ``jax.default_backend()`` and would take its
    CPU branch; steer it here, in the test, as the guide says -- never
    through an option of the program."""
    monkeypatch.setattr(pk, "on_tpu", lambda: True)


def _kexaone_cell():
    """K-EXAONE at the cell ``kexaone-ep8-cut.decode-doc``'s sizes:
    published widths, layers 0-6, 16 of 128 experts, 4096 rows."""
    from cake_tpu.models.config import kexaone_ep8

    return kexaone_ep8(num_hidden_layers=7, vocab_size=19200,
                       max_seq_len=4096)


def _family_programs(topo, config, slots: int, window: int, *buckets: int):
    """(block decode, an admission a bucket) of a latent-family ``config``
    compiled for one described v5e, bf16: BatchGenerator's fused 8-step
    per-row block decode over ``slots`` slots and one admission chunk of
    each of ``buckets`` tokens into the batch-1 staging cache."""
    from jax.sharding import NamedSharding

    from cake_tpu.models.llama import init_params
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.mesh import MeshPlan, cache_specs, param_specs
    from cake_tpu.parallel.pipeline import (build_admit_prefill,
                                            build_sharded_decode)

    plan = MeshPlan.build(config, devices=topo.devices[:1])

    def placed(shapes, specs):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(plan.mesh, spec)),
            shapes, specs)

    params = jax.eval_shape(lambda k: init_params(config, k),
                            jax.random.PRNGKey(0))
    params = placed(params, param_specs(params))
    rep = NamedSharding(plan.mesh, jax.sharding.PartitionSpec())

    def arg(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def cache(batch):
        return placed(
            jax.eval_shape(lambda: init_cache(config, batch=batch,
                                              max_seq=window)),
            cache_specs(None, batch_replicated=batch == 1,
                        held=config.cache_plan))

    settings = SamplerSettings(temperature=0.0)
    decode = build_sharded_decode(
        config, settings, plan, params_like=params, steps=8, per_row=True
    ).lower(params, arg((slots,)), cache(slots), arg((slots,)),
            arg((slots, 2), jnp.uint32),
            arg((slots, settings.repeat_last_n)), arg((slots,)),
            arg((slots,))).compile()
    admits = [
        build_admit_prefill(config, plan, params_like=params).lower(
            params, arg((1, bucket)), cache(1), arg(()), arg((1,))).compile()
        for bucket in buckets]
    return (decode, *admits)


def _scoped_fusions(compiled, scope: str) -> dict[str, int]:
    """Fusions (outside fused computations) whose ``op_name`` carries the
    named scope ``scope``, counted a computation."""
    import collections

    found: dict[str, int] = collections.Counter()
    for comp, _, _, op, line in _instructions(compiled):
        if op != "fusion" or "fused" in comp:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        if name and scope in name.group(1):
            found[comp] += 1
    return dict(found)


def _layouts(compiled, shape: str) -> set[str]:
    """Every layout the compiled program gives a value of ``shape``
    (``bf16[4,32,8,2048,64]``): the text between its braces. (What a
    kernel's call asks of its operands, ``operand_layout_constraints``,
    names dimension orders and no value.)"""
    import re

    text = re.sub(r"operand_layout_constraints=\{[^=]*\}, ", "",
                  compiled.as_text())
    return set(re.findall(re.escape(shape) + r"\{([^}]*)\}", text))
