"""The chip's compiler on the main path's kernels, at real widths.

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
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cake_tpu.ops import pallas as pk
from cake_tpu.ops.pallas import (
    flash_attention,
    flash_attention_q8,
    flash_decode,
    quant4_matmul_pallas,
    quant_matmul_pallas,
)


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


def _flash(t, s, window):
    return (partial(flash_attention, window=window, interpret=False),
            [((1, H, t, D), BF16), ((1, KVH, s, D), BF16),
             ((1, KVH, s, D), BF16), ((), I32)])


def _flash_q8(t, s, window):
    return (partial(flash_attention_q8, window=window, interpret=False),
            [((1, H, t, D), BF16), ((1, KVH, s, D), I8), ((1, KVH, s), F32),
             ((1, KVH, s, D), I8), ((1, KVH, s), F32), ((), I32)])


def _decode(b, s, window):
    return (partial(flash_decode, window=window, interpret=False),
            [((b, H, 1, D), BF16), ((b, KVH, s, D), BF16),
             ((b, KVH, s, D), BF16), ((b,), I32)])


def _decode_stacked(layers, b, s, window, h=H, kvh=KVH, d=D):
    """The decode kernel as the layer loop calls it: the stacked cache and
    a traced layer index."""
    def fn(q, k, v, pos, layer):
        return flash_decode(q, k, v, pos, layer=layer, window=window,
                            interpret=False)

    return (fn, [((b, h, 1, d), BF16), ((layers, b, kvh, s, d), BF16),
                 ((layers, b, kvh, s, d), BF16), ((b,), I32), ((), I32)])


def _latent_decode(layers, b, s, h, dc=512, dr=64):
    """The latent decode kernel as the layer loop calls it: the stacked
    latent buffers (``dr`` = 64 is half a lane tile) and a traced layer
    index."""
    from cake_tpu.ops.pallas import latent_decode

    def fn(q_c, q_pe, c, r, pos, layer):
        return latent_decode(q_c, q_pe, c, r, pos, scale=0.1, layer=layer,
                             interpret=False)

    return (fn, [((b, h, dc), BF16), ((b, h, dr), BF16),
                 ((layers, b, 1, s, dc), BF16), ((layers, b, 1, s, dr), BF16),
                 ((b,), I32), ((), I32)])


def _kda_decode(layers, b, h=32, d=128, head_block=8, key_heads=None,
                scalar=False):
    """The delta-rule decode kernel as the layer loop calls it: the
    stacked float32 state and a traced layer index. ``key_heads`` under
    ``h`` value heads and ``scalar`` (a decay a head, in scalar memory):
    the scalar-gated rule's case."""
    from cake_tpu.ops.pallas import kda_decode

    def fn(q, k, v, g, beta, state, layer):
        return kda_decode(q, k, v, g, beta, state, layer,
                          head_block=head_block, interpret=False)

    vec, keys = ((b, h, d), F32), ((b, key_heads or h, d), F32)
    return (fn, [keys, keys, vec, ((b, h), F32) if scalar else vec,
                 ((b, h), F32), ((layers, b, h, d, d), F32), ((), I32)])


def _ssm(layers, b, t, n=16, c=5120):
    """The state-space kernels as the layer loop calls them: the stacked
    float32 state ``[L, B, d_state, d_inner]`` and a traced layer index;
    ``t`` None: the decode step, else an admission chunk of ``t`` tokens."""
    from cake_tpu.ops.pallas.mamba import ssm_decode, ssm_scan

    def fn(x, delta, bm, cm, a, d, state, layer):
        call = ssm_decode if t is None else ssm_scan
        return call(x, delta, bm, cm, a, d, state, layer, interpret=False)

    lead = (b,) if t is None else (b, t)
    return (fn, [(lead + (c,), F32), (lead + (c,), F32), (lead + (n,), F32),
                 (lead + (n,), F32), ((n, c), F32), ((c,), F32),
                 ((layers, b, n, c), F32), ((), I32)])


def _tiles(pairs, experts):
    """A :class:`GroupTiles`' shapes over ``pairs`` sorted rows."""
    from cake_tpu.ops.pallas.moe import ROW_TILE

    visits = pairs // ROW_TILE + experts - 1
    return [((experts + 1,), I32), ((visits,), I32), ((visits,), I32),
            ((1,), I32), ((1,), I32)]


def _gmm(pairs, k, n, experts, layers, int8=False):
    """The expert block's down product as the layer loop calls it:
    ``pairs`` sorted rows (a 512-row bucket's or a 32-row step's ``rows x
    top-k``), the whole ``[layers, experts, k, n]`` stack (int8 with a
    scale per output channel, or bf16), a traced layer index, the product
    left in float32 until the rows are summed."""
    from cake_tpu.ops.pallas.moe import GroupTiles

    def fn(lhs, rhs, scale, *rest):
        *tiles, layer = rest
        return pk.grouped_matmul(lhs, rhs, GroupTiles(*tiles), layer=layer,
                                 scale=scale, out_dtype=F32, interpret=False)

    return (fn, [((pairs, k), BF16),
                 ((layers, experts, k, n), I8 if int8 else BF16),
                 ((layers, experts, n), F32) if int8 else None,
                 *_tiles(pairs, experts), ((), I32)])


def _gswiglu(pairs, k, n, experts, layers, int8=False):
    """Gate, up and the SwiGLU in one call over the same operands: both
    whole stacks, a converted block each where they are int8."""
    from cake_tpu.ops.pallas.moe import GroupTiles

    def fn(lhs, gate, up, gate_scale, up_scale, *rest):
        *tiles, layer = rest
        return pk.grouped_swiglu(
            lhs, gate, up, GroupTiles(*tiles), layer=layer,
            gate_scale=gate_scale, up_scale=up_scale, interpret=False)

    stack = ((layers, experts, k, n), I8 if int8 else BF16)
    scale = ((layers, experts, n), F32) if int8 else None
    return (fn, [((pairs, k), BF16), stack, stack, scale, scale,
                 *_tiles(pairs, experts), ((), I32)])


def _live_rows(rows, pairs, h, experts):
    """The live tiles' gather and sum as a sorted call of ``rows`` rows
    makes them: ``[rows, h]`` in, ``pairs`` sorted rows between."""
    from cake_tpu.ops.pallas.moe import GroupTiles

    def fn(x, y, token, weight, *tiles):
        tiles = GroupTiles(*tiles)
        return (pk.gather_rows(x, token, tiles, interpret=False),
                pk.combine_rows(y, token, weight, tiles, rows,
                                out_dtype=BF16, interpret=False))

    return (fn, [((rows, h), BF16), ((pairs, h), F32), ((pairs,), I32),
                 ((pairs,), F32), *_tiles(pairs, experts)])


def _qmm(m, k, n):
    return (partial(quant_matmul_pallas, interpret=False),
            [((m, k), BF16), ((k, n), I8), ((n,), F32)])


def _q4mm(m, k, n, group):
    scale = ((k // group, n) if group else (n,), F32)
    return (partial(quant4_matmul_pallas, interpret=False),
            [((m, k), BF16), ((k // 2, n), I8), scale])


KERNELS = {
    "flash_t256_s2048": _flash(256, 2048, None),
    "flash_t512_s4096": _flash(512, 4096, None),
    "flash_win4096_t256_s2048": _flash(256, 2048, 4096),
    "flash_win4096_t512_s4096": _flash(512, 4096, 4096),
    "flash_q8_t256_s2048": _flash_q8(256, 2048, None),
    # the acceptance case: --kv-quant int8, a 512-token chunk, 4096 window
    "flash_q8_win4096_t512_s4096": _flash_q8(512, 4096, 4096),
    "flash_decode_b8_s4096_per_row": _decode(8, 4096, 4096),
    # the served shapes: 8 slots x 2048 under Mistral's window, x 4096
    "flash_decode_stacked_b8_s2048_win4096": _decode_stacked(2, 8, 2048, 4096),
    "flash_decode_stacked_b8_s4096": _decode_stacked(2, 8, 4096, None),
    # a batch of 32, and one stream
    "flash_decode_stacked_b32_s2048": _decode_stacked(2, 32, 2048, None),
    "flash_decode_stacked_b1_s4096": _decode_stacked(2, 1, 4096, None),
    # wider rows of heads with ONE query row a KV head (an MHA 7B's 32 x
    # 128, Gemma-7B's 16 x 256): the batched form in 128-row blocks
    # (pk.decode_block_k), which auto takes since PR 50
    "flash_decode_stacked_b8_s2048_kvh32": _decode_stacked(
        2, 8, 2048, None, h=32, kvh=32),
    "flash_decode_stacked_b1_s4096_kvh32": _decode_stacked(
        2, 1, 4096, None, h=32, kvh=32),
    "flash_decode_stacked_b8_s2048_kvh16_d256": _decode_stacked(
        2, 8, 2048, None, h=16, kvh=16, d=256),
    # the looped cell's own step (192 planes of 6 slots x 768 rows, KVH 16
    # x G 1: the batched form, the 128-row block ``pk.decode_block_k`` gives
    # that row of heads) and the same row of heads at 8 x 2048
    "flash_decode_stacked_ouro_b6_s768_kvh16": _decode_stacked(
        192, 6, 768, None, h=16, kvh=16),
    "flash_decode_stacked_b8_s2048_kvh16": _decode_stacked(
        2, 8, 2048, None, h=16, kvh=16),
    # heads of 64, two to a lane tile (PR 52): the cell lfm2-8b-a1b-cut's
    # own step (4 attention layers of 32 slots x 2048 rows, KVH 8 x G 4),
    # the floor's 1024 rows, and TinyLlama's row of heads (KVH 4 x G 8)
    "flash_decode_stacked_lfm2_b32_s2048_d64": _decode_stacked(
        4, 32, 2048, None, h=32, kvh=8, d=64),
    "flash_decode_stacked_b32_s1024_d64": _decode_stacked(
        4, 32, 1024, None, h=32, kvh=8, d=64),
    "flash_decode_stacked_b8_s2048_kvh4_d64": _decode_stacked(
        2, 8, 2048, None, h=32, kvh=4, d=64),
    # the latent cells' decode step: A.X-K1's 64 heads over 8 layers of 32
    # slots x 4096 rows of 512 + 64, Ling-3.0-flash's 32 heads over its one
    # latent layer, and the floor's 1024 rows
    "latent_decode_axk1_b32_s4096_h64": _latent_decode(8, 32, 4096, 64),
    "latent_decode_ling_b32_s4096_h32": _latent_decode(1, 32, 4096, 32),
    "latent_decode_b32_s1024_h64": _latent_decode(8, 32, 1024, 64),
    # Ling-3.0-flash's 32 heads of 128 x 128 at the cell's 32 slots, at
    # 48, and one stream
    "kda_decode_b32_h32": _kda_decode(6, 32),
    "kda_decode_b48_h32": _kda_decode(6, 48),
    "kda_decode_b1_h32": _kda_decode(6, 1),
    # Qwen3-Next's 16 key heads under 32 value heads of 128 x 128, a decay
    # a head, at the cell's 32 slots (the kernel's default block) and one
    "kda_decode_scalar_b32_h16_32": _kda_decode(6, 32, head_block=16,
                                                key_heads=16, scalar=True),
    "kda_decode_scalar_b1_h16_32": _kda_decode(6, 1, head_block=16,
                                               key_heads=16, scalar=True),
    # Jamba2-3B's 5120 channels of a 16-wide state at the cell's 32 slots,
    # at 64, one stream, and an admission chunk of a 512- and a 16-token
    # bucket
    "ssm_decode_b32": _ssm(26, 32, None),
    "ssm_decode_b64": _ssm(26, 64, None),
    "ssm_decode_b1": _ssm(26, 1, None),
    "ssm_scan_t512": _ssm(26, 1, 512),
    "ssm_scan_t16": _ssm(26, 1, 16),
    # the three expert cells' 512-row admission: Mixtral's 8 int8 experts
    # (1024 pairs), A.X-K1's 12 held of 192 and Ling-3.0-flash's 128 held
    # of 512 (4096 pairs each, whatever share of them falls here)
    "gmm_mixtral_int8_gate": _gswiglu(1024, HID, FFN, 8, 7, int8=True),
    "gmm_mixtral_int8_down": _gmm(1024, FFN, HID, 8, 7, int8=True),
    "gmm_axk1_gate": _gswiglu(4096, 7168, 2048, 12, 7),
    "gmm_axk1_down": _gmm(4096, 2048, 7168, 12, 7),
    "gmm_ling_gate": _gswiglu(4096, 2560, 768, 128, 6),
    "gmm_ling_down": _gmm(4096, 768, 2560, 128, 6),
    # the two 32-slot cells' decode step: 256 pairs, two row tiles
    "gmm_axk1_gate_step": _gswiglu(256, 7168, 2048, 12, 7),
    "gmm_axk1_down_step": _gmm(256, 2048, 7168, 12, 7),
    "gmm_ling_gate_step": _gswiglu(256, 2560, 768, 128, 6),
    "gmm_ling_down_step": _gmm(256, 768, 2560, 128, 6),
    # the live tiles' gather and sum where a share of the experts is held:
    # the two cells' 512-row admission and step, K-EXAONE's 2048-row prompt
    "live_rows_axk1": _live_rows(512, 4096, 7168, 12),
    "live_rows_axk1_step": _live_rows(32, 256, 7168, 12),
    "live_rows_ling": _live_rows(512, 4096, 2560, 128),
    "live_rows_kexaone_t2048": _live_rows(2048, 16384, 6144, 16),
    "qmm_m64_4096x14336": _qmm(64, HID, FFN),
    "qmm_m64_14336x4096": _qmm(64, FFN, HID),
    "qmm_m64_4096x32000": _qmm(64, HID, VOCAB),
    "q4mm_m1_4096x14336_per_channel": _q4mm(1, HID, FFN, 0),
    "q4mm_m1_4096x14336_g256": _q4mm(1, HID, FFN, 256),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(topo, name):
    fn, shapes = KERNELS[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [None if spec is None else
            jax.ShapeDtypeStruct(*spec, sharding=one_chip) for spec in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel is in the program (not silently an XLA fallback)
    assert "tpu_custom_call" in compiled.as_text()


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


# the attention projections' type and [in, out] in each program that the
# ``program`` fixture compiles: the dense and sparse int8 block decode by
# depth, the cell kexaone-ep8-cut.decode-doc's block decode and 2048-row
# admission
DENSE_PROJECTIONS = ("s8", ((HID, H * D), (HID, KVH * D)))
KEXAONE_PROJECTIONS = ("bf16", ((6144, 64 * 128), (6144, 8 * 128)))
PROGRAMS = {
    "dense.decode.depth2": DENSE_PROJECTIONS,
    "dense.decode.depth4": DENSE_PROJECTIONS,
    "dense.decode.depth32": DENSE_PROJECTIONS,
    "sparse.decode.depth2": DENSE_PROJECTIONS,
    "kexaone.decode": KEXAONE_PROJECTIONS,
    "kexaone.admit2048": KEXAONE_PROJECTIONS,
}


@pytest.fixture(scope="module")
def program(topo):
    """``program(name)``: the program ``name`` of ``PROGRAMS`` compiled for
    one described v5e, once for the tests of this file that share it (ask
    under ``as_on_chip``)."""
    compiled = {}

    def get(name: str):
        if name not in compiled:
            family, *_, last = name.split(".")
            if family == "kexaone":  # one call compiles both
                compiled["kexaone.decode"], compiled["kexaone.admit2048"] = (
                    _family_programs(topo, _kexaone_cell(), 32, 4096, 2048))
            else:
                compiled[name] = _block_decode(
                    topo, int(last.removeprefix("depth")),
                    sparse=family == "sparse")
        return compiled[name]

    return get


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_program_moves_no_projection(program, as_on_chip, name):
    """No program of the layer loop moves an attention projection's weight
    before its product reads it (``_projection_moves``): the dense int8
    block decode at depth 2, 4 and 32, the sparse one, and the cell
    ``kexaone-ep8-cut.decode-doc``'s block decode and 2048-row admission
    at published widths. The q and k products are followed by a per-head
    operation (the reshape to heads, heads ahead, a norm over D, the
    rotation); fused with it, the product took that operation's layout,
    and the compiler answered by re-laying the weight: a layer's ``wq
    [6144, 8192]`` sliced out of its stack, written to a buffer of its
    own and copied transposed into fast memory, 100 MB read and written a
    layer and step (0.34 s of 3.83 s busy in the cell, ledger, PR 40), the
    int8 stacks transposed whole once a dispatch. ``ops/attention.py``
    ``_project_heads`` keeps the two apart with an optimization barrier
    wherever a norm or a rotation follows (Jamba's attention has neither
    and keeps the program it had: ``PR31_TEXTS``); what is left are
    prefetches of a one-layer stack in the parameter's own layout, the
    product's read started early."""
    compiled = program(name)
    dtype, shapes = PROGRAMS[name]
    for k, n in shapes:
        assert _projection_moves(compiled, dtype, k, n) == [], (k, n)


def test_block_decode_program_fits_one_chip(program, as_on_chip):
    """One whole engine program on one described device, by the
    compiler's own text and memory analysis, at depth 2, 4 and 32 (a few
    seconds each; the layer loop is a scan).

    The stacked cache is the layer loop's carry and each stream's row is
    written into it in place (``models/llama.forward_layers``,
    ``ops/kvcache.update_layer``; PR 26). So the program allocates no
    second buffer of the cache's shape and copies none: in no loop, and
    not in ENTRY either, since the compiler keeps the carried cache in
    the parameter's own layout and nothing is re-laid on the way in or
    out. No instruction of the layer loop leaves a layer's slab behind
    before attention: the score and value fusions slice the carried
    buffer themselves. Before PR 26 the cache was scanned as ``xs``/``ys``:
    two such allocations, two copies in every decode step, a slab written
    and a slab read per layer, and about one KV cache of temporaries
    beside the donated one (1.07x at depth 2).

    Attention is the decode kernel (PR 29): ONE custom call, inside the
    layer loop (the decode block's scan over steps, ``one_step``, the
    layer scan: three ``while`` bodies deep), whose key and value operands
    are the carried buffers themselves; a Mosaic call fixes its operands'
    layout, and a compiler that answered by re-laying the cache on the
    way in would fail the two assertions above it. The sparse decoder's
    widths over 4096 rows (the sparse cell's, depth 2) are held to the
    same.

    Temporaries: under 4 MiB at every depth (1.8 MiB at depth 2, 1.1 at
    depth 4 and 32: the step's activations). Until PR 41 there were 0.626
    GiB at depth 32 (0.33 of the cache at depth 2): the compiler fused the
    q and k products with the reshape to heads and the rotation behind
    them, let that per-head operation's layout decide the product's, and
    so re-laid the WEIGHT instead of the 64 KB activation: the whole
    ``s8[L,4096,4096]`` and ``s8[L,4096,1024]`` stacks transposed once a
    dispatch in ENTRY (``copy.185``/``.184``), and a layer's slice of each
    written into fast memory before its product
    (``constant_dynamic-slice_fusion.19``/``.21``). ``_project_heads``
    now keeps product and per-head operation apart, and the products read
    ``wq`` and ``wk`` out of the stack as they read ``wv`` and ``wo``:
    ``test_program_moves_no_projection`` holds every program here to
    that. The 32-layer program itself, with a GiB for the admission
    staging row and the allocator, fits the chip with the whole 2 GiB
    cache."""
    from cake_tpu.utils.chips import HBM_GIB

    def held(compiled, depth, window):
        assert _cache_sized_moves(
            compiled, f"bf16[{depth},{SLOTS},{KVH},{window},{D}]") == []
        assert _slabs_written(compiled, (
            f"bf16[1,{SLOTS},{KVH},{window},{D}]",
            f"bf16[{SLOTS},{KVH},{window},{D}]")) == []
        (call,) = _decode_kernel_calls(compiled)
        assert call.count("while/body") == 3, call

    held(program("sparse.decode.depth2"), 2, SPARSE_WINDOW)
    for depth in (2, 4, 32):
        compiled = program(f"dense.decode.depth{depth}")
        held(compiled, depth, WINDOW)
        args, temps = _donated_bytes(compiled)
        assert temps <= 4 * 2**20, (depth, temps / 2**20)
    assert 8.8 * GIB < args < 8.95 * GIB, args / GIB  # 6.87 weights + 2.0
    assert args + temps + 1.0 * GIB < HBM_GIB["v5 lite"] * GIB


def test_admit_prefill_program_keeps_one_staging_cache(topo, as_on_chip):
    """The admission program (``build_admit_prefill``: one 512-token
    chunk into the batch-1 staging cache) shares the layer loop, so it
    is held to the same facts at depth 2 and 4: no allocation and no copy
    of the staging cache's shape anywhere, the donated cache aliased to
    the result. Its temporaries are the chunk's activations and do not
    grow with depth (9.2 MiB at depth 2, 4 and 32): the bar of 12 MiB is
    three quarters of the 2-layer staging cache and three eighths of the
    4-layer one; the scanned form kept 34 and 50 MiB (2.1 and 1.6 such
    caches). What the program may still do is fetch a layer's keys and
    values (4 MiB each) into fast memory ahead of the chunk's attention:
    that read is the one attention needs."""
    for depth in (2, 4):
        compiled = _admit_prefill(topo, depth, 512)
        assert _cache_sized_moves(
            compiled, f"bf16[{depth},1,{KVH},{WINDOW},{D}]") == []
        _, temps = _donated_bytes(compiled)
        assert temps <= 12 * 2**20, (depth, temps / 2**20)


def _landing_splice(topo, config, slots: int, window: int, rows: int = 1):
    """``(compiled, the live cache's shapes)`` of the engine's landing
    splice -- ``batch_generator.build_splice`` -- of ``rows`` staged rows
    into the ``slots``-slot live cache of ``config``, for one described
    v5e."""
    from jax.sharding import NamedSharding

    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.mesh import MeshPlan, cache_specs
    from cake_tpu.runtime.batch_generator import build_splice

    plan = MeshPlan.build(config, devices=topo.devices[:1])
    rep = NamedSharding(plan.mesh, jax.sharding.PartitionSpec())

    def arg(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def cache(batch):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(plan.mesh, spec)),
            jax.eval_shape(lambda: init_cache(config, batch=batch,
                                              max_seq=window)),
            cache_specs(None, batch_replicated=batch == 1,
                        held=config.cache_plan))

    n_hist = SamplerSettings().repeat_last_n
    live = cache(slots)
    compiled = build_splice((rep,) * 4).lower(
        live, cache(rows), arg((slots, 2), jnp.uint32),
        arg((slots, n_hist)), arg((slots,)), arg((slots,)),
        arg((rows, 2), jnp.uint32), arg((rows, n_hist)), arg((rows,)),
        arg((rows,)), arg((rows,))).compile()
    return compiled, live


@pytest.mark.parametrize("cell", ["mistral7b-int8", "axk1-ep16-cut"])
def test_landing_splice_writes_the_donated_cache_in_place(topo, as_on_chip,
                                                          cell):
    """A landing's splice at the two cells' shapes (the dense one's whole
    depth: 32 layers x 8 slots x 2048 rows, 2 GiB; the latent one's 8
    layers x 32 slots x 4096 rows beside its rope rows): the live cache
    and the sampler state are donated and every leaf leaves in the buffer
    it came in, so nothing of a cache leaf's shape is allocated or copied
    and the program's temporaries are a staged row's size at most, not
    the cache's (undonated it copied the whole cache, once a landing:
    7.5-8.4 ms dense, 3.3 ms latent, my chip runs, PR 37 and 44, and two
    caches were alive while it ran)."""
    from cake_tpu.models.config import axk1_ep16, mistral_7b

    if cell == "mistral7b-int8":
        slots, window = SLOTS, WINDOW
        config = mistral_7b(max_seq_len=window, num_hidden_layers=32)
    else:
        slots, window = 32, 4096
        config = axk1_ep16(num_hidden_layers=8, vocab_size=20480,
                           max_seq_len=window)
    compiled, live = _landing_splice(topo, config, slots, window)
    leaves = jax.tree.leaves(live)
    for leaf in leaves:
        shape = f"{leaf.dtype.name.replace('bfloat', 'bf')}" \
                f"[{','.join(map(str, leaf.shape))}]"
        assert _cache_sized_moves(compiled, shape) == [], shape
    m = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in leaves)
    # outputs alias arguments: the cache and the four state arrays
    assert m.alias_size_in_bytes >= m.output_size_in_bytes * 0.99
    assert m.alias_size_in_bytes >= held
    assert m.temp_size_in_bytes <= held / slots, (
        m.temp_size_in_bytes, held / slots)


def test_sparse_admission_reads_the_int8_stacks_where_they_lie(
        topo, as_on_chip):
    """Mixtral 8x7B's widths, int8, 3 layers, the sparse cell's 4096 rows:
    the admission programs from the threshold's bucket to the 512-row one
    take the expert block's sorted form, whose grouped matmul streams the
    int8 stacks as they lie in the parameters: no instruction of a
    layer's ``s8[1,8,4096,14336]`` or ``s8[1,8,14336,4096]`` (or their
    rank-3 forms) anywhere, where the dense form's dequantised batched
    product had two copies and two slices a layer (``copy.55``/``.56``,
    ``constant_dynamic-slice_fusion.25``/``.26``: 6.74 ms a layer, 47 ms
    of every admission, my chip run, PR 33), and temporaries of the
    chunk's size, not a stack's (470 MB). The decode step's 8 rows stay
    on the dense form: no kernel call there."""
    from cake_tpu.ops.moe import SORTED_MIN_ROWS_INT8

    for bucket in (SORTED_MIN_ROWS_INT8, 512):
        compiled = _admit_prefill(topo, 3, bucket, sparse=True)
        for k, n in ((HID, FFN),):
            assert _expert_stack_moves(compiled, "s8", 8, k, n) == []
        assert _grouped_matmul_calls(compiled) == 2
        assert _live_tile_calls(compiled) == 0  # all 8 experts held
        _, temps = _donated_bytes(compiled)
        assert temps < 0.2 * GIB, (bucket, temps / GIB)
    assert _grouped_matmul_calls(_block_decode(topo, 2, sparse=True)) == 0


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


def _latent_programs(topo, layers: int, slots: int, window: int, bucket: int):
    """(config, block decode, admission) at A.X-K1's published widths (one
    chip's share of 16, an eighth of the vocabulary), ``layers`` of its
    depth."""
    from cake_tpu.models.config import axk1_ep16

    config = axk1_ep16(num_hidden_layers=layers, vocab_size=20480,
                       max_seq_len=window)
    return (config, *_family_programs(topo, config, slots, window, bucket))


def test_latent_programs_move_no_cache_and_no_expert_stack(topo, as_on_chip):
    """The latent-attention, shared-expert family's two serving programs
    at A.X-K1's published widths, 1 dense + 2 expert layers, 32 slots x
    4096 rows (the cell ``axk1-ep16-cut.decode-full`` but for its depth):
    the chip's compiler takes them; the latent cache (two buffers, 512 and
    64 values a row, one "head") is carried through BOTH layer stacks and
    written in place, so nothing of either buffer's shape is allocated or
    copied; and no layer's expert stack ``[1, 12, 7168, 2048]`` is written
    out of the scanned weights before use. That last one is what control
    flow in the layer body costs (a ``lax.cond`` between two expert
    strategies wrote the three stacks out before it: 24 ms of every
    admission on the chip, PR 28), so a program runs one strategy, chosen
    from its shapes when it is traced: the 512-row admission the sorted
    form, whose kernel reads the whole stacks the layer loop closes over
    (PR 33), and so the 32-row step, whose 256 pairs hit 0.74 of the 192
    scored experts (PR 35): the stacks stay whole outside BOTH of the
    block's loops (steps, then layers) and the kernel reads the hit
    experts' matrices where they lie."""
    layers, slots, window = 3, 32, 4096
    config, decode, admit = _latent_programs(topo, layers, slots, window, 512)
    assert config.cache_row == (1, 512, 64)
    for compiled, batch in ((decode, slots), (admit, 1)):
        for width in (512, 64):
            assert _cache_sized_moves(
                compiled, f"bf16[{layers},{batch},1,{window},{width}]") == []
        assert _expert_stack_moves(compiled, "bf16", 12, 7168, 2048) == []
    # both take the sorted form: gate, up and the SwiGLU one grouped call
    # on the whole stacks and down another, a scan body, between the live
    # tiles' gather and sum
    assert _grouped_matmul_calls(admit) == 2
    assert _grouped_matmul_calls(decode) == 2
    assert _live_tile_calls(admit) == _live_tile_calls(decode) == 2
    # the step's absorbed attention is the kernel (PR 44), once in the
    # dense stack's scan body and once in the expert stack's, on the
    # carried buffers themselves: the rope half goes in rows-last, which
    # is how the chip holds it (rows on the lanes), so the swap is a
    # bitcast and nothing of its swapped shape is allocated or copied
    # either; and it stays in HBM (left to choose, the compiler moved it
    # into VMEM whole ahead of the loops: ``S(1)``)
    _latent_kernel_held(decode, slots, window, 64, calls=2)
    assert _cache_sized_moves(
        decode, f"bf16[{layers},{slots},1,64,{window}]") == []
    assert _layouts(decode, f"bf16[{layers},{slots},1,{window},64]") == {
        "3,4,2,1,0:T(8,128)(2,1)"}
    args, temps = _donated_bytes(decode)
    # 2 x 1.35 GB of expert layers + 1.0 of the dense one + 0.59 of
    # embedding and head = 4.29 GB = 4.0 GiB, + 0.42 GiB of latent cache
    assert 4.3 * GIB < args < 4.6 * GIB, args / GIB
    assert temps < 0.6 * GIB, temps / GIB


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


def test_wide_stream_programs_move_no_cache_no_stack_and_no_wide_stream(
        topo, as_on_chip):
    """The latent family under a residual stream FOUR hidden vectors wide
    (``hc_mult`` 4, ops/hyper.py) at Xing4.0-29B-A4B's published widths, 1
    dense + 2 expert layers with ALL 64 experts each, the whole 131,072-row
    vocabulary, 32 slots x 4096 rows (the cell ``xing4-29b-cut.decode-full``
    but for its depth): the chip's compiler takes both programs; nothing of
    the latent cache's shapes and no layer's expert stack ``[1, 64, 3584,
    1024]`` is allocated or copied.

    THE FORMS (my AOT compiles, PR 51; PERF.md section 7). The layer loop
    carries the stream as its four hidden vectors, a ``[B, T, 3584]`` array
    each. With ONE array ``[B, T, 4, 3584]`` in the carry the compiler
    holds it in ``(4, 128)`` tiles and, every sub-layer, writes it out
    again as float32 with the streams apart (``copy_convert_fusion
    f32[1,512,4,3584]``: 29 MB a 512-row admission where the stream is
    14.7) for the product with ``phi`` and the mixes; held flat (``[B, T,
    14336]``) the mixed streams' ``concatenate`` is a pass of its own. So
    the wide shape appears TWICE a program and in no loop: where the
    embedding is widened (a broadcast the split reads through: no
    instruction of its own where the compiler fuses it) and where the
    loop's result is joined for the head. Pinned: at most 2 instructions of
    the wide shape a program, all in ENTRY or the step loop's body, none
    in a layer loop's; no float32 copy of it anywhere.

    A sub-layer's coefficients: ``x~ phi`` is four products over the
    streams as they lie (``phi`` in three bfloat16 parts, 72 columns, so
    the stream is never converted), the statistics four reductions, and
    the Sinkhorn chain elementwise adds of the sixteen cells: no
    reduction, no ``dot`` over an axis of 4. The compiler cuts a chain
    where a fusion passes ~180 instructions and where several cells leave
    it, so it is NOT one fusion: RECORDED 53 and 43 fusions under the
    ``mhc.*`` scopes in the step's two layer bodies (two sub-layers each,
    the products, statistics and both mixes counted in), 43 in the
    admission's expert body; as ``sum(axis)`` rounds a chain alone is 80.
    Pinned at those counts + 10%.

    The step takes the expert block's DENSE form (32 rows x top-4 of 64
    hit 0.87 of the experts, over ``SORTED_MAX_HIT_SHARE``: no grouped
    matmul) and the 512-row admission the sorted one (three calls).
    RECORDED: the step 5.19 GiB of arguments (2 x 1.49 GB of expert layers
    + 0.26 of the dense one + 1.88 of embedding and head = 5.11 GB = 4.76
    GiB, + 0.42 GiB of latent cache) and 0.07 GiB of temporaries, the
    admission 4.78 + 0.31; at the cell's 1 + 6 layers 11.31 + 0.16 and
    10.35 + 0.31 GiB (a scratch script: the test stays at three layers),
    under ISSUE 51's 14.5."""
    from cake_tpu.models.config import xing4_29b

    layers, slots, window = 3, 32, 4096
    config = xing4_29b(num_hidden_layers=layers, first_k_dense_replace=1,
                       max_seq_len=window)
    decode, admit = _family_programs(topo, config, slots, window, 512)
    assert config.cache_row == (1, 512, 64)
    for compiled, rows in ((decode, f"{slots},1"), (admit, "1,512")):
        batch = int(rows.split(",")[0])
        for width in (512, 64):
            assert _cache_sized_moves(
                compiled, f"bf16[{layers},{batch},1,{window},{width}]") == []
        assert _expert_stack_moves(compiled, "bf16", 64, 3584, 1024) == []
        wide = [(comp, op, shape[:3])
                for comp, _, shape, op, _ in _instructions(compiled)
                if shape in (f"bf16[{rows},4,3584]", f"f32[{rows},4,3584]")
                and "fused" not in comp
                and op not in ("parameter", "get-tuple-element", "bitcast",
                               "tuple")]
        assert len(wide) <= 2, wide
        assert not [w for w in wide if w[1] in ("copy", "copy-start")], wide
        assert not [w for w in wide if w[2] == "f32"], wide
        text = compiled.as_text()
        for scope in ("mhc.coeff", "mhc.pre", "mhc.post"):
            assert scope in text, scope
    assert _grouped_matmul_calls(decode) == 0
    assert _grouped_matmul_calls(admit) == 2
    bodies = sorted(sum(_scoped_fusions(decode, scope).get(comp, 0)
                        for scope in ("mhc.", "btc,ck->btk"))
                    for comp in _scoped_fusions(decode, "mhc.coeff")
                    if "region" in comp)
    assert len(bodies) == 2 and bodies[0] <= 48 and bodies[1] <= 58, bodies
    args, temps = _donated_bytes(decode)
    assert 5.1 * GIB < args < 5.3 * GIB, args / GIB
    assert temps < 0.15 * GIB, temps / GIB
    m = admit.memory_analysis()
    assert 4.7 * GIB < m.argument_size_in_bytes < 4.9 * GIB
    assert m.temp_size_in_bytes < 0.4 * GIB


def _hybrid_programs(topo, layers: int, slots: int, window: int, bucket: int):
    """(config, block decode, admission) at Ling-3.0-flash's published
    widths (one chip's share of 4, a quarter of the vocabulary), the cut's
    ``layers`` (one leading dense)."""
    from cake_tpu.models.config import ling3flash_ep4

    config = ling3flash_ep4(num_hidden_layers=layers, first_k_dense_replace=1,
                            vocab_size=39296, max_seq_len=window)
    return (config, *_family_programs(topo, config, slots, window, bucket))


def test_hybrid_programs_move_no_cache_no_state_and_no_expert_stack(
        topo, as_on_chip):
    """The delta-rule + latent hybrid's two serving programs at
    Ling-3.0-flash's published widths, the cell
    ``ling3flash-ep4-cut.decode-full`` itself: 7 layers (K | K K K K | M |
    K: four segments, the fourth KDA stack of one layer after the latent
    one), 32 slots x 4096 rows. The chip's compiler takes them; the cache's
    two kinds of state (latent rows for the ONE latent layer, a float32
    state and a convolution tail for the six delta-rule layers) are
    carried through every segment and written in place, so nothing of any
    of the four buffers' shapes is allocated or copied; no expert stack
    ``[.., 128, 2560, 768]`` is written out of the scanned weights (both
    programs' expert calls are the grouped matmul on the whole stacks);
    the decode step is the kernel, inside the layer loop, on the carried
    state. Sizes: 9.75 GiB of weights + 0.53 GiB of cache in, under 0.3
    GiB of temporaries: the cell fits the chip with the admission's
    staging row and a second cache while the splice is undonated (and
    would at 48 slots: 10.54 + 0.17 GiB; the slots are 32 for the spread
    of TTFT between seeds, not for memory). The admission's chunk form
    holds no triangular solve (PR 58: the unit-triangular block's inverse
    is ``ops/kda.py`` ``_unit_lower_inverse``, products; XLA's solve was
    the custom call ``InvertDiagBlocksLowerTriangular``, 161 us a chunk
    and layer), and the channel case makes the inverse inside the scan, a
    chunk at a time (a ``[C, C, d_k]`` decay is 67 MB a chunk here).
    RECORDED (my AOT compiles, PR 58): the 512-row admission, the cell's
    largest bucket, 0.3100 GiB of temporaries (0.3095 with the solve)."""
    from cake_tpu.utils.chips import HBM_GIB

    layers, slots, window = 7, 32, 4096
    config, decode, admit = _hybrid_programs(topo, layers, slots, window, 512)
    assert config.cache_plan == {"rows": (1, 1, 512, 64),
                                 "state": (6, 32, 128, 128),
                                 "conv": (6, 3, 12288)}
    for compiled, batch in ((decode, slots), (admit, 1)):
        for shape in (f"bf16[1,{batch},1,{window},512]",
                      f"bf16[1,{batch},1,{window},64]",
                      f"f32[6,{batch},32,128,128]",
                      f"bf16[6,{batch},3,12288]"):
            assert _cache_sized_moves(compiled, shape) == [], shape
        assert _expert_stack_moves(compiled, "bf16", 128, 2560, 768) == []
    # three stacks of expert layers (K K K K | M | K), three products each,
    # in the admission and in the 32-row step (0.39 of 512 scored hit)
    assert _grouped_matmul_calls(admit) == 6
    assert _grouped_matmul_calls(decode) == 6
    assert _live_tile_calls(admit) == _live_tile_calls(decode) == 6
    # the kernel's result is a pair, which ``_instructions`` does not
    # parse: read its calls off the text's lines
    calls = [line for line in decode.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line
             and "kda_decode" in line]
    assert len(calls) >= 1
    for call in calls:
        name = re.search(r'op_name="([^"]*)"', call).group(1)
        assert name.count("while/body") == 3, name
        # the state it returns is the operand it was given, in place
        assert "output_to_operand_aliasing={{1}: (6, {})}" in call
    # the ONE latent layer's absorbed attention is the kernel too (PR 44)
    _latent_kernel_held(decode, slots, window, 32, calls=1)
    assert _cache_sized_moves(decode, f"bf16[1,{slots},1,64,{window}]") == []
    assert _layouts(decode, f"bf16[1,{slots},1,{window},64]") == {
        "3,4,2,1,0:T(8,128)(2,1)"}
    args, temps = _donated_bytes(decode)
    assert 10.15 * GIB < args < 10.4 * GIB, args / GIB  # 9.75 + 0.53
    assert temps < 0.3 * GIB, temps / GIB
    assert args + temps + 0.55 * GIB + 0.5 * GIB < HBM_GIB["v5 lite"] * GIB
    assert "triangular" not in admit.as_text().lower()
    m = admit.memory_analysis()
    assert m.temp_size_in_bytes < 0.35 * GIB, m.temp_size_in_bytes / GIB


def test_state_space_programs_move_no_cache_and_no_state(topo, as_on_chip):
    """The state-space + attention hybrid's two serving programs at
    Jamba2-3B's published sizes, the cell ``jamba2-3b.decode-long`` itself:
    all 28 layers (M7 A M6, twice: one period of three segments scanned
    over two repetitions), 32 slots x 2048 rows. The chip's compiler takes
    them; the cache's two kinds of state (rows for the two attention
    layers, a float32 ``[16, 5120]`` state and a convolution tail for the
    26 state-space layers) are carried through every segment and written
    in place, so nothing of the state's or the rows' shapes is allocated
    or copied; no repetition's weights are written out before use (the
    period's stacks stay whole outside both loops: handed to the inner
    loops as their ``xs``, a repetition's ``[7, 2560, 10240]`` and its
    like were 1.43 GiB of temporaries a program); the decode step is
    ``ssm_decode`` and the attention ``flash_decode`` (one key/value head
    under twenty), the admission ``ssm_scan``, each inside the layer loops
    on the carried buffers. Sizes: 5.96 GiB of weights (the tied matrix
    held twice) + 0.34 GiB of cache in, ~0.1 GiB of temporaries (64 slots:
    6.64 + 0.10 GiB, and a peak of 7.39 GiB on the chip; the slots are 32
    for the spread of `tokens_per_s` between seeds, not for memory)."""
    from cake_tpu.models.config import jamba2_3b
    from cake_tpu.utils.chips import HBM_GIB

    slots, window = 32, 2048
    config = jamba2_3b(max_seq_len=window)
    decode, admit = _family_programs(topo, config, slots, window, 512)
    for compiled, batch in ((decode, slots), (admit, 1)):
        for shape in (f"bf16[2,{batch},1,{window},128]",
                      f"f32[26,{batch},16,5120]"):
            assert _cache_sized_moves(compiled, shape) == [], shape
        # a repetition's slice of a stack, as a value of its own
        slabs = {f"bf16[{lead}{n},{a},{b}]" for lead in ("1,", "")
                 for n in (7, 6) for a, b in (
                     (2560, 10240), (5120, 2560), (2560, 8192), (8192, 2560))}
        assert [name for _, name, shape, op, _ in _instructions(compiled)
                if shape in slabs and op not in (
                    "parameter", "get-tuple-element", "bitcast",
                    "tuple")] == []

    def calls(compiled, kernel):
        return [re.search(r'op_name="([^"]*)"', line).group(1)
                for line in compiled.as_text().splitlines()
                if "custom-call(" in line and "tpu_custom_call" in line
                and kernel in line]

    # two state-space segments a period, each its own loop inside the
    # period's, inside the block's steps: the kernel sits four loops deep
    # in the step and three in the admission
    assert [n.count("while/body") for n in calls(decode, "ssm_decode")] == [
        4, 4]
    assert [n.count("while/body") for n in calls(decode, "flash_decode")] == [
        4]
    assert [n.count("while/body") for n in calls(admit, "ssm_scan")] == [3, 3]
    assert calls(decode, "ssm_scan") == calls(admit, "ssm_decode") == []
    for line in decode.as_text().splitlines():
        if "tpu_custom_call" in line and "ssm_decode" in line:
            # the state it returns is the operand it was given, in place
            assert "output_to_operand_aliasing={{1}: (7, {})}" in line
    args, temps = _donated_bytes(decode)
    assert 6.2 * GIB < args < 6.45 * GIB, args / GIB  # 5.96 + 0.34
    assert temps < 0.3 * GIB, temps / GIB
    # with the admission's staging row and a second cache while the
    # splice is undonated
    assert args + temps + 0.02 * GIB + 0.35 * GIB < HBM_GIB["v5 lite"] * GIB
    assert admit.memory_analysis().temp_size_in_bytes < 0.3 * GIB


def test_window_and_full_programs_move_no_cache_and_no_ring(program,
                                                            as_on_chip):
    """The window + full attention family's two serving programs at
    K-EXAONE's published widths, the cell ``kexaone-ep8-cut.decode-doc``
    itself: layers 0-6 (a dense window layer, two sparse window layers,
    the full layer, three sparse window layers: four scanned segments),
    16 of 128 experts, 32 slots x 4096 rows. The chip's compiler takes
    them; the cache's two kinds of rows (one full layer's ``[1, 32, 8,
    4096, 128]`` and six rings ``[6, 32, 8, 128, 128]``) are carried
    through every segment and written in place, so nothing of either
    shape is allocated or copied in the step, and nothing in any loop of
    the admission (a ``lax.switch`` over the kinds inside one scan, tried
    first, copied the rings in and out of every branch: PERF.md section
    7); no segment's expert stack is written out before
    use (the 2048-row admission takes the sorted form, whose kernel reads
    the whole stacks; the 32-row step the dense one, on a scan's slice in
    place); the full layer's decode
    attention is ``flash_decode`` on its rows, the window layers' XLA's
    over 128 ring rows. Sizes: 9.73 GiB of weights + 0.59 GiB of cache in
    (where six whole window layers would be 3.5 GiB), 0.006 GiB of
    temporaries (0.111 until PR 41: a layer's ``wq`` and ``wk`` written
    out of their stacks and transposed before each product,
    ``test_program_moves_no_projection``); the 2048-row admission 0.79 GiB
    beside its staging row (a band of blocks: 2048 x 256 scores a head,
    not 2048 x 2048)."""
    from cake_tpu.utils.chips import HBM_GIB

    slots, window = 32, 4096
    decode, admit = program("kexaone.decode"), program("kexaone.admit2048")
    for compiled, batch in ((decode, slots), (admit, 1)):
        assert _cache_sized_moves(
            compiled, f"bf16[1,{batch},8,{window},128]") == []
        # no layer's expert stack is left behind as a value of its own
        assert _expert_stack_moves(compiled, "bf16", 16, 6144, 2048) == []
    assert _cache_sized_moves(decode, f"bf16[6,{slots},8,128,128]") == []
    # the admission's one-stream staging rings (1.5 MiB each) are re-laid
    # rows ahead of heads on the way in and back on the way out, in ENTRY,
    # once a program (~16 us each by the compiler's estimate); in no loop
    staged = _cache_sized_moves(admit, "bf16[6,1,8,128,128]")
    assert len(staged) <= 4 and all(
        m.startswith("main") for m in staged), staged

    def calls(compiled, kernel):
        return [line for line in compiled.as_text().splitlines()
                if "custom-call(" in line and "tpu_custom_call" in line
                and kernel in line]

    assert len(calls(decode, "flash_decode")) == 1  # the full layer's
    # the step's 256 pairs hit 0.87 of the 16 held experts: the dense form
    # stays (ops/moe.py expert_form); the admission sorts, in each of the
    # three sparse segments
    assert _grouped_matmul_calls(decode) == 0
    assert _grouped_matmul_calls(admit) == 6
    args, temps = _donated_bytes(decode)
    assert 10.25 * GIB < args < 10.4 * GIB, args / GIB  # 9.73 + 0.59
    assert temps < 0.02 * GIB, temps / GIB
    m = admit.memory_analysis()
    assert m.temp_size_in_bytes < 1.0 * GIB, m.temp_size_in_bytes / GIB
    # the admission beside the live cache and the undonated splice's copy
    assert (args + temps + m.temp_size_in_bytes + 0.6 * GIB
            < 13 / 16 * HBM_GIB["v5 lite"] * GIB)


def test_two_rotation_programs_band_through_the_kernel_and_fit(topo,
                                                              as_on_chip):
    """The window + full attention family under Mellum2's keys at the cell
    ``mellum2-12b-cut.code-mixed``'s sizes: published widths, layers 0-7
    (three window layers and a full one, twice: four scanned segments and
    no repeated period), all 64 experts, the whole vocabulary, 32 slots x
    8192 rows, rings of 1024 rows; the block decode and the 256- and
    8192-row admissions. The chip's compiler takes them and they fit one
    chip. RECORDED (my AOT compiles, PR 55): 8.44 GiB of arguments (7.07
    of weights + 1.375 of rows and rings) and 0.006 GiB of temporaries in
    the step; 0.014 GiB in the 256-row admission, whose expert block takes
    the dense form (3.95 GiB with ``W W W G`` scanned as a repeated
    period: the period's gate and up stacks copied transposed in ENTRY,
    so ``layer_plan`` repeats no period here); 1.18 GiB in the 8192-row
    admission, whose window layers attend through the flash prefill
    kernel over the ring-then-chunk buffer (the XLA band's float32 scores
    alone would be 2.15 GB a window layer: ``1 x 32 x 8 x 1024 x 2048``):
    two attention kernels (one a kind of layer) and three grouped products
    a sparse segment. The step's full layers read their rows through
    ``flash_decode``, its window layers sweep their rings in XLA. Neither
    kind of row buffer is copied in the step."""
    from cake_tpu.models.config import mellum2_12b
    from cake_tpu.utils.chips import HBM_GIB

    slots, window = 32, 8192
    cfg = mellum2_12b(num_hidden_layers=8, max_seq_len=window)
    decode, admit256, admit8192 = _family_programs(topo, cfg, slots, window,
                                                   256, 8192)
    assert _cache_sized_moves(decode, f"bf16[2,{slots},4,{window},128]") == []
    assert _cache_sized_moves(decode, f"bf16[6,{slots},4,1024,128]") == []
    for compiled in (decode, admit256, admit8192):
        assert _expert_stack_moves(compiled, "bf16", 64, 2304, 896) == []

    def kernels(compiled):
        return sum("custom-call(" in line and "tpu_custom_call" in line
                   for line in compiled.as_text().splitlines())

    # the step: a decode kernel a full segment, the dense expert form
    assert kernels(decode) == 2 and _grouped_matmul_calls(decode) == 0
    assert _grouped_matmul_calls(admit8192) == 8  # 2 a sparse segment
    assert kernels(admit8192) == 8 + 4  # ... and an attention kernel each
    assert _live_tile_calls(admit8192) == 0  # all 64 experts held
    # 256 rows: the dense expert form and XLA's band (a band's shape is
    # under the prefill policy's floor), the full layers' flash prefill
    assert _grouped_matmul_calls(admit256) == 0 and kernels(admit256) == 2
    args, temps = _donated_bytes(decode)
    assert 8.4 * GIB < args < 8.5 * GIB, args / GIB
    assert temps < 0.02 * GIB, temps / GIB
    small, large = (a.memory_analysis().temp_size_in_bytes
                    for a in (admit256, admit8192))
    assert small < 0.1 * GIB, small / GIB
    assert large < 1.5 * GIB, large / GIB
    assert args + temps + large + 0.1 * GIB < 11 / 16 * HBM_GIB["v5 lite"] * GIB


def test_gated_delta_programs_fit_and_repeat_no_period(topo, as_on_chip):
    """The scalar-gated delta-rule + gated attention family at the cell
    ``qwen3next-ep4-cut.code-mixed``'s sizes: published widths, layers 0-7
    (``D D D`` and ``A`` by turns: four scanned segments and no repeated
    period), 128 of 512 experts, a quarter of the vocabulary, 32 slots x
    8192 rows; the block decode and the 128-row admission. The chip's
    compiler takes them. RECORDED (my AOT compiles, PR 57): 8.21 GiB of
    arguments (6.83 of weights + 1.0 of rows + 0.38 of state and tails) and
    0.006 GiB of temporaries in the step; 0.09 GiB in the 128-row
    admission, whose expert block takes the dense form (4.10 GiB with ``D D
    D A`` scanned as a repeated period: the period's gate and up stacks
    copied transposed, so ``layer_plan`` repeats no period here); 0.02 /
    0.08 / 0.13 / 0.95 GiB at 256 / 512 / 1024 / 8192 rows. The step's
    delta-rule layers go through ``kda_decode`` (its scalar case) in place
    on the carried state and its full layers' 256-wide heads through
    ``flash_decode``; neither the rows, the state nor the tails are
    copied. Neither admission holds a triangular solve (PR 58: XLA's was
    the custom call ``InvertDiagBlocksLowerTriangular``, one a 64-token
    chunk and layer inside the scan; ``ops/kda.py``
    ``_unit_lower_inverse`` makes every chunk's inverse by products ahead
    of it). RECORDED (my AOT compiles, PR 58): what the 8192-row bucket's
    128 chunks hold ahead of the scan lifts its temporaries from 0.947 to
    1.403 GiB (0.091 -> 0.068 at 128 rows, 0.132 -> 0.120 at 1024)."""
    from cake_tpu.models.config import qwen3next_ep4
    from cake_tpu.utils.chips import HBM_GIB

    slots, window = 32, 8192
    cfg = qwen3next_ep4(num_hidden_layers=8, vocab_size=37984,
                        max_seq_len=window)
    assert cfg.cache_plan == {"rows": (2, 2, 256, 256),
                              "state": (6, 32, 128, 128),
                              "conv": (6, 3, 8192)}
    decode, admit, widest = _family_programs(topo, cfg, slots, window, 128,
                                             8192)
    for shape in (f"bf16[2,{slots},2,{window},256]",
                  f"f32[6,{slots},32,128,128]", f"bf16[6,{slots},3,8192]"):
        assert _cache_sized_moves(decode, shape) == [], shape
    for compiled in (decode, admit):
        assert _expert_stack_moves(compiled, "bf16", 128, 2048, 512) == []
    calls = [line for line in decode.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert sum("kda_decode" in c for c in calls) == 2  # one a D D D segment
    assert sum("flash_decode" in c for c in calls) == 2  # one an A segment
    for call in calls:
        if "kda_decode" in call:  # the state it returns is its operand
            assert "output_to_operand_aliasing={{1}: (6, {})}" in call
    args, temps = _donated_bytes(decode)
    assert 8.15 * GIB < args < 8.3 * GIB, args / GIB
    assert temps < 0.02 * GIB, temps / GIB
    for compiled in (admit, widest):
        assert "triangular" not in compiled.as_text().lower()
    small, large = (a.memory_analysis().temp_size_in_bytes
                    for a in (admit, widest))
    assert small < 0.3 * GIB, small / GIB
    assert large < 1.5 * GIB, large / GIB
    assert args + temps + large + 0.4 * GIB < HBM_GIB["v5 lite"] * GIB


def _layouts(compiled, shape: str) -> set[str]:
    """Every layout the compiled program gives a value of ``shape``
    (``bf16[4,32,8,2048,64]``): the text between its braces. (What a
    kernel's call asks of its operands, ``operand_layout_constraints``,
    names dimension orders and no value.)"""
    import re

    text = re.sub(r"operand_layout_constraints=\{[^=]*\}, ", "",
                  compiled.as_text())
    return set(re.findall(re.escape(shape) + r"\{([^}]*)\}", text))


def test_conv_and_attention_programs_fit_one_chip(topo, as_on_chip):
    """The short-convolution + attention family's serving programs at
    LFM2-8B-A1B's published widths, the cell ``lfm2-8b-a1b-cut.decode-full``
    itself: layers 0-15 (two dense conv layers, then ``A`` and ``c c c``
    by turns, ``A``, ``c``: nine scanned segments and no repeated period),
    every one of the 32 experts, the whole vocabulary, 32 slots x 2048 rows; the block decode
    and the 128-, 512- and 2048-row admissions. The chip's compiler takes
    them and they fit one chip. RECORDED (my AOT compile, PR 43): 10.81
    GiB of arguments (10.31 of weights, the tied matrix twice, + 0.50 of
    rows + 3 MiB of tails) and 0.007 GiB of temporaries in the step;
    0.004, 0.14 and 0.53 GiB of temporaries in the admissions. (With
    ``A c c c`` scanned as a repeated period the 128- and 256-row
    admissions, whose expert block takes the dense form, held 5.26 GiB of
    temporaries: the period's gate and up stacks copied transposed in
    ENTRY; the chip refused to load them beside the weights. So
    ``layer_plan`` repeats no period here.)

    What the 64-wide rows got: the chip's default layout of ``[.., 2048,
    64]`` in bfloat16 puts the ROWS on the lanes and the head's 64
    channels on the sublanes (``{3,4,2,1,0:T(8,128)(2,1)}``: minor-most is
    the sequence axis), in the step and in both admissions alike, so no
    row is padded to a tile and nothing re-lays the cache: no value of the
    rows' shape is allocated or copied. The tails ``[12, 32, 2, 2048]``
    lie as they are declared, two rows a tile (``T(2,128)``), and are
    copied once on the way into and once out of the step (3 MiB each, in
    ENTRY, in no loop); no expert stack is written out of the scanned
    weights. The step's 128 pairs hit 0.98 of the 32 experts: the dense
    form, and so the 128-row admission; the 512- and 2048-row admissions
    sort, in each of the eight sparse segments.

    Since PR 52 the STEP attends through the decode kernel, once in each
    of the four attention segments, inside the layer loop (steps,
    ``one_step``, layers: three ``while`` bodies deep). Asked for ``[KVH,
    BK, 64]`` blocks of the cache as declared Mosaic REFUSES (my AOT
    compile, PR 52: "Slice shape along dimension 4 must be aligned to
    tiling (128), but is 64": it sees a buffer whose rows are padded to
    128 lanes, which XLA would have had to write, both buffers, every
    layer). So the kernel is handed the rows as columns and the heads in
    pairs, ``bf16[4,32,4,128,2048]`` in the order it is declared in:
    RECORDED (my AOT compile, PR 52) a ``bitcast`` of the carried buffer
    in each segment, no value of either shape allocated or copied,
    arguments 10.81 GiB as before, temporaries 0.0115 GiB (0.007 before:
    the kernel's q and o a segment). The admissions (``T > 1``) keep XLA's
    attention and their recorded sizes."""
    from cake_tpu.models.config import lfm2_8b_a1b
    from cake_tpu.utils.chips import HBM_GIB

    slots, window = 32, 2048
    config = lfm2_8b_a1b(num_hidden_layers=16, max_seq_len=window)
    decode, admit128, admit512, admit2048 = _family_programs(
        topo, config, slots, window, 128, 512, 2048)
    rows_on_lanes = "3,4,2,1,0:T(8,128)(2,1)"
    for compiled, batch in ((decode, slots), (admit128, 1), (admit512, 1),
                            (admit2048, 1)):
        rows = f"bf16[4,{batch},8,{window},64]"
        assert _layouts(compiled, rows) == {rows_on_lanes}, _layouts(
            compiled, rows)
        assert _cache_sized_moves(compiled, rows) == []
        assert _expert_stack_moves(compiled, "bf16", 32, 2048, 1792) == []
        tails = _cache_sized_moves(compiled, f"bf16[12,{batch},2,2048]")
        assert len(tails) <= 2 and all(
            m.startswith("main") for m in tails), tails
    assert "3,2,1,0:T(2,128)(2,1)" in _layouts(decode, "bf16[12,32,2,2048]")
    # the step's kernel reads the carried rows where they lie: its operand
    # is a bitcast of them (rows as columns, heads in pairs), nothing else
    calls = _decode_kernel_calls(decode)
    assert len(calls) == 4 and all(
        c.count("while/body") == 3 and "attn.full" in c for c in calls), calls
    view = f"bf16[4,{slots},4,128,{window}]"
    assert _layouts(decode, view) == {"4,3,2,1,0:T(8,128)(2,1)"}
    assert {op for _, _, shape, op, _ in _instructions(decode)
            if shape == view} == {"bitcast"}
    for compiled in (admit128, admit512, admit2048):
        assert "flash_decode" not in compiled.as_text()
    assert _grouped_matmul_calls(decode) == 0
    assert _grouped_matmul_calls(admit128) == 0
    assert _grouped_matmul_calls(admit512) == 16
    assert _grouped_matmul_calls(admit2048) == 16
    args, temps = _donated_bytes(decode)
    assert 10.75 * GIB < args < 10.9 * GIB, args / GIB  # 10.31 + 0.50
    assert temps < 0.02 * GIB, temps / GIB
    dense, small, large = (a.memory_analysis().temp_size_in_bytes
                           for a in (admit128, admit512, admit2048))
    assert dense < 0.02 * GIB, dense / GIB  # no stack re-laid
    assert small < 0.2 * GIB and large < 0.7 * GIB, (small / GIB,
                                                     large / GIB)
    # the admission beside the live cache, its staging row and the
    # undonated splice's second cache
    assert (args + temps + large + 0.6 * GIB
            < 13 / 16 * HBM_GIB["v5 lite"] * GIB)


def test_looped_programs_fit_one_chip_and_copy_no_cache(topo, as_on_chip):
    """The looped family's serving programs at Ouro-2.6B's published
    widths, the cell ``ouro-2p6b.decode-full`` itself: 48 layers run 4
    times a token over one stack, 192 cache planes, the whole vocabulary,
    6 slots x 768 rows; the block decode and the 128- and 512-row
    admissions. The chip's compiler takes them and they fit one chip.
    RECORDED (my AOT compile, PR 47): the step holds 11.72 GiB of
    arguments (4.97 of weights + 6.75 of rows; 13.97 at 8 slots) and 0.001
    GiB of temporaries; an admission 6.09 GiB of arguments (the weights
    and the batch-1 staging cache of 1.125 GiB) and 0.564 / 0.569 GiB of
    temporaries.

    The pass loop is a ``lax.fori_loop`` around the scan over THE stack
    (``forward_layers``): three loops nest (a block's 8 steps, 4 passes, a
    pass's 48 layers; the plane's offset is a loop value) around the
    carried cache, and the compiler copies neither cache buffer (3.375 GiB
    each at 6 slots) nor a layer stack (``bf16[48,2048,2048]``,
    ``[48,2048,5632]``, ``[48,5632,2048]``: read where they lie, in every
    pass), in no loop and not in ENTRY; temporaries 0.001 GiB. Four
    unrolled passes (four layer loops, a constant offset each) compile to
    the same facts (0.002 GiB); on the chip they read 1.7% more
    ``tpot_p50_ms`` in both pairs of runs (PERF.md section 6), so the loop
    is kept.

    What DID copy the cache was not the loop but the row of heads: with
    ONE query row a key/value head (KVH 16 x G 1, T == 1) the compiler
    multiplies q and K elementwise, wants the heads on the sublanes, and
    re-laid both carried buffers to ``{4,2,3,1,0}`` (``[.., S, KVH, D]``)
    on the way into and out of the step: 9.0 GiB of temporaries at 8
    slots, 22.97 GiB in all, refused (a plain 16/16-head decoder of 8
    layers does the same; 16/8 does not). ``ops/attention.py``
    ``_attend_xla`` hands such a row to the products as a group of two,
    which takes the product every grouped-query model takes.

    Since PR 50 the STEP attends through the decode kernel's batched form
    (``flash_decode``, 128-row blocks, each stream's live rows and no
    others), handed the two carried buffers where they lie, in the layout
    they are declared in: RECORDED (my AOT compile, PR 50) arguments 11.719
    GiB and temporaries 0.0009 GiB as before, no cache-sized move, the
    kernel's blocks in VMEM scratch alone. The admissions (``T > 1``) keep
    XLA's attention, the group of two included, and their recorded sizes.

    An admission re-lays the staging cache's KEYS once on the way in and
    once out (``{3,4,2,1,0}``: the rows on the lanes, K transposed for the
    chunk's score product), in ENTRY and in no loop, as every family's
    admission on XLA's attention does (an 8-layer plain decoder's too,
    16/8 heads as well, in fast memory there): one K buffer (0.5625 GiB)
    of temporaries. It is why a chip holds 6 slots and not 8, and why two
    arrivals do not ride one program here (``GROUP_STAGING_BYTES``); PERF.md
    section 7 queues it."""
    from cake_tpu.models.config import ouro_2_6b
    from cake_tpu.utils.chips import HBM_GIB

    slots, window = 6, 768
    config = ouro_2_6b(max_seq_len=window)
    decode, admit128, admit512 = _family_programs(
        topo, config, slots, window, 128, 512)
    stacks = ("bf16[48,2048,2048]", "bf16[48,2048,5632]",
              "bf16[48,5632,2048]")
    as_declared = "4,3,2,1,0:T(8,128)(2,1)"
    rows = f"bf16[192,{slots},16,{window},128]"
    assert _layouts(decode, rows) == {as_declared}, _layouts(decode, rows)
    assert _cache_sized_moves(decode, rows) == []
    for compiled in (decode, admit128, admit512):
        for stack in stacks:
            assert _cache_sized_moves(compiled, stack) == [], stack
        text = compiled.as_text()
        assert "loop.pass" in text and "loop.norm" in text
    # the step attends through the decode kernel (ONE query row a KV head:
    # the batched form), once in the program, inside the layer
    # loop (steps, ``one_step``, passes, layers: four ``while`` bodies
    # deep), handed the carried buffers themselves; an admission (T > 1)
    # keeps XLA's attention
    (call,) = _decode_kernel_calls(decode)
    assert call.count("while/body") == 4 and "loop.pass" in call, call
    for compiled in (admit128, admit512):
        assert "flash_decode" not in compiled.as_text()
    args, temps = _donated_bytes(decode)
    assert 11.7 * GIB < args < 11.75 * GIB, args / GIB
    assert temps < 0.01 * GIB, temps / GIB
    staging = f"bf16[192,1,16,{window},128]"
    for compiled in (admit128, admit512):
        moves = _cache_sized_moves(compiled, staging)
        assert len(moves) <= 2 and all(
            m.startswith("main") for m in moves), moves  # ENTRY, no loop
        a, t = _donated_bytes(compiled)
        assert 6.05 * GIB < a < 6.15 * GIB, a / GIB
        assert t < 0.6 * GIB, t / GIB  # one K buffer, not both, not twice
    # the step's arguments, an admission's staging row and temporaries
    # beside them: under the 14.5 GiB ISSUE 47 sets (8 slots: 15.7)
    worst = max(c.memory_analysis().temp_size_in_bytes
                for c in (admit128, admit512))
    staging_bytes = 2 * 192 * 16 * window * 128 * 2
    assert args + temps + staging_bytes + worst < 14.5 * GIB
    assert args + temps + staging_bytes + worst < HBM_GIB["v5 lite"] * GIB
    assert (args + 2.25 * GIB) + staging_bytes + worst > 14.5 * GIB


# sha256[:16] of the lowered text of each family's serving programs at tiny
# widths, taken on PR 31's tree (commit 8273b40): the layer plan, the
# cache's two kinds of state and the routing bias are additions that the
# families PR 31 served do not pass through.
PR31_TEXTS = {
    # PR 56 re-pinned the five families that count their held experts
    # (latent, hybrid, windowed, short_conv, latent_hc), both programs, on
    # purpose: their expert block returns two more counts (the pair rows
    # handed to the sorted form and those of the row tiles it touched:
    # ``moe.sorted_pair_rows*``), which the decode programs carry beside
    # the two they had and the admission programs now return. The dense,
    # sparse, state-space and looped families keep every hash: no cell
    # without a counted expert block runs a changed program.
    # re-pinned by PR 41, on purpose: the dense and sparse families'
    # attention is ``ops/attention.py`` ``_project_heads``, which now puts
    # an ``optimization_barrier`` between the q, k and v products and the
    # reshape to heads (one more operation a layer in all four texts, the
    # mathematics unchanged: tests/test_ops.py
    # ``test_project_heads_is_the_plain_projection``); the latent and
    # hybrid families' attention (``ops/mla.py``, ``ops/kda.py``) does not
    # pass through it and keeps its text
    "dense.decode": "fbeebde18feae957", "dense.admit": "4e5df660db577dc7",
    "sparse.decode": "158883cf34015073", "sparse.admit": "1aca5de8033d67e6",
    # the two families that count their held experts' load: the decode
    # programs re-pinned by PR 35, which return one more count (the held
    # experts some row chose: ``moe.experts_hit``); their admissions are
    # the text they were
    "latent.decode": "1409e4ef740439b3", "latent.admit": "247a7617458f8410",
    # the hybrid's admission, taken on PR 32's tree (commit 9a9bb52): its
    # delta-rule expert segments share ONE scan body, which a body built
    # anew for each segment would lower once a segment (PR 33 met it).
    # The ADMISSION re-pinned by PR 57, on purpose: ``ops/kda.py``
    # ``kda_chunk`` is ONE chunk form for this family's decay a channel and
    # for the scalar-gated rule's decay a head under grouped key heads, so
    # its operands carry a group axis of one here (``[B, G, R = 1, C, ..]``:
    # reshapes, the same sums, products and triangular solve; tests/
    # test_qwen3_next.py ``test_chunk_form_is_the_recurrence[kda]``), and
    # again by PR 58, on purpose: the triangular solve is gone from
    # ``kda_chunk`` (the unit-triangular block's inverse by products,
    # ``_unit_lower_inverse``, and the halves of the chunk's update that do
    # not read the state made ahead of the scan over the chunks where they
    # fit, as they do at this fixture's widths; the same tests, and
    # tests/test_kda_hybrid.py ``test_kda_chunk_is_the_recurrence``); the
    # decode step's text is what it was
    "hybrid.decode": "dd65de518af3a6cb", "hybrid.admit": "0754319899298397",
    # the state-space family, taken on PR 40's tree (commit d2e802e): its
    # attention layers pass through ``_project_heads`` with no norm and no
    # rotation behind the products, where PR 41 puts no barrier (the chip's
    # step is 0.4% faster with the products fused: PERF.md section 6)
    "state_space.decode": "1db9244f72aa04e8",
    "state_space.admit": "b095bb3adf962eef",
    # the window and short-convolution families, taken on PR 45's tree
    # (commit 545f5a9), before PR 46 moved what a family is into one record
    "windowed.decode": "ef316bfadefe7e91",
    "windowed.admit": "bbea7a8b932e2425",
    "short_conv.decode": "21e8ec7733b740d7",
    "short_conv.admit": "903657caf6523926",
    # the looped family, taken on PR 47's tree, which brought it: a loop of
    # passes around the scan over one stack, a plane a layer and a pass
    "looped.decode": "1790a3781f0fb307",
    "looped.admit": "2d341182e4882929",
    # the latent family under a residual stream four hidden vectors wide,
    # taken on PR 51's tree, which brought it (``hc_mult`` 1 lowers every
    # family above to the text it had: no hash replaced)
    "latent_hc.decode": "64178e4935b87604",
    "latent_hc.admit": "17ac986347105f98",
}


def _family_fixtures():
    from cake_tpu.models.config import (tiny, tiny_exaone_moe, tiny_jamba,
                                        tiny_kda_hybrid, tiny_lfm2_moe,
                                        tiny_mla_moe, tiny_moe, tiny_ouro,
                                        tiny_xing4)

    return {"dense": lambda: tiny(sliding_window=32), "sparse": tiny_moe,
            "latent": tiny_mla_moe, "hybrid": tiny_kda_hybrid,
            "state_space": tiny_jamba, "windowed": tiny_exaone_moe,
            "short_conv": tiny_lfm2_moe, "looped": tiny_ouro,
            "latent_hc": tiny_xing4}


@pytest.mark.parametrize("name", ["dense", "sparse", "latent", "hybrid",
                                  "state_space", "windowed", "short_conv",
                                  "looped", "latent_hc"])
def test_existing_families_lower_to_the_text_they_had(name):
    """Each family's block decode and admission programs lower (StableHLO,
    CPU, tiny widths) to the text PR 31's tree (PR 32's for the hybrid,
    PR 40's for the state-space family, PR 45's for the window and
    short-convolution families, PR 47's for the looped one) gave them, so the chip's
    compiler sees what it saw and the cells it measured stay where they
    are: without kernels (the CPU's default) every call of the expert
    block takes the form it took before there was a sorted one (PR 33,
    PR 35). A PR that
    changes one of these programs on purpose replaces its hash here, and
    says so."""
    import hashlib

    from cake_tpu.models.llama import init_params
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.mesh import MeshPlan
    from cake_tpu.parallel.pipeline import (build_admit_prefill,
                                            build_sharded_decode)

    settings = SamplerSettings(temperature=0.0)
    config = _family_fixtures()[name]()
    plan = MeshPlan.build(config, devices=jax.devices()[:1])
    params = jax.eval_shape(lambda k: init_params(config, k),
                            jax.random.PRNGKey(0))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32)

    def cache(b):
        return jax.eval_shape(
            lambda: init_cache(config, batch=b, max_seq=64))

    decode = build_sharded_decode(
        config, settings, plan, params_like=params, steps=4,
        per_row=True).lower(
        params, i32(4), cache(4), i32(4),
        jax.ShapeDtypeStruct((4, 2), jnp.uint32),
        i32(4, settings.repeat_last_n), i32(4), i32(4))
    admit = build_admit_prefill(config, plan, params_like=params).lower(
        params, i32(1, 16), cache(1), i32(), i32(1))
    got = {f"{name}.{kind}": hashlib.sha256(
        lowered.as_text().encode()).hexdigest()[:16]
        for kind, lowered in (("decode", decode), ("admit", admit))}
    assert got == {k: v for k, v in PR31_TEXTS.items()
                   if k.startswith(name + ".")}
