"""The chip's compiler on the engine's whole programs at real widths
(``tests/test_chip_compile.py`` says what these compiles are; in a file
of its own since PR 59): the dense and sparse int8 block decode and
admission, the landing's splice, and the attention-only families'
programs (window and full layers mixed, two rotations, a loop of
passes). Shared: ``tests/chip_compile_kit.py``.
"""

import jax
import jax.numpy as jnp
import pytest

from chip_compile_kit import (  # noqa: F401
    D, FFN, GIB, H, HID, I32, KVH, SLOTS, SPARSE_WINDOW, WINDOW,
    _admit_prefill, _block_decode, _cache_sized_moves,
    _decode_kernel_calls, _donated_bytes, _expert_stack_moves,
    _family_programs, _grouped_matmul_calls, _kexaone_cell, _layouts,
    _live_tile_calls, _projection_moves, _slabs_written, as_on_chip,
    no_compile_cache, topo,
)


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
    kind of row buffer is copied in the step. Since PR 60 the admissions
    tell their expert blocks the rows' true lengths (``moe_swiglu``'s
    ``valid``: one select on what XLA gathers, where every expert is
    held). RECORDED (my AOT compiles, PR 60): 1.1810 GiB of temporaries
    in the 8192-row admission against the parent's 1.1807, 0.0137 at 256
    rows on both."""
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
    assert large < 1.19 * GIB, large / GIB
    assert args + temps + large + 0.1 * GIB < 11 / 16 * HBM_GIB["v5 lite"] * GIB


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
