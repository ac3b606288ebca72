"""The chip's compiler on the latent family's whole programs at real
widths (``tests/test_chip_compile.py`` says what these compiles are; in a
file of its own since PR 59): latent attention with held experts, the
residual stream four hidden vectors wide, the delta-rule hybrid.
Shared: ``tests/chip_compile_kit.py``.
"""

import re

from chip_compile_kit import (  # noqa: F401
    GIB, _cache_sized_moves, _donated_bytes, _expert_stack_moves,
    _family_programs, _grouped_matmul_calls, _instructions,
    _latent_kernel_held, _layouts, _live_tile_calls, _scoped_fusions,
    as_on_chip, no_compile_cache, topo,
)


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
    largest bucket, 0.3100 GiB of temporaries (0.3095 with the solve).
    Since PR 60 the loop over the chunks runs to the launch's last live
    chunk, a bound that is data, inside the scanned layer body (it takes
    no layer weight and carries none of the four cache buffers: the
    assertions below hold of the admission as they did), and the expert
    block is told the rows' true lengths. RECORDED (my AOT compiles, PR
    60): 0.3102 GiB against the parent's 0.3101."""
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
    assert m.temp_size_in_bytes < 0.315 * GIB, m.temp_size_in_bytes / GIB


def _kernel_calls(compiled, name: str) -> list[str]:
    """The lines of ``compiled``'s text that call the kernel ``name``."""
    return [line for line in compiled.as_text().splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line
            and name in line.split("=")[0]]


def _sorted_shapes(compiled) -> list[str]:
    """The first result of every sort in ``compiled`` (a sort's result is
    a tuple, which ``_instructions`` does not parse)."""
    return re.findall(r"= \((\w+\[[\d,]*\])[^=]* sort\(", compiled.as_text())


def _glm5(layers: int, window: int):
    from cake_tpu.models.config import glm5_ep16

    return glm5_ep16(num_hidden_layers=layers, first_k_dense_replace=1,
                     vocab_size=19360, max_seq_len=window)


def _in_the_layer_loop(compiled, name: str, calls: int) -> None:
    """The kernel ``name`` is called ``calls`` times in ``compiled``, each
    inside the block's loops (steps, then layers)."""
    found = _kernel_calls(compiled, name)
    assert len(found) == calls, (name, len(found))
    for call in found:
        scope = re.search(r'op_name="([^"]*)"', call).group(1)
        assert scope.count("while/body") == 3, scope


def test_sparse_latent_programs_read_the_chosen_rows_and_write_no_scores(
        topo, as_on_chip):
    """The latent family under a learned sparse attention at GLM-5's
    published widths, the cell ``glm5-ep16-cut.agent-long`` itself: 1
    dense + 4 expert layers, 16 slots x 16,384 rows, and its largest
    admission, 16,384 rows. The chip's compiler takes them. The cache's
    two kinds of row (``[c | k_pe | padding]`` in ONE row of 640, the
    second buffer empty: ``cache_plan`` ``rows (5, 1, 640, 0)``; the index
    key) are carried through both stacks and written in place: in the
    decode program nothing of the latent or the index buffer's shape is
    allocated or copied. A decode step runs the SWEEP (16,384 rows lie
    under ``ops.dsa.SWEEP_MAX_ROWS``; PR 62): its index scores are the
    kernel on the carried index buffer (``dsa_index``), its choice a
    threshold (``dsa_select``: no ``sort`` of ``[16, 16384]`` scores), its
    attention the kernel over the carried row buffer itself under the kept
    scores (``dsa_attend``: no gathered ``[16, 2048, 640]`` or ``[32768,
    640]`` rows exist), each once a stack inside the layer loop: no score
    ``[16, 64, 16384]`` of a full sweep exists, and the plain latent
    kernel is not called. The admission holds the choice and the masked
    sweep as kernels (``dsa_prefill_select``, ``dsa_prefill_attend``) and
    writes out neither the heads' index products ``[32, T, T]``, nor the
    index scores ``[T, T]`` in float32, nor the attention's ``[64, T,
    T]``: a row's mask ``[T, T]`` int8 is what passes between them (268
    MB). RECORDED (my AOT compiles, PR 62): block decode 9.16 GiB of
    arguments + 0.49 of temporaries (PR 61, the gather: 9.16 + 0.57); the
    16,384-row admission 7.40 + 3.95 GiB beside the 1.875 GiB live cache
    (8192 rows: 1.89; 4096: 0.95): well under the 6 GiB the cut leaves."""
    from cake_tpu.utils.chips import HBM_GIB

    layers, slots, window, bucket = 5, 16, 16384, 16384
    config = _glm5(layers, window)
    decode, admit = _family_programs(topo, config, slots, window, bucket)
    assert config.cache_plan == {"rows": (5, 1, 640, 0),
                                 "index": (5, 1, 128)}
    for width in (640, 128):
        assert _cache_sized_moves(
            decode, f"bf16[{layers},{slots},1,{window},{width}]") == []
    # the admission's one-row staging cache (0.12 GiB) may be re-laid at
    # the program's entry, never inside a loop
    for width in (640, 128):
        assert all(m.startswith("main") for m in _cache_sized_moves(
            admit, f"bf16[{layers},1,1,{window},{width}]"))
    for compiled in (decode, admit):
        assert _expert_stack_moves(compiled, "bf16", 16, 6144, 2048) == []
    for name in ("dsa_index", "dsa_select", "dsa_attend"):
        _in_the_layer_loop(decode, name, calls=2)  # a stack each
    assert _kernel_calls(decode, "latent_decode") == []
    assert f"f32[{slots},{window}]" not in _sorted_shapes(decode)
    gathered = {f"bf16[{slots},{config.index_topk},640]",
                f"bf16[{slots * config.index_topk},640]"}
    swept = {f"{t}[{slots},64,{one}{window}]"
             for t in ("f32", "bf16") for one in ("", "1,")}
    assert [s for _, _, s, _, _ in _instructions(decode)
            if s in gathered | swept] == []
    # the 16,384-row bucket's expert blocks fetch their live rows by
    # address (PR 63); a step's 16 rows are picked by the one-hot product
    for name, (step, bucketed) in {"moe_fetch_rows": (0, 1),
                                   "moe_row_words": (0, 1),
                                   "moe_gather_rows": (1, 0)}.items():
        assert len(_kernel_calls(decode, name)) == step, name
        assert len(_kernel_calls(admit, name)) == bucketed, name
    assert len(_kernel_calls(admit, "dsa_prefill_select")) == 2
    assert len(_kernel_calls(admit, "dsa_prefill_attend")) == 2
    whole = {f"f32[{lead}{heads}{bucket},{bucket}]"
             for lead in ("", "1,") for heads in ("", "32,", "64,")}
    assert [s for _, _, s, _, _ in _instructions(admit) if s in whole] == []
    # (no row's scores are sorted: the router's [T, 256] alone is)
    assert not [s for s in _sorted_shapes(admit) if s.endswith(f",{bucket}]")]
    args, temps = _donated_bytes(decode)
    assert 9.05 * GIB < args < 9.25 * GIB, args / GIB  # 7.28 + 1.875
    assert temps < 0.55 * GIB, temps / GIB
    m = admit.memory_analysis()
    assert m.temp_size_in_bytes < 4.1 * GIB, m.temp_size_in_bytes / GIB
    # beside the live cache and a second staging row
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes + 1.875 * GIB
            + 0.12 * GIB) < HBM_GIB["v5 lite"] * GIB


def test_sparse_latent_decode_gathers_the_chosen_rows_of_a_long_buffer(
        topo, as_on_chip):
    """The same model over a buffer PAST ``ops.dsa.SWEEP_MAX_ROWS``, 2
    slots x 202,752 rows (the published positions; 3.1 GB of cache): the
    chip's compiler takes the decode program in the GATHER form as PR 61
    left it: the choice ``lax.top_k`` of ``[2, 202752]`` scores, the chosen
    ``[2, 2048, 640]`` rows gathered out of the carried buffer and
    attended by the kernel over the copies (trace name ``dsa_attend``),
    each once a stack inside the layer loop; no ``dsa_select``, no
    ``latent_decode``, and still no copy of a cache-sized buffer."""
    from cake_tpu.ops import dsa

    layers, slots, window = 5, 2, 202752
    config = _glm5(layers, window)
    assert dsa.attend_form_choice(window, config.index_topk) == "gather"
    assert dsa.attend_form_choice(16384, config.index_topk) == "sweep"
    decode, = _family_programs(topo, config, slots, window)
    for width in (640, 128):
        assert _cache_sized_moves(
            decode, f"bf16[{layers},{slots},1,{window},{width}]") == []
    for name in ("dsa_index", "dsa_attend"):
        _in_the_layer_loop(decode, name, calls=2)
    assert _kernel_calls(decode, "dsa_select") == []
    assert _kernel_calls(decode, "latent_decode") == []
    # (the choice is ``lax.top_k``: a sort of the scores at 65,536 rows,
    # the compiler's ``TopK`` call at these)
    assert 'dsa.select/top_k"' in decode.as_text()
    gathered = {f"bf16[{slots},{config.index_topk},640]",
                f"bf16[{slots * config.index_topk},640]"}
    assert [s for _, _, s, _, _ in _instructions(decode) if s in gathered]
