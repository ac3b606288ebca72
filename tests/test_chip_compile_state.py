"""The chip's compiler on the whole programs of the families that hold a
state or a tail beside their rows, at real widths
(``tests/test_chip_compile.py`` says what these compiles are; in a file
of its own since PR 59): state-space layers, scalar-gated delta-rule
layers, short convolutions. Shared: ``tests/chip_compile_kit.py``.
"""

import re

from chip_compile_kit import (  # noqa: F401
    GIB, _cache_sized_moves, _decode_kernel_calls, _donated_bytes,
    _expert_stack_moves, _family_programs, _grouped_matmul_calls,
    _instructions, _layouts, as_on_chip, no_compile_cache, topo,
)


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
    1.403 GiB (0.091 -> 0.068 at 128 rows, 0.132 -> 0.120 at 1024).
    Since PR 60 the loop over the chunks runs to a bound that is data (the
    launch's last live chunk) inside the scanned layer body, and the
    expert block is told the rows' true lengths: the loop takes no layer
    weight and carries no cache buffer (no expert stack, state or tail is
    written out or copied in the 8192-row admission either). RECORDED (my
    AOT compiles, PR 60): 1.3332 GiB of temporaries at 8192 rows against
    the parent's 1.3327 (the loop's ``o`` is written in place into the one
    ``[n, ..]`` buffer it carries, which nothing fills first), 0.068 at
    128 on both. Since PR 65 a bucket of ``KDA_SCAN_MIN_T`` tokens or more
    runs that loop as ONE ``kda_chunk_scan`` call a delta-rule layer (a
    scanned ``D D D`` segment's body holds one), which advances the state
    it is handed where it lies and takes ``q``, ``k``, ``Q K^T`` a key
    head and the decays' sums, and writes the layer's normed and gated
    output as the output projection reads it: neither the decayed operands
    a value head, nor ``o``, nor a float32 copy of the gate ``z`` in
    another tiling is made for the whole bucket. RECORDED (my AOT
    compiles, PR 65): 0.896 GiB of temporaries at 8192 rows where the
    loop's program held 1.333; the 128-row bucket keeps XLA's loop and its
    0.068."""
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
    for compiled in (decode, admit, widest):
        assert _expert_stack_moves(compiled, "bf16", 128, 2048, 512) == []
    for shape in ("f32[6,1,32,128,128]", "bf16[6,1,3,8192]",
                  f"bf16[2,1,2,{window},256]"):
        assert _cache_sized_moves(widest, shape) == [], shape
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
    # the 8192-row bucket's expert blocks fetch their live rows by address
    # (PR 63: the pass that re-lays the bucket as words, then a copy a
    # row); a step's 32 rows are picked by the one-hot product, and the
    # 128-row bucket's block is dense
    assert "moe_fetch_rows" in widest.as_text()
    assert "moe_row_words" in widest.as_text()
    assert "moe_gather_rows" not in widest.as_text()
    for compiled in (decode, admit):
        assert "moe_fetch_rows" not in compiled.as_text()
    assert "moe_gather_rows" in decode.as_text()
    # the 8192-row bucket's scan is the kernel, once a delta-rule segment's
    # body, its state the operand it came in as; the 128-row one's and the
    # step hold none
    scans = [line for line in widest.as_text().splitlines()
             if "custom-call(" in line and "kda_chunk_scan" in line]
    assert len(scans) == 2
    for call in scans:
        assert "output_to_operand_aliasing={{1}: (7, {})}" in call
        name = re.search(r'op_name="([^"]*)"', call).group(1)
        assert name.count("while/body") == 2 and "gdn.chunk" in name, name
        assert call.lstrip().split(" = ")[1].startswith("(bf16[1,8192,4096]")
    for compiled in (decode, admit):
        assert "kda_chunk_scan" not in compiled.as_text()
    # ... and neither its output nor the gate is re-laid for XLA's fusion
    assert "f32[1024,8,32,128]" not in widest.as_text()
    small, large = (a.memory_analysis().temp_size_in_bytes
                    for a in (admit, widest))
    assert small < 0.3 * GIB, small / GIB
    # the loop's program: 1.3327 (held under 1.34 until PR 65)
    assert large < 0.95 * GIB, large / GIB
    assert args + temps + large + 0.4 * GIB < HBM_GIB["v5 lite"] * GIB


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
