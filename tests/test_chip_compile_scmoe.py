"""The chip's compiler on LongCat-Flash's two serving programs at the
cell's sizes (``tests/test_chip_compile.py`` says what these compiles are;
a file of its own so that no one file sets tier-1's wall clock): the
shortcut-connected double layer, two latent planes a layer, zero-compute
experts, the blocked latent admission. Shared: ``tests/chip_compile_kit.py``.
"""

import re

from chip_compile_kit import (  # noqa: F401
    GIB, _cache_sized_moves, _donated_bytes, _expert_stack_moves,
    _family_programs, _grouped_matmul_calls, _instructions,
    _latent_kernel_held, as_on_chip, no_compile_cache, topo,
)


def test_the_cells_decode_block_and_8192_row_admission_fit_the_chip(
        topo, as_on_chip):
    """``longcat-flash-ep32-cut.code-mixed``'s programs as the cell serves
    them: published widths, 4 double layers, 16 of 512 experts beside 256
    zero-compute outputs, 16,384 vocabulary rows, 32 slots x 8192 rows.
    The chip's compiler takes the 8-step block decode and the 8192-row
    admission; both fit 15.75 GiB with the live cache beside the
    admission's staging row; the eight latent planes are carried through
    the ONE scanned segment and written in place (nothing of either
    buffer's shape is allocated or copied), no layer's expert stack is
    written out; the step's attention is the latent decode kernel, twice
    in the scan's body (a plane each), on the carried buffers; the
    admission's own-chunk attention is the flash prefill kernel over the
    expanded keys (``latent_prefill``), twice in the body, and builds no
    ``[1, 64, 8192, 8192]`` array."""
    from cake_tpu.models.config import longcat_flash_ep32

    layers, slots, window = 4, 32, 8192
    config = longcat_flash_ep32(num_hidden_layers=layers, vocab_size=16384,
                                max_seq_len=window)
    assert config.cache_plan == {"rows": (8, 1, 512, 64)}
    decode, admit = _family_programs(topo, config, slots, window, window)
    for compiled, batch in ((decode, slots), (admit, 1)):
        for width in (512, 64):
            assert _cache_sized_moves(
                compiled, f"bf16[8,{batch},1,{window},{width}]") == []
        assert _expert_stack_moves(compiled, "bf16", 16, 6144, 2048) == []
    assert _grouped_matmul_calls(admit) == 2
    assert _grouped_matmul_calls(decode) == 2
    _latent_kernel_held(decode, slots, window, 64, calls=2)
    text = admit.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call".*'
                          r'latent_prefill', text)) == 2
    assert not re.search(rf"f32\[1,64,{window},{window}\]", text)
    d_args, d_temps = _donated_bytes(decode)
    a_args, a_temps = _donated_bytes(admit)
    cache = slots * window * config.cache_token_bytes
    row = window * config.cache_token_bytes
    print("decode args", d_args / GIB, "temps", d_temps / GIB,
          "admit args", a_args / GIB, "temps", a_temps / GIB,
          "cache", cache / GIB, "row", row / GIB)
    # 9.64 GiB of weights + the 2.25 GiB cache; the admission holds the
    # weights, its 72 MiB staging row and its temporaries BESIDE the live
    # cache
    assert 11.8 * GIB < d_args < 12.2 * GIB, d_args / GIB
    assert d_args + d_temps < 15.75 * GIB
    assert a_args + a_temps + cache < 15.75 * GIB - 0.75 * GIB, (
        (a_args + a_temps + cache) / GIB)
