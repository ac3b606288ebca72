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

This file: each kernel alone, and the families' lowered text. The
engine's whole programs are ``tests/test_chip_compile_engine.py``, the
latent family's ``tests/test_chip_compile_latent.py``, the families that
hold a state or a tail ``tests/test_chip_compile_state.py`` (PR 59); what
they share is ``tests/chip_compile_kit.py``.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cake_tpu.ops import pallas as pk
from cake_tpu.ops.pallas import (
    flash_attention, flash_attention_q8, flash_decode,
    quant4_matmul_pallas, quant_matmul_pallas,
)

from chip_compile_kit import (  # noqa: F401
    BF16, D, F32, FFN, H, HID, I32, I8, KVH, VOCAB, no_compile_cache, topo,
)


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


def _kda_chunk_scan(t, gated=True, b=1, hk=16, hv=32, d=128, c=64):
    """The scalar-gated rule's scan kernel as ``kda_chunk`` calls it: a
    bucket of ``t`` tokens' keys and queries and what its ``t / c`` chunks
    made ahead, the staging row's float32 state, the live chunks (traced)
    and, ``gated`` (as a layer calls it), the bfloat16 gate ``z`` and the
    head norm's weight of its epilogue."""
    from cake_tpu.ops.pallas.kda import kda_chunk_scan

    n = t // c

    def fn(q, k, qk, cum, u_hat, w, state, live, *gate):
        return kda_chunk_scan(q, k, qk, cum, u_hat, w, state, live,
                              gate=(*gate, 1e-6) if gate else None,
                              interpret=False)

    return (fn, [((n, b, hk, c, d), F32), ((n, b, hk, c, d), F32),
                 ((n, b, hk, c, c), F32), ((n, b, hv, c), F32),
                 ((n, b, hv, c, d), F32), ((n, b, hv, c, d), F32),
                 ((b, hv, d, d), F32), ((), I32)]
            + ([((b, t, hv * d), BF16), ((d,), F32)] if gated else []))


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
    makes them: ``[rows, h]`` in, ``pairs`` sorted rows between; the
    gather in the form the call takes at those rows (by address from
    ``ops.moe.GATHER_FETCH_MIN_ROWS`` on: the pass that re-lays the
    bucket as words and the kernel that copies a row an address)."""
    from cake_tpu.ops.moe import gather_form
    from cake_tpu.ops.pallas.moe import GroupTiles

    fetch = gather_form(rows, h, BF16) == "fetch"

    def fn(x, y, token, weight, *tiles):
        tiles = GroupTiles(*tiles)
        return (pk.gather_rows(x, token, tiles, fetch=fetch,
                               interpret=False),
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
    # the scan of qwen3next-ep4-cut's long buckets: B 1, 16 key heads under
    # 32 value heads of 128
    "kda_chunk_scan_t4096": _kda_chunk_scan(4096),
    "kda_chunk_scan_t8192": _kda_chunk_scan(8192),
    "kda_chunk_scan_ungated_t8192": _kda_chunk_scan(8192, gated=False),
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
    # ... fetched by address: Qwen3-Next's 8192-row bucket (top-10, 128
    # held of 512) and GLM-5's 16,384-row one (top-8, 16 of 256)
    "live_rows_qwen3next_t8192": _live_rows(8192, 81920, 2048, 128),
    "live_rows_glm5_t16384": _live_rows(16384, 131072, 6144, 16),
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
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # ... and a long bucket's gather is the fetch, a short one's the product
    if name.startswith("live_rows"):
        long = name in ("live_rows_qwen3next_t8192", "live_rows_glm5_t16384")
        assert ("moe_fetch_rows" in text) == long
        assert ("moe_gather_rows" in text) != long


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
    # decode step's text is what it was; and by PR 60, on purpose: the
    # serial loop over the chunks runs to the launch's last live chunk
    # (``_advance`` counts them from ``valid``; a ``fori_loop`` whose
    # bound is data where the scan was, over the same ``advance``: tests/
    # test_qwen3_next.py
    # ``test_scan_that_stops_at_the_last_live_chunk_is_the_whole_scan``)
    "hybrid.decode": "dd65de518af3a6cb", "hybrid.admit": "4f52b8ee0ff2bed8",
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
    # the latent family under a learned sparse attention, taken on PR 61's
    # tree, which brought it (``index_topk`` 0 lowers every family above
    # to the text it had: no hash replaced); the decode step's retaken on
    # PR 62's tree: off the chip too a buffer of 64 rows takes the sweep's
    # ``jnp`` form (``chosen_mask`` and the masked sweep of the layer) where
    # it took ``lax.top_k`` and a gather (``ops.dsa.attend_form_choice``,
    # by the rows); the admission's, as every family's above, is PR 61's
    "sparse_latent.decode": "4f84a34ca6d0ea0c",
    "sparse_latent.admit": "9fb811b1982e9704",
    # PR 60 (an admission tells its expert blocks and its chunked delta
    # rule the rows' true lengths) replaced the hybrid's admission alone:
    # a decode program is told no length, and at these 16 rows, without
    # kernels, no expert block takes the sorted form, the other place
    # that reads one
}


def _family_fixtures():
    from cake_tpu.models.config import (tiny, tiny_exaone_moe, tiny_glm_dsa,
                                        tiny_jamba, tiny_kda_hybrid,
                                        tiny_lfm2_moe, tiny_mla_moe, tiny_moe,
                                        tiny_ouro, tiny_xing4)

    return {"dense": lambda: tiny(sliding_window=32), "sparse": tiny_moe,
            "latent": tiny_mla_moe, "hybrid": tiny_kda_hybrid,
            "state_space": tiny_jamba, "windowed": tiny_exaone_moe,
            "short_conv": tiny_lfm2_moe, "looped": tiny_ouro,
            "latent_hc": tiny_xing4, "sparse_latent": tiny_glm_dsa}


@pytest.mark.parametrize("name", ["dense", "sparse", "latent", "hybrid",
                                  "state_space", "windowed", "short_conv",
                                  "looped", "latent_hc", "sparse_latent"])
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
