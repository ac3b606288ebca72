"""Scalar-gated delta-rule layers beside gated, part-rotated grouped-query
attention over softmax-scored experts and a gated shared one (Qwen3-Next's
keys) against the plain reference
``cake_tpu/testing/reference_qwen3_next.py``, on seeded random weights at
tiny widths that keep the published pattern (``models.config.
tiny_qwen3_next``: ``D D D A`` twice, 2 key heads under 4 value heads of 16
x 8 state, 4 gated heads of 16 whose first 8 channels rotate, 16 experts
top-4 of which rank 1 of 4 holds 4).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls are
full precision. Program and reference differ in the order of sums only (the
chunked WY form and its blocked inverse against the recurrence token by
token, grouped against repeated key/value heads, softmax over the chosen
logits against softmax over all then renormalised, the dense expert form
against a Python loop over the experts, norms folded to ``1 + w`` on load
against added where they are applied): measured 3.9e-5 to 1.2e-4 on logits
of magnitude ~4.4 through eight layers over 160 tokens, ten times what the
window family's tests read, and as much on a pure decode from an empty state
(the same recurrence, token by token, on both sides) as through the chunk
form. One delta-rule layer alone agrees to 1.8e-6: its output is ``S^T q``,
sixteen products of mixed sign, normed over 8 channels a head and so scale
free, and six such layers in a row carry each other's last bits forward.
``TIGHT`` is 5e-4, four times the worst. The wrong-mathematics controls
move the logits by 4.0 (no attention gate), 2.9 (the whole head rotated),
6.5 (the shared expert ungated) and 0.56 (the state rounded to bfloat16
after every token), each checked below to pass a hundred times ``TIGHT``.
"""

from __future__ import annotations

import json
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import (LlamaConfig, qwen3next_ep4, tiny,
                                    tiny_kda_hybrid, tiny_qwen3_next)
from cake_tpu.obs import metrics
from cake_tpu.ops import kda, moe
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import apply_rope, rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_qwen3_next as ref
from cake_tpu.utils.weights import (latent_hf_tensors, load_llama_params,
                                    save_llama_params)

TIGHT = 5e-4
CFG = tiny_qwen3_next(max_seq_len=256, eos_token_id=-1)
HF = CFG.to_hf_dict()
TOKENS = np.random.default_rng(57).integers(3, 250, 160).astype(np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales (the heads' q and k norms and the
    delta rule's plain output norm among them) are not all ones, so that
    ``1 + w`` against ``w`` shows. The router's logits are scaled up so
    that the softmax shares of the chosen experts differ by far more than
    rounding, and the shared expert's gate so that it is no constant."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        name = path[-1].key
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name == "norm_f":
            return leaf * (1.0 + 0.25 * jax.random.uniform(
                k, leaf.shape, minval=-1.0))
        if name in ("router", "ws_share"):
            return 3.0 * leaf
        return leaf

    return jax.tree_util.tree_map_with_path(jitter, params)


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def tensors(params):
    return latent_hf_tensors(params, CFG)


@pytest.fixture(scope="module")
def want(tensors):
    """The reference's logits at every position of TOKENS (two and a half
    chunks of the scan)."""
    return np.asarray(ref.logits(HF, tensors, TOKENS))


def _logits(params, cfg, tokens, cache, pos, valid=None):
    """Logits at every position of one call, and the cache it leaves."""
    cos, sin = rope_tables_for(cfg, cache.max_seq)
    x = llama.embed_tokens(params, jnp.asarray(tokens), cfg)
    x, cache = llama.forward_layers(params["layers"], x, cache, cos, sin, pos,
                                    cfg, valid=valid)
    x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return x @ params["lm_head"], cache


_STEP = jax.jit(_logits, static_argnums=(1,))


def _through_the_cache(params, tokens, prefill, chunk, bucket=None,
                       cfg=CFG, step=_STEP):
    """Logits at every position through the cache: a prefill of
    ``prefill`` tokens in chunks of ``chunk`` (the last padded to
    ``bucket`` rows, its true length told), then a step a token."""
    cache = init_cache(cfg, batch=1, max_seq=256)
    out = []
    for lo in range(0, prefill, chunk):
        n = min(chunk, prefill - lo)
        rows = np.full((1, bucket or chunk), 7, np.int32)
        rows[0, :n] = tokens[lo:lo + n]
        logits, cache = step(params, cfg, rows, cache, jnp.int32(lo),
                             jnp.asarray([n], jnp.int32))
        out.append(np.asarray(logits[0, :n]))
    for i in range(prefill, len(tokens)):
        logits, cache = step(params, cfg, tokens[None, i:i + 1], cache,
                             jnp.asarray([i], jnp.int32))
        out.append(np.asarray(logits[0]))
    return np.concatenate(out), cache


# -- against the reference -----------------------------------------------------

def test_full_forward_matches_reference(params, want):
    """``llama.forward`` over the whole sequence in one call (two and a
    half chunks of the scan, the last padded inside ``kda_chunk``) gives
    the reference's last logits; the plan is a segment a stretch (``D D D``,
    ``A``, twice) and no repeated period, as beside every other mixer over
    routed experts (``models/llama.py`` ``layer_plan`` says why: 4.10 GiB of
    temporaries in a 128-row admission at the published widths)."""
    logits, cache = llama.forward(
        params, jnp.asarray(TOKENS[None]), init_cache(CFG, batch=1,
                                                      max_seq=256),
        jnp.int32(0), CFG)
    np.testing.assert_allclose(logits[0], want[-1], atol=TIGHT, rtol=0)
    assert [(run.repeats, seg.name, seg.mixer, seg.count, seg.cache_first)
            for run, seg in llama.plan_segments(CFG)] == [
        (1, "gdn_moe", "gdn", 3, 0), (1, "gqa_moe", "gqa", 1, 0),
        (1, "gdn_moe_2", "gdn", 3, 3), (1, "gqa_moe_2", "gqa", 1, 1)]
    assert cache.state.shape == (6, 1, 4, 16, 8)
    assert cache.conv.shape == (6, 1, 3, 2 * 2 * 16 + 4 * 8)
    assert cache.k.shape == (2, 1, 2, 256, 16)


CASES = [
    (40, 11, 11, 16),  # a bucket's padding, inside the first chunk
    (160, 150, 64, None),  # a prompt over three chunks, the last short
    (160, 100, 100, 128),  # one dispatch of two chunks of the scan, padded
    (100, 96, 48, None),  # chunks shorter than the scan's 64
]
CASE_IDS = ["padded-bucket", "three-chunks", "one-chunk-padded",
            "chunks-of-48"]


@pytest.mark.parametrize("context, prefill, chunk, bucket", CASES,
                         ids=CASE_IDS)
def test_prefill_then_decode_through_the_cache_match_reference(
        params, want, context, prefill, chunk, bucket):
    """Chunked admission then decode through the state, the tail and the
    rows against the reference's one pass from a zero state, at every
    position: the state enters a chunk and leaves it, a bucket's padding
    neither decays nor writes it, the tail is taken at the true length."""
    got, _ = _through_the_cache(params, TOKENS[:context], prefill, chunk,
                                bucket)
    np.testing.assert_allclose(got, want[:context], atol=TIGHT, rtol=0)


def test_two_rows_of_unequal_length_in_one_dispatch(params, tensors, want):
    """Two rows of one admission program, 70 and 23 true tokens in a bucket
    of 128: each row's logits and, a step later, its next token's are its
    own reference's."""
    other = np.asarray(TOKENS[::-1][:24])
    rows = np.full((2, 128), 7, np.int32)
    rows[0, :70], rows[1, :23] = TOKENS[:70], other[:23]
    cache = init_cache(CFG, batch=2, max_seq=256)
    logits, cache = _STEP(params, CFG, rows, cache, jnp.int32(0),
                          jnp.asarray([70, 23], jnp.int32))
    want_other = np.asarray(ref.logits(HF, tensors, other))
    np.testing.assert_allclose(logits[0, :70], want[:70], atol=TIGHT, rtol=0)
    np.testing.assert_allclose(logits[1, :23], want_other[:23], atol=TIGHT,
                               rtol=0)
    nxt = np.asarray([[TOKENS[70]], [other[23]]])
    logits, _ = _STEP(params, CFG, nxt, cache, jnp.asarray([70, 23],
                                                           jnp.int32))
    np.testing.assert_allclose(logits[0, 0], want[70], atol=TIGHT, rtol=0)
    np.testing.assert_allclose(logits[1, 0], want_other[23], atol=TIGHT,
                               rtol=0)


@pytest.mark.parametrize("control", [
    dict(gate=False), dict(rotate_all=True), dict(shared_gate=False),
    dict(state_dtype=jnp.bfloat16), dict(decay=False), dict(grouped=False)],
    ids=["no-gate", "whole-head-rotated", "shared-ungated", "bf16-state",
         "no-decay", "key-heads-mis-grouped"])
def test_wrong_mathematics_fails_the_tolerance(tensors, want, control):
    """The controls of the mechanisms: the reference without the
    attention's gate, with the whole head rotated, with the shared expert
    unweighted, with its state rounded to bfloat16 after every token, with
    the delta rule's decay left out or its key heads mis-grouped is another
    model by far more than ``TIGHT``."""
    off = np.asarray(ref.logits(HF, tensors, TOKENS[:72], **control))
    assert np.abs(off - want[:72]).max() > 100 * TIGHT


# -- the rule: one step, one chunk form, one kernel ------------------------------

def _rule_inputs(hk, hv, scalar, t=150, b=2, dk=16, dv=8, seed=3):
    rs = np.random.default_rng(seed)

    def rnd(*shape):
        return jnp.asarray(rs.normal(size=shape), jnp.float32)

    q = kda._l2norm(rnd(b, t, hk, dk)) * dk ** -0.5
    k, v = kda._l2norm(rnd(b, t, hk, dk)), rnd(b, t, hv, dv)
    # decays down to e^-6 a token: unbounded below, as the scalar rule's
    g = -3 * jax.nn.softplus(rnd(*((b, t, hv) if scalar
                                   else (b, t, hv, dk))))
    return q, k, v, g, jax.nn.sigmoid(rnd(b, t, hv)), 0.3 * rnd(b, hv, dk, dv)


RULES = [(4, 4, False), (2, 4, True), (4, 4, True), (2, 4, False)]
RULE_IDS = ["kda", "scalar-grouped", "scalar", "channel-grouped"]


CHUNK_CASES = [(*rule, "random") for rule in RULES] + [
    (2, 4, True, "repeated-keys"), (2, 4, True, "padded-tail")]
CHUNK_IDS = RULE_IDS + ["scalar-repeated-keys", "scalar-padded-tail"]
# ... and the scan as the Pallas kernel (interpreted here, forced), which
# is the scalar-gated rule's alone
CHUNK_FORMS = [("xla", *case) for case in CHUNK_CASES] + [
    ("kernel", *case) for case in CHUNK_CASES if case[2]]
CHUNK_FORM_IDS = CHUNK_IDS + [
    f"kernel-{name}" for name, case in zip(CHUNK_IDS, CHUNK_CASES) if case[2]]


@pytest.mark.parametrize("form, hk, hv, scalar, case", CHUNK_FORMS,
                         ids=CHUNK_FORM_IDS)
def test_chunk_form_is_the_recurrence(form, hk, hv, scalar, case,
                                      monkeypatch):
    """``kda_chunk`` (150 tokens: two whole chunks and a padded one)
    against ``kda_recurrence`` for both rules, with as many key heads as
    value heads and with groups: outputs and the state it leaves, the
    serial scan as the ``jnp`` loop and, for a decay a head, as the kernel
    ``kda_chunk_scan`` (``Hk < Hv`` and ``Hk == Hv``; two value heads a
    grid step, so that a block's key heads are fetched by the index maps).
    The recurrence is given the key heads repeated; the chunk form groups.
    ``repeated-keys``: one key a head and row at every token, no decay and
    ``beta`` within 1e-3 of 1, so that ``N = beta tril(K K^T, -1)`` is all
    ones under its diagonal, its powers grow to 5e17 before they vanish,
    and an inverse made of them (the Neumann product) cancels to nothing:
    the blocked inverse does not form them.
    ``padded-tail``: three whole chunks from a nonzero state of which a
    row holds 130 and 64 true tokens (``_advance`` with ``valid``): the
    state is the recurrence's over the true tokens alone."""
    t = 192 if case == "padded-tail" else 150
    q, k, v, g, beta, s0 = _rule_inputs(hk, hv, scalar, t=t)
    if form == "kernel":
        from cake_tpu.ops.pallas import kda as pallas_kda

        monkeypatch.setenv("CAKE_PALLAS", "1")  # what ``_advance`` asks
        monkeypatch.setattr(pallas_kda, "SCAN_HEAD_BLOCK", 2)
        assert kda.kda_chunk_choice(t, 16, 8, scalar) == "kernel"
    if case == "repeated-keys":
        k = jnp.broadcast_to(k[:, :1], k.shape)
        g, beta = 0 * g, 1 - 1e-3 * beta
    rep = hv // hk
    wide = (jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v, g,
            beta)
    if case == "padded-tail":
        valid = np.array([130, 64], np.int32)
        o, s = jax.jit(lambda *a: kda._advance(*a, None, "gdn"))(
            q, k, v, g, beta, s0, jnp.asarray(valid))
        for row, n in enumerate(valid):
            o_want, s_want = kda.kda_recurrence(
                *(a[row:row + 1, :n] for a in wide), s0[row:row + 1])
            np.testing.assert_allclose(o[row:row + 1, :n], o_want,
                                       atol=5e-6, rtol=0)
            np.testing.assert_allclose(s[row:row + 1], s_want, atol=5e-6,
                                       rtol=0)
        return
    o_want, s_want = kda.kda_recurrence(*wide, s0)
    o, s = kda.kda_chunk(q, k, v, g, beta, s0, form=form)
    np.testing.assert_allclose(o, o_want, atol=5e-6, rtol=0)
    np.testing.assert_allclose(s, s_want, atol=5e-6, rtol=0)


@pytest.mark.parametrize("live", [None, 2], ids=["whole", "two-chunks"])
def test_scan_kernel_gates_its_output_as_the_layer_does(live):
    """``kda_chunk(form="kernel", gate=)``: the kernel's epilogue,
    ``rmsnorm_head(o) * silu(z)`` written a head's lanes of a token, is
    ``_gated`` of the ``jnp`` loop's ``o`` (float32 ``z``: no rounding on
    the way out), zero past the live chunks as ``o`` is, and the state
    the same; in ``z``'s type where that is bfloat16."""
    from cake_tpu.ops.pallas import kda as pallas_kda

    q, k, v, g, beta, s0 = _rule_inputs(2, 4, True, t=152)
    rs = np.random.default_rng(11)
    z = jnp.asarray(rs.normal(size=(2, 152, 4 * 8)), jnp.float32)
    norm = jnp.asarray(1 + 0.1 * rs.normal(size=8), jnp.float32)
    live = live if live is None else jnp.int32(live)
    o, s_want = kda.kda_chunk(q, k, v, g, beta, s0, live)
    want = kda._gated(o, z, norm, 1e-6)
    got, s = kda.kda_chunk(q, k, v, g, beta, s0, live, form="kernel",
                           gate=(z, norm), eps=1e-6)
    assert got.shape == (2, 152, 32) and got.dtype == jnp.float32
    # (the norm divides by the rms of eight values: errors of 5e-6 in o
    # stand over an rms of ~0.1)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    np.testing.assert_allclose(s, s_want, atol=5e-6, rtol=0)
    if live is not None:
        assert not np.asarray(got)[:, 128:].any()
    half, _ = kda.kda_chunk(q, k, v, g, beta, s0, live, form="kernel",
                            gate=(z.astype(jnp.bfloat16), norm), eps=1e-6)
    assert half.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        half.astype(jnp.float32),
        kda._gated(o, z.astype(jnp.bfloat16), norm, 1e-6).astype(
            jnp.float32), atol=2e-2, rtol=0)


def test_a_bfloat16_operand_in_the_scan_kernel_fails_the_tolerance(
        monkeypatch):
    """The control of the kernel's precision: ``kda_chunk_scan`` handed ONE
    operand (``u_hat``) rounded to bfloat16 leaves a state and outputs that
    miss ``kda_recurrence`` by far more than the 5e-6 the chunk forms are
    held to (as the reference with a bfloat16 state misses ``TIGHT``)."""
    from cake_tpu.ops.pallas import kda as pallas_kda

    scan = pallas_kda.kda_chunk_scan

    def rounded(q, k, qk, cum, u_hat, w, *rest, **kw):
        return scan(q, k, qk, cum,
                    u_hat.astype(jnp.bfloat16).astype(jnp.float32), w, *rest,
                    **kw)

    monkeypatch.setattr(pallas_kda, "kda_chunk_scan", rounded)
    # (a length of its own: JAX keeps ``kda_chunk``'s trace by its shapes)
    q, k, v, g, beta, s0 = _rule_inputs(2, 4, True, t=151)
    wide = (jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta)
    o_want, s_want = kda.kda_recurrence(*wide, s0)
    o, s = kda.kda_chunk(q, k, v, g, beta, s0, form="kernel")
    assert np.abs(np.asarray(o - o_want)).max() > 100 * 5e-6
    assert np.abs(np.asarray(s - s_want)).max() > 100 * 5e-6


@pytest.mark.parametrize("on_chip, mode, t, dk, dv, scalar, want", [
    (True, "auto", 8192, 128, 128, True, "kernel"),
    (True, "auto", kda.KDA_SCAN_MIN_T, 128, 128, True, "kernel"),
    (True, "auto", kda.KDA_SCAN_MIN_T - 64, 128, 128, True, "xla"),
    (True, "auto", 512, 128, 128, False, "xla"),  # Ling: a decay a channel
    (True, "auto", 8192, 64, 128, True, "xla"),  # no whole tile of a state
    (True, "auto", 8192, 128, 96, True, "xla"),
    (True, "0", 8192, 128, 128, True, "xla"),  # kernels off
    (False, "auto", 8192, 128, 128, True, "xla"),  # off the chip
    (False, "1", 150, 16, 8, True, "kernel"),  # ... forced: interpreted
    (False, "1", 150, 16, 8, False, "xla"),
], ids=["the-cell", "the-floor", "under-the-floor", "channel", "narrow-keys",
        "narrow-values", "kernels-off", "off-the-chip", "forced",
        "forced-channel"])
def test_scan_kernel_is_chosen_by_the_shapes(on_chip, mode, t, dk, dv,
                                             scalar, want, monkeypatch):
    """``kda_chunk_choice``: the kernel for a decay a head over whole
    ``(8, 128)`` tiles of a head's state from ``KDA_SCAN_MIN_T`` tokens
    on, on the chip; elsewhere XLA's loop, but for forced kernels."""
    from cake_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "on_tpu", lambda: on_chip)
    monkeypatch.setenv("CAKE_PALLAS", mode)
    assert kda.kda_chunk_choice(t, dk, dv, scalar) == want


@pytest.mark.parametrize("valid", [(130, 40), (0, 0), (290, 7)],
                         ids=["uneven", "no-token", "whole"])
@pytest.mark.parametrize("ahead", ["hoisted", "in-the-scan"])
@pytest.mark.parametrize("form, hk, hv, scalar", [
    ("xla", *RULES[0]), ("xla", *RULES[1]), ("kernel", *RULES[1])],
    ids=RULE_IDS[:2] + ["kernel-scalar-grouped"])
def test_scan_that_stops_at_the_last_live_chunk_is_the_whole_scan(
        form, hk, hv, scalar, ahead, valid, monkeypatch):
    """``_advance`` hands ``kda_chunk`` the chunks that hold a true token
    of some row of the launch, and the serial loop runs that many of the
    300 (290) tokens' five (the last a padded one): three where the rows
    hold 130 and 40 tokens, none where they hold none, all five where one
    row fills the bucket. Against the scan over all five on the same masked
    inputs, for both decays and both placements of what a chunk makes
    ahead of the state (every chunk at once, or a chunk at a time inside
    the loop: ``HOIST_BYTES``): the state it leaves and ``o`` on every
    true row are EQUAL, and ``o`` past the last live chunk is zero. The
    same of the scan as the kernel (a two-row launch's uneven rows,
    ``live`` 0 and ``live == n``): a grid step past ``live`` does no
    product and writes zeros; what cannot be held ahead of ONE call goes
    into as many calls as it takes, the state carried between them."""
    # (a length of its own a placement and form: JAX keeps a trace by its
    # shapes, and ``HOIST_BYTES`` is read when ``kda_chunk`` is traced)
    t = (300 if ahead == "hoisted" else 290) + (form == "kernel")
    if form == "kernel":
        monkeypatch.setenv("CAKE_PALLAS", "1")
    q, k, v, g, beta, s0 = _rule_inputs(hk, hv, scalar, t=t)
    lengths = jnp.asarray(valid, jnp.int32)
    true = np.arange(t)[None] < np.asarray(valid)[:, None]  # [B, T]
    mask = jnp.asarray(true)
    masked = (jnp.where(mask.reshape(mask.shape + (1,) * (g.ndim - 2)), g, 0),
              jnp.where(mask[..., None], beta, 0))
    if ahead == "in-the-scan":
        monkeypatch.setattr(kda, "HOIST_BYTES", 0)
    o_want, s_want = kda.kda_chunk(q, k, v, *masked, s0, form=form)
    o, s = kda._advance(q, k, v, g, beta, s0, lengths, None, "gdn")
    assert kda.chunk_form_traced(t) == form
    text = str(jax.make_jaxpr(partial(kda.kda_chunk, form=form))(
        q, k, v, *masked, s0, jnp.int32(1)))
    if form == "kernel":
        # no loop of XLA's: one call over all five chunks, or one a chunk
        assert "while[" not in text
        assert text.count("pallas_call[") == (1 if ahead == "hoisted" else 5)
        jnp_o, jnp_s = kda.kda_chunk(q, k, v, *masked, s0)
        np.testing.assert_allclose(o_want, jnp_o, atol=5e-6, rtol=0)
        np.testing.assert_allclose(s_want, jnp_s, atol=5e-6, rtol=0)
    else:
        # a loop whose bound is data (and a second that zeroes ``o`` past
        # it), the chunks' inverse called ahead of them or in the first
        # one's body
        assert text.count("while[") == 2
        assert (text.index("name=_unit_lower_inverse")
                < text.index("while[")) == (ahead == "hoisted")
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_want))
    np.testing.assert_array_equal(np.asarray(o)[true], np.asarray(o_want)[true])
    swept = -(-max(valid) // kda.CHUNK) * kda.CHUNK
    assert not np.asarray(o)[:, swept:].any()
    assert np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("rows, block", [(64, 8), (64, 16), (64, 32),
                                         (40, 16), (5, 16)])
@pytest.mark.parametrize("keys", ["random", "near", "same"])
def test_unit_lower_inverse_is_the_inverse(keys, rows, block):
    """``_unit_lower_inverse`` of ``N = beta tril(K K^T, -1)`` (``beta``
    within 1e-3 of 1; unit keys drawn apart, near one another, and one key
    repeated) against ``numpy.linalg.inv(I + N)`` in float64, at the three
    sizes of diagonal block the sweep tried on a whole chunk, and where the
    chunk is no ``block * 2^m`` rows (a launch of under 64 tokens): 1.3e-7
    to 4.4e-7 measured, held to 2e-6 on entries of magnitude <= 1."""
    rs = np.random.default_rng(rows + block)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True))

    base = unit(rs.normal(size=(6, 1, 16)))
    k = {"random": unit(rs.normal(size=(6, rows, 16))),
         "near": unit(base + 0.1 * rs.normal(size=(6, rows, 16))),
         "same": np.broadcast_to(base, (6, rows, 16))}[keys]
    n = (1 - 1e-3 * rs.random((6, rows, 1))) * np.tril(
        k @ k.transpose(0, 2, 1), -1)
    got = kda._unit_lower_inverse(jnp.asarray(n, jnp.float32), block)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, np.linalg.inv(np.eye(rows) + n),
                               atol=2e-6, rtol=0)


def test_scalar_chunk_form_builds_no_channel_decay():
    """The scalar case is the cheaper one: its chunk form's program holds
    no ``[.., C, C, d_k]`` tensor (the channel case's decay), where KDA's
    does."""
    def widest(scalar):
        args = _rule_inputs(2, 4, scalar, t=128, b=1)
        text = jax.jit(kda.kda_chunk).lower(*args).as_text()
        return "64x64x16xf32" in text

    assert widest(False) and not widest(True)


@pytest.mark.parametrize("hk, hv, scalar", RULES, ids=RULE_IDS)
def test_decode_kernel_is_the_step(hk, hv, scalar):
    """``ops.pallas.kda.kda_decode`` (interpreted here) against
    ``kda_step`` for both rules: the chosen layer of the stacked state
    advances in place and no other layer is touched; the scalar decay goes
    in as one value a head."""
    from cake_tpu.ops.pallas import kda_decode

    *tokens, s0 = _rule_inputs(hk, hv, scalar, t=1, b=3)
    q, k, v, g, beta = (a[:, 0] for a in tokens)
    state = jnp.stack([s0, 0.5 * s0, 2 * s0])
    o_want, s_want = kda.kda_step(q, k, v, g, beta, state[1])
    o, s = kda_decode(q, k, v, g, beta, state, jnp.int32(1), head_block=2,
                      interpret=True)
    np.testing.assert_allclose(o, o_want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(s[1], s_want, atol=2e-6, rtol=0)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(s[2]), np.asarray(state[2]))


def test_decode_through_the_kernel_matches_reference(params, want,
                                                     monkeypatch):
    """With kernels forced (``CAKE_PALLAS=1``: interpreted off the chip)
    the decode steps of the layer loop go through ``kda_decode`` on the
    carried state (the scalar case, key heads under value heads), the
    16-token prefill before them through ``kda_chunk_scan`` and its
    epilogue (the layer's norm and gate made in the kernel), and the
    logits are still the reference's."""
    monkeypatch.setenv("CAKE_PALLAS", "1")
    assert kda.kda_decode_choice(16, 8) == "kernel"
    assert kda.kda_chunk_choice(16, 16, 8, True) == "kernel"
    step = jax.jit(_logits, static_argnums=(1,))  # traced with kernels on
    got, _ = _through_the_cache(params, TOKENS[:24], 16, 16, step=step)
    np.testing.assert_allclose(got, want[:24], atol=TIGHT, rtol=0)
    monkeypatch.setenv("CAKE_PALLAS", "0")
    assert kda.kda_decode_choice(128, 128) == "xla"


# -- the mechanisms, each against a hand-written line ----------------------------

def test_part_of_a_head_rotates_and_the_rest_stays():
    """``apply_rope`` with tables narrower than the head: channels ``(c, c
    + 4)`` of the first 8 turn by ``p * theta^(-c / 4)``, the other 8 are
    untouched; the configuration's tables are that narrow."""
    assert CFG.rope_dim == 8 and CFG.head_dim == 16
    cos, sin = rope_tables_for(CFG, 64)
    assert cos.shape == (64, 4)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 3, 16))
    got = np.asarray(apply_rope(x, cos, sin, jnp.int32(5)))
    x = np.asarray(x)
    for t in range(3):
        angle = (5 + t) * CFG.rope_theta ** (-np.arange(4) / 4.0)
        a, b = x[0, :, t, :4], x[0, :, t, 4:8]
        np.testing.assert_allclose(
            got[0, :, t, :8], np.concatenate(
                [a * np.cos(angle) - b * np.sin(angle),
                 a * np.sin(angle) + b * np.cos(angle)], -1), atol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])


def test_the_gate_sits_in_q_proj_and_multiplies_before_wo(params):
    """A full layer alone: ``wq`` gives a head's ``[q | gate]`` side by
    side, and the heads' output is ``attention * sigmoid(gate)`` before
    ``wo``: the gated block is the ungated one over the q columns, times
    the gate, by hand."""
    from cake_tpu.ops.attention import self_attention_block

    layer = jax.tree.map(lambda a: a[0], params["layers"]["gqa_moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, CFG.hidden_size))
    cos, sin = rope_tables_for(CFG, 32)
    heads, d = CFG.num_attention_heads, CFG.head_dim
    norm = (layer["q_norm"], layer["k_norm"], CFG.rms_norm_eps)

    def block(wq, wo, gated):
        cache = init_cache(tiny(num_hidden_layers=1, head_dim=d), batch=1,
                           max_seq=32)
        return self_attention_block(
            x, wq, layer["wk"], layer["wv"], wo, cache.k[0], cache.v[0], cos,
            sin, jnp.int32(0), heads, CFG.num_key_value_heads, qk_norm=norm,
            gated=gated)[0]

    got = block(layer["wq"], layer["wo"], True)
    fused = layer["wq"].reshape(CFG.hidden_size, heads, 2, d)
    eye = jnp.eye(heads * d)
    plain = block(fused[:, :, 0].reshape(CFG.hidden_size, -1), eye, False)
    gate = jax.nn.sigmoid(x @ fused[:, :, 1].reshape(CFG.hidden_size, -1))
    np.testing.assert_allclose(got, (plain * gate) @ layer["wo"], atol=1e-5)


def test_norms_are_stored_as_an_offset_from_one(params, tensors):
    """The writer stores every norm but the delta rule's output norm as ``w
    - 1`` and the loader adds the one; the program's ``rms_norm`` with the
    folded weight is the family's ``x rsqrt(mean x^2 + eps) (1 + w)`` by
    hand, and the plain norm is stored as it is."""
    ours = np.asarray(params["layers"]["gdn_moe"]["attn_norm"][1])
    stored = np.asarray(tensors["model.layers.1.input_layernorm.weight"])
    np.testing.assert_allclose(stored, ours - 1.0, atol=1e-7)
    np.testing.assert_array_equal(
        np.asarray(tensors["model.layers.1.linear_attn.norm.weight"]),
        np.asarray(params["layers"]["gdn_moe"]["o_norm"][1]))
    np.testing.assert_allclose(
        np.asarray(tensors["model.norm.weight"]),
        np.asarray(params["norm_f"]) - 1.0, atol=1e-7)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (5, 64)))
    by_hand = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * (
        1.0 + stored)
    np.testing.assert_allclose(rms_norm(jnp.asarray(x), jnp.asarray(ours),
                                        1e-6), by_hand, atol=1e-5)
    np.testing.assert_allclose(ref.norm(jnp.asarray(x), jnp.asarray(stored),
                                        1e-6), by_hand, atol=1e-5)


def test_fused_projections_are_stored_a_key_heads_group_at_a_time(params,
                                                                 tensors):
    """``in_proj_qkvz`` as stored is, for key head 0 then 1, ``[q (16) | k
    (16) | v of its two value heads (16) | z (16)]``; ours is ``[q | k | v
    | z]`` part by part; ``in_proj_ba`` likewise ``[b (2) | a (2)]``."""
    ours = np.asarray(params["layers"]["gdn_moe_2"]["w_qkvz"][2])  # layer 6
    stored = np.asarray(
        tensors["model.layers.6.linear_attn.in_proj_qkvz.weight"]).T
    assert stored.shape == ours.shape == (64, 128)
    group = stored.reshape(64, 2, 64)
    for part, (lo, hi, at) in enumerate(
            [(0, 16, 0), (16, 32, 32), (32, 48, 64), (48, 64, 96)]):
        for j in range(2):
            width = hi - lo
            np.testing.assert_array_equal(
                group[:, j, lo:hi],
                ours[:, at + j * width:at + (j + 1) * width], str(part))
    ba = np.asarray(params["layers"]["gdn_moe_2"]["w_ba"][2])
    stored = np.asarray(
        tensors["model.layers.6.linear_attn.in_proj_ba.weight"]).T
    np.testing.assert_array_equal(stored.reshape(64, 2, 4)[:, 1, :2],
                                  ba[:, 2:4])  # b of value heads 2, 3
    np.testing.assert_array_equal(stored.reshape(64, 2, 4)[:, 0, 2:],
                                  ba[:, 4:6])  # a of value heads 0, 1


def test_softmax_over_all_then_renormalised_is_the_programs_routing():
    """The reference's long form (softmax over all 16, top-4, their shares
    over their sum) against ``router_topk``'s ``routing=None`` form."""
    logits = 3 * jax.random.normal(jax.random.PRNGKey(5), (40, 16))
    idx, w = ref.route(HF, logits)
    _, got_w, got_idx = moe.router_topk(logits, jnp.eye(16), 4)
    order = jnp.argsort(got_idx, -1)
    want_order = jnp.argsort(idx, -1)
    np.testing.assert_array_equal(
        jnp.take_along_axis(got_idx, order, -1),
        jnp.take_along_axis(idx, want_order, -1))
    np.testing.assert_allclose(
        jnp.take_along_axis(got_w, order, -1),
        jnp.take_along_axis(w, want_order, -1), atol=1e-6)


# -- the share -------------------------------------------------------------------

def _expert_layer(params, cfg, h, first, count, shared):
    """The program's feed-forward of layer 0 given a told share of the
    experts, less the residual; ``shared``: with the gated shared expert
    (what every rank computes alike)."""
    layer = jax.tree.map(lambda a: a[0], params["layers"]["gdn_moe"])
    # (every rank holds the fixture's four matrices, under its own ids:
    # ``_all_experts`` is the uncut checkpoint they are a share of)
    layer = dict(layer, mlp_norm=jnp.ones_like(layer["mlp_norm"]))
    if not shared:
        layer = {n: w for n, w in layer.items() if not n.startswith("ws_")}
    share = tiny_qwen3_next(n_routed_experts=count, router_experts=16,
                            first_expert=first, max_seq_len=256)
    # (rms_norm with a weight of ones: h is handed in normed already)
    normed = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
    out, _ = llama._shared_feed_forward(layer, h, share, None, None, False,
                                        None)
    return out - h, normed


@pytest.mark.parametrize("rows", [6, 96], ids=["step-rows", "chunk-rows"])
def test_shares_add_up_to_the_uncut_layer(params, tensors, rows):
    """THE SHARE TEST: over ``ep`` = 4 the four ranks' routed parts (4 of
    16 experts each, as 128 of 512 at the published size) plus the gated
    shared expert counted ONCE equal the uncut reference's layer, and one
    rank alone (routed part and shared expert) is the reference given the
    same share."""
    h = jax.random.normal(jax.random.PRNGKey(9), (1, rows, CFG.hidden_size))
    routed = [_expert_layer(params, CFG, h, 4 * r, 4, False)
              for r in range(4)]
    one, normed = _expert_layer(params, CFG, h, 4, 4, True)
    p = "model.layers.0."
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(HF, _all_experts(params), p, normed[0])
        mine = ref.expert_layer(HF, _all_experts(params), p, normed[0],
                                held=range(4, 8))
        only_shared = ref.expert_layer(HF, _all_experts(params), p,
                                       normed[0], held=range(0))
    parts = sum(part for part, _ in routed)[0]
    np.testing.assert_allclose(parts + only_shared, whole, atol=TIGHT,
                               rtol=0)
    np.testing.assert_allclose(one[0], mine, atol=TIGHT, rtol=0)
    assert float(jnp.abs(routed[1][0]).max()) > 0.01  # a share is something
    assert float(jnp.abs(only_shared).max()) > 0.01
    assert HF["expert_share"] == {"n_routed_experts": 16, "ep": 4, "rank": 1}


def _all_experts(params):
    """The fixture's tensors as an UNCUT checkpoint stores them: the
    fixture holds 4 experts under the ids 4-7; here its 16-wide router
    meets 16 experts, the held stack four times over under ids 0-15 (each
    rank's the same four matrices)."""
    whole = tiny_qwen3_next(n_routed_experts=16, router_experts=16,
                            first_expert=0, max_seq_len=256,
                            eos_token_id=-1)
    grown = jax.tree.map(lambda a: a, params)
    grown["layers"] = {
        stack: {n: (jnp.concatenate([w] * 4, axis=1)
                    if n in ("w_gate", "w_up", "w_down") else w)
                for n, w in leaves.items()}
        for stack, leaves in params["layers"].items()}
    return latent_hf_tensors(grown, whole)
