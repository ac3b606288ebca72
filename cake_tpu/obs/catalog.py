"""Declared catalog of every metrics-registry series this tree emits.

The registry (:mod:`cake_tpu.obs.metrics`) is string-keyed and
get-or-create by design — independent modules share series without
import-order coupling. The cost of that convenience is that a typo'd
name silently forks a series: ``wire.bytes_out`` and ``wire.byte_out``
would both exist, each half-populated, and every dashboard built on the
real name goes quietly wrong. This catalog is the fix: one declaration
per series (name, kind, meaning), enforced two ways —

- statically, by the ``metrics-catalog`` checker in
  :mod:`cake_tpu.analysis` (``make lint``): every series-name literal at
  a ``counter()``/``gauge()``/``histogram()``/instrument-constructor
  call site must appear here;
- optionally at runtime: ``CAKE_OBS_STRICT=1`` (or
  ``registry().strict = True``) makes the registry refuse to create an
  undeclared series, for test rigs that want the invariant hot.

Dynamic families (per-segment, per-worker) are declared as patterns with
``*`` standing for exactly the formatted field an f-string interpolates;
the checker derives the same pattern from the f-string AST and requires
an exact match, so even dynamic names can't drift.

Adding a series is a two-line change: the call site and one entry here.
The entry is the review surface — a reviewer sees the new name, its
kind, and what it means, in one place.
"""

from __future__ import annotations

from fnmatch import fnmatchcase

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

# name -> (kind, meaning). Grouped by owning subsystem; keep each group
# sorted so diffs stay reviewable.
SERIES: dict[str, tuple[str, str]] = {
    # -- decode attention over the reservation (ops/attention, ops/mla,
    #    the engine) ------------------------------------------------------
    "attn.decode_kernel": (
        GAUGE, "what the last single-token attention traced (the decode "
               "programs') chose, by ops.attention.attend for per-head "
               "keys and values and by ops.mla.latent_attention_block "
               "for a latent cache: 1 the kernel that reads each "
               "stream's blocks up to its frontier (flash_decode, "
               "latent_decode), 0 the sweep of the reservation; absent "
               "where no program attends through either"),
    "attn.admit_blocked": (
        GAUGE, "what the last admission chunk traced of a latent layer with "
               "no indexer chose (ops.mla.latent_admit_choice, from the "
               "chunk's rows): 1 blocked by query rows (named scope "
               "mla.admit_blocked: a first chunk's own tokens by the flash "
               "prefill kernel over the expanded keys, the trace's "
               "operation latent_prefill, or a strip of query rows at a "
               "time where the chunk has history or no kernel runs), 0 the "
               "chunk's own tokens in one piece (float32 scores [B, H, T, "
               "T]); absent where no program admits through it"),
    "attn.admit_blocked_min_rows": (
        GAUGE, "the fewest rows a chunk a latent admission traced in this "
               "process took the blocked form at (ops.mla."
               "LATENT_ADMIT_BLOCK_MIN_T is the floor); absent where none "
               "did"),
    "attn.latent_decode_calls": (
        COUNTER, "(plane, decode step) calls of a plain latent attention's "
                 "single-token sweep (ops.mla; the trace's operation "
                 "latent_decode where the kernel runs): planes of latent "
                 "rows x steps, what attn.latent_rows_live is a mean over"),
    "attn.latent_rows_live": (
        COUNTER, "latent rows a decode step's sweep must read: over every "
                 "slot, decode step and plane of plain latent rows, the "
                 "stream's rows up to its frontier as dispatched (a slot "
                 "without a live stream goes out at row 0: one row)"),
    "attn.latent_admit_calls": (
        COUNTER, "(plane, dispatch) calls of the blocked latent admission's "
                 "own-chunk kernel (the trace's operation latent_prefill): "
                 "first chunks whose program took the flash form "
                 "(ops.mla.latent_admit_choice), a plane each"),
    "attn.latent_admit_pairs": (
        COUNTER, "causal (query row, row at or before it) pairs of those "
                 "calls at the rows' TRUE lengths (a bucket's padding not "
                 "counted), a plane each"),
    "attn.latent_admit_pairs_handed": (
        COUNTER, "causal pairs of those calls at the BUCKET's length (what "
                 "the kernel was handed, padding rows included), a plane "
                 "each: attn.latent_admit_pairs over it is the share of the "
                 "handed work that was a true token's"),
    "attn.kv_blocks_read": (
        COUNTER, "KV blocks (of the rows flash_decode fetches of this "
                 "cache's shape, ops.pallas.decode_block_k: 512 for a "
                 "group of query rows a KV head, over heads of 128 and "
                 "over pairs of heads of 64 alike) a layer's "
                 "decode attention reads under the kernels' block range "
                 "(ops.pallas.decode_block_range, which flash_decode and "
                 "the latent cache's latent_decode both walk): over "
                 "every slot and decode step, from the window's lower "
                 "bound up to the frontier as dispatched (a slot without "
                 "a live stream goes out at row 0: one block)"),
    "attn.kv_blocks_reserved": (
        COUNTER, "KV blocks a layer's reservation holds for the same "
                 "steps: slots x window / block x steps (a latent "
                 "cache's rows are blocks of the same 512); where the "
                 "layers run several times a token, this and "
                 "attn.kv_blocks_read count a layer's PLANES, one a pass"),
    # -- the cache and the expert layers (runtime/batch_generator) ------
    "cache.bytes": (
        GAUGE, "bytes of the serving cache as allocated (slots x window x "
               "layers x cache.row_bytes)"),
    "cache.device_bytes": (
        GAUGE, "bytes the serving cache's buffers occupy ON the device, "
               "tile padding included (each buffer's "
               "on_device_size_in_bytes, a replica counted once): "
               "cache.bytes over this is the share of the reservation "
               "that holds values, 1 where every row fills its tiles and "
               "1/2 where a 64-wide bfloat16 row is padded to 128 lanes; "
               "absent where the runtime does not say"),
    "cache.token_bytes": (
        GAUGE, "bytes the allocated row buffers hold for one token of one "
               "stream, every plane of it (cache.bytes less state and "
               "rings, over slots x window; cache.layer_planes x "
               "cache.row_bytes): what an int8 cache or a plane shared by "
               "the passes would move, and what sets how many streams fit"),
    "cache.layer_planes": (
        GAUGE, "planes of the serving cache's row buffers (their leading "
               "axis): the layers that keep rows, times the passes where "
               "the layers run several times a token (plane u x layers + "
               "i for layer i in pass u)"),
    "model.loop_passes": (
        GAUGE, "times the layer loop runs its layers a token over one set "
               "of weights (LlamaConfig.total_ut_steps; named scopes "
               "loop.pass and loop.norm, the norm that closes a pass); 1 "
               "where a layer runs once"),
    "model.hc_mult": (
        GAUGE, "hidden vectors a token's residual state holds between "
               "sub-layers (LlamaConfig.hc_mult: manifold-constrained "
               "hyper-connections, ops/hyper.py; named scopes mhc.coeff, "
               "the statistics, the product with phi, the sigmoids and the "
               "Sinkhorn rounds, mhc.pre and mhc.post, the two mixes); 1 "
               "where the residual is the plain one"),
    "resid.token_bytes": (
        GAUGE, "bytes one token's residual state holds between "
               "sub-layers, in the serving type (model.hc_mult x the "
               "hidden size x the type's bytes): what every sub-layer "
               "reads and writes of the stream a row"),
    "cache.row_bytes": (
        GAUGE, "bytes the cache holds for one token of one layer that "
               "keeps every row (of one PLANE where a layer has one a "
               "pass), a mean over the layers of that ONE kind, "
               "from the buffers allocated (their bytes / such layers x "
               "slots x window): per-head keys and values (with an int8 "
               "cache's scales), or latent attention's one shared row; a "
               "window layer's ring is no part of it (cache.rows_bytes)"),
    "cache.ring_rows": (
        GAUGE, "rows R a window layer's ring holds for a stream, whatever "
               "the capacity (LlamaConfig.ring_rows: position p at row "
               "p % R); absent where no layer attends through a ring"),
    "cache.rows_bytes": (
        GAUGE, "bytes of the serving cache's row buffers of both kinds, "
               "from the buffers allocated: the full layers' rows at the "
               "capacity and the window layers' rings; absent where no "
               "layer attends through a ring"),
    "cache.rows_bytes_full": (
        GAUGE, "bytes the row buffers would hold were every window layer "
               "a full one at the capacity (the full layers' bytes x all "
               "attention layers / full layers): cache.rows_bytes over "
               "this is what the rings leave of the cache"),
    "attn.layers_swa": (
        GAUGE, "layers that attend through a sliding window over a ring "
               "(named scope attn.swa); absent where window and full "
               "layers are not mixed by layer"),
    "attn.layers_full": (
        GAUGE, "layers that attend over every row, beside window layers "
               "(named scope attn.full); attn.kv_blocks_read and "
               "attn.kv_blocks_reserved count one of THESE"),
    "attn.ring_rows_live": (
        COUNTER, "rows of the window layers' rings that hold a key the "
                 "step's query may see, over every slot, decode step and "
                 "window layer: min(position + 1, sliding_window) a row "
                 "of the batch as dispatched (a slot without a live "
                 "stream goes out at row 0: one row)"),
    "attn.ring_rows_swept": (
        COUNTER, "rows of the window layers' rings the same steps' "
                 "attention reads: the ring whole, cache.ring_rows a slot, "
                 "step and window layer (ops.attention."
                 "window_attention_block sweeps it in XLA whatever the "
                 "stream holds); live over swept is what a step that read "
                 "live rows alone would save"),
    "rope.tables": (
        GAUGE, "pairs of rotary tables (cos, sin) the last program traced "
               "carries (ops.rope.rope_tables_for): 0 a model with no "
               "position embedding, 1 one rotation for every layer that "
               "rotates, 2 a rotation a layer KIND (LlamaConfig.layer_rope: "
               "window layers rotated plainly beside full layers under "
               "YaRN)"),
    "load.tensors_skipped": (
        COUNTER, "tensors a checkpoint stores that are no part of the "
                 "served model and were not read (a next-token prediction "
                 "block's mtp.*, layers past the served depth), by the "
                 "loader of a model of several layer stacks"),
    "cache.state_bytes": (
        GAUGE, "bytes of the serving cache that are recurrent state "
               "(delta-rule or state-space layers' float32 state and "
               "convolution tails; a gated short convolution's tails "
               "alone, which has no state), from the buffers allocated; 0 "
               "where no layer holds one"),
    "cache.state_bytes_per_stream": (
        GAUGE, "cache.state_bytes / slots: what a stream's recurrent "
               "state and convolution tails cost whatever its length"),
    "conv.state_resets": (
        COUNTER, "admissions that started a slot's short-convolution "
                 "tails from zero (a fresh staging row, spliced over what "
                 "the slot's last stream left; named scope mixer.conv)"),
    "kda.state_resets": (
        COUNTER, "admissions that started a slot's delta-rule state "
                 "(either rule of ops/kda.py: KDA's or the scalar-gated "
                 "one) from zero (a fresh staging row, spliced over what "
                 "the slot's last stream left)"),
    # -- a learned sparse attention over the latent cache (ops/dsa.py, the
    #    engine; named scopes dsa.index, dsa.select, dsa.attend in both
    #    programs; the trace's operations dsa_index, then a decode
    #    program's one of two forms (ops.dsa.attend_form_choice): the
    #    sweep's dsa_select and dsa_attend over the carried buffer, or the
    #    gather's sort and gather, which are XLA's, and dsa_attend over the
    #    copies; dsa_prefill_select, dsa_prefill_attend (admission)) -------
    "cache.index_row_bytes": (
        GAUGE, "bytes the index buffer holds for one token of one layer "
               "(a sparse attention's one index key, normed and rotated, "
               "in the serving type; KVCache.index): counted in "
               "cache.token_bytes, not in cache.row_bytes"),
    "dsa.admit_calls": (
        COUNTER, "(layer, dispatch) calls of an admission launch's sparse "
                 "attention path: the layers under it, a dispatch (what "
                 "the dsa.admit_* counts are a mean over)"),
    "dsa.admit_pairs_attended": (
        COUNTER, "of dsa.admit_pairs_scored, the pairs a query row attends: "
                 "min(t + 1, index_topk) a row, a layer"),
    "dsa.admit_pairs_scored": (
        COUNTER, "(query row, row at or before it) pairs the indexers of an "
                 "admission launch score at the rows' TRUE lengths: n (n + "
                 "1) / 2 a prompt of n tokens, a layer under the sparse "
                 "attention (the kernels compute a bucket's whole lower "
                 "triangle)"),
    "dsa.admit_rows": (
        COUNTER, "rows an admission launch's programs were handed under a "
                 "learned sparse attention: the bucket's, times the "
                 "launch's staging rows, a dispatch"),
    "dsa.admit_rows_true": (
        COUNTER, "of dsa.admit_rows, the rows that were prompt tokens (the "
                 "rest a bucket's padding, which lies past every frontier "
                 "and is never chosen)"),
    "dsa.attend_sweep": (
        GAUGE, "what ops.dsa.attend_form_choice chose for the last decode "
               "step's sparse attention traced (the decode programs'; by "
               "the buffer's rows): 1 the sweep (the choice a threshold, "
               "dsa_select, and dsa_attend over the carried buffer to each "
               "frontier under the mask: no sort, no gather), 0 the gather "
               "(lax.top_k, the chosen rows gathered, dsa_attend over the "
               "copies)"),
    "dsa.decode_calls": (
        COUNTER, "(layer, step) calls of the decode step's sparse attention "
                 "path: the layers under it x the steps, a dispatch (what "
                 "dsa.rows_live and dsa.rows_selected are a mean over: a "
                 "capture's trace counts the calls it holds)"),
    "dsa.index_kernel": (
        GAUGE, "what ops.dsa.decode_index_scores chose for the last "
               "single-token index scoring it traced (the decode "
               "programs'): 1 the kernel that reads each stream's index "
               "keys up to its frontier (dsa_index), 0 XLA's product over "
               "the whole buffer, masked"),
    "dsa.index_topk": (
        GAUGE, "rows a query attends at most under the model's learned "
               "sparse attention (LlamaConfig.index_topk); absent where the "
               "model has none"),
    "dsa.rows_live": (
        COUNTER, "rows a decode step's indexers score: over every slot, "
                 "decode step and layer under the sparse attention, the "
                 "stream's rows up to its frontier as dispatched (a slot "
                 "without a live stream goes out at row 0: one row)"),
    "dsa.rows_read": (
        COUNTER, "latent rows a decode step's attention fetches, over every "
                 "slot, step and layer under the sparse attention, from the "
                 "positions as dispatched: under the sweep the whole blocks "
                 "to the frontier ((frontier // block + 1) x block, the "
                 "unchosen rows among them read and masked), under the "
                 "gather the chosen rows (dsa.rows_selected). Over "
                 "dsa.rows_selected: what the sweep pays in bytes for "
                 "fetching blocks and not rows"),
    "dsa.rows_selected": (
        COUNTER, "of dsa.rows_live, the rows a step attends: min(frontier + "
                 "1, index_topk) a stream, step and layer: the latent rows "
                 "a step's softmax runs over (the gather fetches these "
                 "alone; the sweep fetches dsa.rows_read and masks the "
                 "rest), where a full sweep would attend dsa.rows_live"),
    # -- EVA attention: a window that resets and the summaries of those
    #    before it (ops/eva.py, ops/pallas/eva.py, the engine; named scopes
    #    eva_decode, eva_summarise, eva_prefill, attn.eva) ------------------
    "attn.eva_decode_calls": (
        COUNTER, "(layer, decode step) calls of EVA attention's single-token "
                 "form (the trace's operation eva_decode where the kernel "
                 "runs): EVA layers x steps, what attn.eva_window_rows_live, "
                 "attn.eva_summary_rows_visible and attn.eva_rows_read are "
                 "means over"),
    "attn.eva_decode_kernel": (
        GAUGE, "what the last EVA decode program traced chose "
               "(ops.eva.eva_decode_choice, from the buffers' shapes): 1 the "
               "kernel that reads the ring and the summary plane each to "
               "its own frontier, 0 two masked products over both buffers "
               "whole, merged by their statistics; absent where no program "
               "attends through it"),
    "attn.eva_rows_read": (
        COUNTER, "rows (ring and summary together) a decode step's EVA "
                 "attention fetches, over every slot, step and layer, from "
                 "the positions as dispatched: under the kernel the whole "
                 "blocks to each frontier (ops.pallas.eva.eva_block_counts), "
                 "else both buffers whole"),
    "attn.eva_summary_rows_visible": (
        COUNTER, "summary rows a decode step's query attends: over every "
                 "slot, step and EVA layer, (position // window_size) x "
                 "(window_size // chunk_size): every chunk of every window "
                 "completed before the query's own (a slot without a live "
                 "stream goes out at row 0: none)"),
    "attn.eva_window_rows_live": (
        COUNTER, "ring rows a decode step's query attends: over every slot, "
                 "step and EVA layer, position % window_size + 1: the rows "
                 "of the query's own window at or before it (1 right after "
                 "a reset)"),
    "cache.eva_summary_rows": (
        GAUGE, "summary rows a stream holds a layer under EVA attention: "
               "capacity // chunk_size (one row for every chunk_size "
               "positions); absent for every other model"),
    "cache.eva_window_rows": (
        GAUGE, "rows of an EVA layer's ring a stream: window_size, whatever "
               "the capacity; absent for every other model"),
    "eva.chunks_summarised.admit": (
        COUNTER, "chunks an admission dispatch summarises: EVA layers x the "
                 "launch's rows x bucket // chunk_size (every chunk of the "
                 "bucket: a bucket's padding takes no part in a summary but "
                 "its chunks are computed)"),
    "eva.chunks_summarised.step": (
        COUNTER, "chunks the decode steps summarise: EVA layers x slots x "
                 "steps (each step refreshes the current chunk's row from "
                 "the ring: no branch in the layer body)"),
    "eva.window_resets": (
        COUNTER, "windows that reset under a decode step: over every slot, "
                 "step and EVA layer, the steps whose position is a "
                 "multiple of window_size (their query sees ONE ring row "
                 "and window_size // chunk_size more summaries than the "
                 "step before)"),
    "delta.chunks_swept": (
        COUNTER, "chunks of ops.kda.CHUNK tokens that the delta-rule "
                 "layers' admission scans ran through: delta-rule layers x "
                 "a launch's rows x ceil(its longest row's true tokens / "
                 "64), a dispatch (the scan is serial over a launch's "
                 "rows and stops at the last chunk that holds a true "
                 "token of any of them)"),
    "delta.chunks_live": (
        COUNTER, "of delta.chunks_swept, the chunks that held a true "
                 "token: the same with each row's own length in place of "
                 "the longest's"),
    "delta.chunks_kernel": (
        COUNTER, "of delta.chunks_swept, the chunks of the dispatches whose "
                 "program runs a layer's serial scan as ONE Pallas call "
                 "(kda_chunk_scan: a block of heads' state in VMEM from "
                 "chunk to chunk) where the others run XLA's loop "
                 "(ops.kda.kda_chunk_choice, by the bucket's tokens and "
                 "the rule)"),
    "delta.chunk_kernel": (
        GAUGE, "1 once an admission program that holds the scan kernel has "
               "been traced (ops.kda.kda_chunk_choice said so for its "
               "bucket; set at trace time), 0 while every traced bucket's "
               "scan is XLA's loop; absent where no program holds a "
               "delta-rule layer"),
    "ssm.decode_kernel": (
        GAUGE, "what ops.mamba.mamba_mixer_block chose for the last "
               "single-token state-space step it traced (the decode "
               "programs'): 1 the kernel that reads and writes each slot's "
               "state once, in place, 0 XLA's fusions; absent where no "
               "program holds a state-space layer"),
    "ssm.state_resets": (
        COUNTER, "admissions that started a slot's state-space state from "
                 "zero (a fresh staging row, spliced over what the slot's "
                 "last stream left)"),
    "moe.admit_rows": (
        COUNTER, "prompt rows (the bucket's, padding included) that "
                 "admission dispatches of an expert model ran through "
                 "the expert block"),
    "moe.admit_rows_sorted": (
        COUNTER, "of moe.admit_rows, those of dispatches whose program "
                 "took the sorted form (only the routed pairs on held "
                 "experts computed), by what the expert block recorded "
                 "when that bucket's program was traced"),
    "moe.decode_sorted": (
        GAUGE, "1 where the expert calls of the decode program (one row a "
               "slot) took the sorted form when it was traced "
               "(ops.moe.expert_form): a step reads the experts some row "
               "chose and no others; 0: every held expert"),
    "moe.decode_steps": (
        COUNTER, "decode steps whose routed pairs were counted"),
    "moe.experts_hit": (
        COUNTER, "distinct held experts that some row of a decode step "
                 "chose, summed over expert layers and steps: counted on "
                 "the device over every row that goes through the "
                 "program, a dead slot's too"),
    "moe.local_pairs": (
        COUNTER, "(row, chosen expert) pairs of decode steps that fell on "
                 "experts held here: counted on the device a batch row, "
                 "added up over the rows live at dispatch"),
    "moe.routed_pairs": (
        COUNTER, "(row, chosen expert) pairs decode steps routed over all "
                 "the router's experts: live rows x top-k x expert layers"),
    "moe.zero_pairs": (
        COUNTER, "(row, chosen output) pairs decode steps routed to the "
                 "router's zero-compute outputs (LlamaConfig."
                 "zero_expert_num: each returns its input; named scope "
                 "moe.zero, the identity part added once behind the "
                 "experts' sum), counted on the device a batch row and "
                 "added up over the rows live at dispatch: of "
                 "moe.routed_pairs, the pairs that cost no expert; 0 where "
                 "the router scores experts alone"),
    "model.planes_a_layer": (
        GAUGE, "cache planes a layer keeps (Family.planes_a_layer): 2 where "
               "a layer is a shortcut-connected double layer (named scopes "
               "scmoe.first: the first attention, the expert block whose "
               "result is held back, the first dense feed-forward; "
               "scmoe.second: the second attention and feed-forward and "
               "the late add), and attn.kv_blocks_* then count both; 1 "
               "elsewhere"),
    "moe.sorted_pair_rows": (
        COUNTER, "(row, chosen expert) pair rows handed to expert calls "
                 "that took the sorted form: rows x top-k a call and "
                 "expert layer (an admission bucket's padding rows "
                 "included), of decode steps and admission dispatches, "
                 "counted on the device"),
    "moe.sorted_pair_rows_live": (
        COUNTER, "of moe.sorted_pair_rows, the rows of the row tiles "
                 "those calls touched (the tiles that hold a true token's "
                 "pair on a held expert: live tiles x the row tile; the "
                 "others, a bucket's padding among them, are neither "
                 "read nor written), counted on the device"),
    "moe.gather_rows_fetched": (
        COUNTER, "of moe.sorted_pair_rows_live, the rows of admission "
                 "dispatches whose sorted calls gathered them by address "
                 "(ops.moe.gather_form: a long bucket's; a short one's "
                 "and a decode step's are picked by a one-hot product)"),
    "moe.gather_fetch_min_rows": (
        GAUGE, "the fewest rows of a traced sorted call that gathered its "
               "live tiles' rows by address (ops.moe.gather_form, set at "
               "trace time; 0 while none has)"),
    "moe.sorted_from_rows": (
        GAUGE, "the fewest rows of a traced call whose expert block took "
               "the sorted form (ops.moe.expert_form, set at trace time; "
               "0 while none has)"),
    # -- constrained decoding (cake_tpu/constrain) -----------------------
    "constrain.dead_ends": (
        COUNTER, "constrained streams retired at a grammar dead end"),
    "constrain.fsm_cache_hits": (
        COUNTER, "token-DFA compiles served from memo/disk cache"),
    "constrain.fsm_cache_misses": (
        COUNTER, "token-DFA compiles that ran the vocab walk"),
    "constrain.fsm_compile_ms": (
        HISTOGRAM, "grammar -> token-DFA compile wall time"),
    # -- disaggregated prefill/decode (cake_tpu/disagg) ------------------
    "disagg.exports": (
        COUNTER, "stream snapshots exported (prefill handoffs + session "
                 "suspends)"),
    "disagg.handoffs": (
        COUNTER, "gateway two-stage routes completed (prefill -> "
                 "transfer -> decode resume)"),
    "disagg.import_aborts": (
        COUNTER, "imports dropped unresumed (TTL expiry, cancelled "
                 "resume, pool rebuild)"),
    "disagg.imports": (
        COUNTER, "snapshots whose pages landed in the local pool"),
    "disagg.inflight": (
        GAUGE, "KV transfers in flight on this replica (outgoing sends "
               "+ imports awaiting resume) — the /healthz "
               "kv_transfers_inflight field"),
    "disagg.reprefills": (
        COUNTER, "gateway fallbacks that re-prefilled a request after a "
                 "tiered-path failure"),
    "disagg.resumes": (
        COUNTER, "imported streams attached to a slot and decoding"),
    "disagg.transfer_bytes": (
        HISTOGRAM, "snapshot payload size per completed transfer"),
    "disagg.transfer_failures": (
        COUNTER, "transfers that exhausted their retry budget or were "
                 "rejected"),
    "disagg.transfer_ms": (
        HISTOGRAM, "export-to-ACK wall time per completed transfer"),
    # -- an admission's stages, a block's period, and the order of work at
    # -- a block boundary (runtime/batch_generator) -----------------------
    "engine.admissions_landed": (
        COUNTER, "prompt admissions (enqueue()) whose first token was "
                 "fetched and whose splice was enqueued: one observation "
                 "of each engine.admit_*_ms; imports, attaches and the "
                 "synchronous admit() count nothing"),
    "engine.admit_launches": (
        COUNTER, "prompt admission programs launched: one for the head of "
                 "the arrival queue and every plain prompt that waited "
                 "behind it with a free slot (BatchGenerator."
                 "_start_arrival); admissions_landed / admit_launches is "
                 "how many admissions a launch carries"),
    "engine.admit_land_ms": (
        HISTOGRAM, "per landed admission: _finish_admission entered -> its "
                   "first token on the host (what is left of the prefill, "
                   "the sampling program, the fetch); every live stream "
                   "waits, the device works"),
    "engine.admit_launch_wait_ms": (
        HISTOGRAM, "per landed admission: enqueue() -> its launch's "
                   "first prefill dispatch returned (no free slot, or "
                   "another launch staged; the prefix match and the "
                   "staging rows)"),
    "engine.admit_rows_wait_ms": (
        HISTOGRAM, "per landed admission: first prefill dispatch returned "
                   "-> _finish_admission entered (the running block and "
                   "its rows going out; the prefill runs on the device "
                   "meanwhile; a chunked admission's later chunks)"),
    "engine.admit_to_splice_ms": (
        HISTOGRAM, "per landed admission: max(0, the splice program "
                   "enqueued - first token on the host): host work while "
                   "the device has nothing to run; 0 where the splice "
                   "left before the token was fetched (every landing but "
                   "a guided arrival's)"),
    "engine.block_period_clear_ms": (
        HISTOGRAM, "engine.block_period_ms of the periods in which no "
                   "admission landed: the block's steps and the boundary"),
    "engine.block_period_ms": (
        HISTOGRAM, "a decode block's landing less the previous block's "
                   "(what a live stream waits for its next block of "
                   "tokens), once per landed block but the first of a "
                   "busy stretch: an idle engine's wait for a request, "
                   "single steps and speculative rounds close no period"),
    "engine.boundaries": (
        COUNTER, "landed decode blocks after which the engine enqueued a "
                 "next device program (a block, or an arrival's prefill)"),
    "engine.boundaries_ahead": (
        COUNTER, "of those, the ones whose next program was enqueued "
                 "before any row of the landed block was handed out (all "
                 "of them, but where a live guide, batched speculation or "
                 "a chunked admission keeps the host between steps)"),
    "engine.boundary_emit_ms": (
        HISTOGRAM, "first part of a boundary at which the device WAITED "
                   "(a later step() than the landing one enqueued its "
                   "next program; none where an admission launched while "
                   "the block ran was that program already, so the mean "
                   "is a clear boundary's and an idle landing's): the "
                   "block's fetch returned -> the landing step() "
                   "returned, i.e. recording the block's rows"),
    "engine.boundary_enqueue_ms": (
        HISTOGRAM, "third part of such a boundary: the entry of the "
                   "step() that enqueues -> its program call returned "
                   "(the step's preamble, an admission tick, the "
                   "frontiers' uploads, the call); the three parts add "
                   "up to engine.boundary_ms's observation less what "
                   "that step() does after the enqueue"),
    "engine.boundary_ms": (
        HISTOGRAM, "host time from a decode block's fetch returning to "
                   "the return of the step() call that enqueued the "
                   "device's next program (so a row's hand-out after the "
                   "enqueue is in it), once per landed block: the "
                   "device has nothing to run while it lasts (next to "
                   "nothing where an arrival's prefill was launched while "
                   "the block still ran)"),
    "engine.boundary_pass_ms": (
        HISTOGRAM, "second part of such a boundary: the landing step() "
                   "returned -> the step() that enqueues was entered: "
                   "the caller's pass between the two (the scheduler's "
                   "deliver, retire, pass_rest, sched_admit)"),
    "engine.landing_counts_fetch_ms": (
        HISTOGRAM, "an expert model's counts of landed decode blocks "
                   "(what moe.local_pairs and moe.experts_hit add up): "
                   "the time of their fetches, once per step() that "
                   "fetched some. They are fetched at the return of a "
                   "step() that leaves no boundary open, i.e. under the "
                   "device's next program, not while it waits for one; "
                   "nothing where no decode program counts"),
    "engine.landings_ahead": (
        COUNTER, "landings whose splice AND the device's next program "
                 "(the next arrival's prefill, else the next decode "
                 "block) were enqueued before the first token was "
                 "fetched; engine.admit_launches is its denominator "
                 "(not a guided arrival's, not admit()'s, none under "
                 "batched speculation or a live guide)"),
    "engine.landings_before_rows": (
        COUNTER, "landings whose device half (the first tokens' sampler, "
                 "the splice, the device's next program) was enqueued "
                 "while rows recorded before it were still to be handed "
                 "out: the stream's install and its first token followed "
                 "those rows; engine.admit_launches is its denominator "
                 "(not a guided arrival's, not admit()'s, none under "
                 "batched speculation, in the paged layout, or where no "
                 "staging row fits beside the landing's)"),
    # -- gateway (multi-replica routing front door) ----------------------
    "gateway.added_ms": (
        HISTOGRAM, "gateway-added latency ahead of the backend "
                   "(route + connect + request send, failed attempts "
                   "included)"),
    "gateway.backends_up": (GAUGE, "backends currently routable (UP)"),
    "gateway.breaker_open": (
        GAUGE, "DOWN backends whose circuit breaker is holding probes"),
    "gateway.deregistrations": (
        COUNTER, "explicit fleet leaves (the SIGTERM drain path's "
                 "goodbye; pins the member DRAINING)"),
    "gateway.lease_expired": (
        COUNTER, "registration leases that missed their renewal window "
                 "(demotes through the probe hysteresis, never an "
                 "instant delete)"),
    "gateway.queued_admissions": (
        COUNTER, "saturated-fleet requests held in the bounded admission "
                 "queue instead of being shed"),
    "gateway.registrations": (
        COUNTER, "fleet registration/renewal POSTs accepted (dynamic "
                 "membership leases)"),
    "gateway.rejected": (
        COUNTER, "requests refused at the gateway (draining / no backend "
                 "up)"),
    "gateway.requests": (COUNTER, "completions requests accepted"),
    "gateway.retries": (
        COUNTER, "transparent re-routes after a backend failure or 429"),
    "gateway.route_prefix_fallback": (
        COUNTER, "prefix-affinity routes that fell back to p2c"),
    "gateway.route_prefix_hits": (
        COUNTER, "requests landed on their prefix-preferred replica"),
    "gateway.saturated": (
        COUNTER, "429s propagated because every UP backend was saturated"),
    "gateway.shed": (
        COUNTER, "requests shed at the front door under fleet saturation "
                 "(429 with a fleet-derived Retry-After)"),
    # -- engine profiling plane (cake_tpu/obs/prof) ----------------------
    "prof.compiles": (
        COUNTER, "XLA backend compiles observed process-wide "
                 "(jax.monitoring duration events)"),
    "prof.mem_device_bytes": (
        GAUGE, "device memory live bytes (backends exposing "
               "memory_stats; absent elsewhere)"),
    "prof.mem_device_peak_bytes": (
        GAUGE, "device memory high-water mark in bytes"),
    "prof.mem_host_peak_bytes": (
        GAUGE, "host process peak RSS (VmHWM)"),
    "prof.mem_host_rss_bytes": (
        GAUGE, "host process resident set size (VmRSS)"),
    "prof.retraces": (
        COUNTER, "steady-state decode-phase compiles — retrace findings "
                 "(warn; raise under CAKE_PROF_STRICT=1)"),
    "prof.sampled_steps": (
        COUNTER, "engine steps that recorded a sampled phase breakdown"),
    "prof.slow_pass_ms": (
        COUNTER, "total length (ms) of serve-scheduler passes that took "
                 "longer than obs/prof.SLOW_PASS_MS: the time stalls "
                 "cost (their parts are in /debug/prof slow_passes)"),
    "prof.slow_passes": (
        COUNTER, "serve-scheduler passes longer than "
                 "obs/prof.SLOW_PASS_MS"),
    # -- speculative decoding acceptance (runtime/speculative) -----------
    "spec.accept_rate_ema": (
        GAUGE, "EMA of accepted-proposal fraction per round — the "
               "adaptive-spec_k control signal"),
    "spec.accepted": (
        COUNTER, "draft proposals accepted by verification rounds"),
    "spec.proposed": (
        COUNTER, "draft tokens proposed to verification rounds"),
    # -- paged KV pool (cake_tpu/kvpool) ---------------------------------
    "kvpool.admit_defers": (
        COUNTER, "admissions deferred waiting for free pages"),
    "kvpool.cow_copies": (
        COUNTER, "private copy-on-write materializations of partially "
                 "shared prefix pages"),
    "kvpool.evictions": (
        COUNTER, "prefix-tree page claims evicted to refill the free "
                 "list"),
    "kvpool.pages_free": (GAUGE, "pool pages on the free list"),
    "kvpool.pages_pinned": (
        GAUGE, "pages held by in-flight KV-transfer pins (claims outside "
               "stream tables and the prefix tree)"),
    "kvpool.pages_shared": (
        GAUGE, "physical pages referenced more than once (streams and/or "
               "the prefix tree)"),
    "kvpool.prefix_nodes": (
        GAUGE, "prefix-tree nodes (cached shared-prefix pages)"),
    # -- generator (local single-stream decode) --------------------------
    "generator.decode_ms": (HISTOGRAM, "per-token decode latency"),
    "generator.prefill_ms": (HISTOGRAM, "prompt prefill latency"),
    # -- master (distributed decode walk) --------------------------------
    "master.failovers": (COUNTER, "recoveries that landed on a replica"),
    "master.recoveries": (COUNTER, "successful mid-stream reconnect+replay"),
    "master.tokens_generated": (COUNTER, "tokens emitted by the master"),
    # -- recovery/backoff plane ------------------------------------------
    "recover.backoff_ms": (COUNTER, "total backoff sleep during recovery"),
    # -- request-scoped tracing (cake_tpu/obs/reqtrace) ------------------
    "reqtrace.header_errors": (
        COUNTER, "malformed inbound traceparent headers (fell back to a "
                 "fresh mint)"),
    "reqtrace.requests": (
        COUNTER, "distinct trace ids landed in the per-process request "
                 "log"),
    "reqtrace.stitched": (
        COUNTER, "remote tier timelines merged into the local tracer"),
    # -- SLO accounting (per-class TTFT/TPOT targets) --------------------
    "slo.bad": (COUNTER, "requests that missed their TTFT/TPOT targets"),
    "slo.burn_long": (
        GAUGE, "long-window (600 s) error-budget burn rate (bad-fraction "
               "/ budget; >1 = burning faster than the objective allows)"),
    "slo.burn_short": (
        GAUGE, "short-window (60 s) error-budget burn rate"),
    "slo.good": (COUNTER, "requests that met their TTFT/TPOT targets"),
    # -- serving plane (HTTP API + scheduler) ----------------------------
    "serve.admit_to_first_ms": (
        HISTOGRAM, "handed to the engine -> first token emitted, per "
                   "request (admission, prefill, the block it joined); "
                   "serve.queue_wait_ms + this = serve.ttft_ms"),
    "serve.cancelled": (COUNTER, "requests cancelled (client went away)"),
    "serve.completed": (COUNTER, "requests that got their tokens"),
    "serve.decode_dispatch_ms": (HISTOGRAM, "batched decode dispatch"),
    "serve.migrated_sessions": (
        COUNTER, "live sessions re-homed to a sibling replica by a "
                 "drain-migration (rolling restart)"),
    "serve.preemptions": (
        COUNTER, "batch streams spilled to host RAM so a higher-class "
                 "arrival could take the slot (SLO scheduling)"),
    "serve.queue_depth": (GAUGE, "requests waiting for admission"),
    "serve.queue_wait_ms": (
        HISTOGRAM, "submit -> handed to the engine, per request (the "
                   "wait for a slot and for undelivered rows)"),
    "serve.rejected": (COUNTER, "submissions refused at the queue bound"),
    "serve.resume_ms": (
        HISTOGRAM, "preempted-stream resume time (spill take through "
                   "replay + attach queued)"),
    "serve.spill_bytes": (
        GAUGE, "host-RAM bytes held by spilled stream snapshots"),
    "serve.spill_pages": (
        GAUGE, "KV pages represented by spilled stream snapshots"),
    "serve.stop_matches": (COUNTER, "streams ended by a stop-string match"),
    "serve.tenant_throttled": (
        COUNTER, "admissions where an over-budget tenant's arrival was "
                 "queued behind in-budget traffic of its class"),
    "serve.timeouts": (COUNTER, "requests expired (queued or mid-stream)"),
    "serve.tokens_emitted": (COUNTER, "tokens emitted by the batch engine"),
    "serve.tpot_ms": (HISTOGRAM, "inter-token gap per serving request"),
    "serve.ttft_ms": (HISTOGRAM, "submit-to-first-token per request"),
    # -- wire transport ---------------------------------------------------
    "wire.bytes_in": (COUNTER, "frame payload bytes received"),
    "wire.bytes_out": (COUNTER, "frame payload bytes sent"),
    "wire.codec_bytes_encoded": (COUNTER, "activation bytes after codec"),
    "wire.codec_bytes_raw": (COUNTER, "activation bytes before codec"),
    "wire.crc_failures": (COUNTER, "frames dropped on CRC mismatch"),
    "wire.deserialize_ms": (HISTOGRAM, "reply tensor decode time"),
    "wire.frame_bytes": (HISTOGRAM, "payload size distribution"),
    "wire.frames_in": (COUNTER, "frames received"),
    "wire.frames_out": (COUNTER, "frames sent"),
    "wire.serialize_ms": (HISTOGRAM, "request tensor encode time"),
    "wire.timeouts": (COUNTER, "recv/send deadlines expired"),
    # -- worker (remote segment server) ----------------------------------
    "worker.bytes_in": (COUNTER, "op payload bytes received"),
    "worker.bytes_out": (COUNTER, "op payload bytes sent"),
    "worker.forward_ms": (HISTOGRAM, "steady-state decode forward time"),
    "worker.ops": (COUNTER, "ops handled"),
    "worker.prefill_ms": (HISTOGRAM, "prefill/replay forward time"),
    "worker.warmup_ms": (GAUGE, "per-shape XLA compile warmup"),
    # -- cluster aggregation (master-side merged view) -------------------
    "cluster.forward_p99_median_ms": (GAUGE, "median of worker p99s"),
    "cluster.stragglers": (GAUGE, "workers currently flagged"),
    "cluster.workers_up": (GAUGE, "workers answering scrapes"),
}

# Dynamic families: ``*`` stands for exactly one interpolated field. The
# static checker requires an f-string series name to reduce to one of
# these patterns verbatim; fnmatch covers literal names that happen to
# land inside a family.
DYNAMIC: dict[str, tuple[str, str]] = {
    "gateway.*.errors": (
        COUNTER, "per-backend proxy failures (connect / 5xx / stream)"),
    "gateway.*.requests": (COUNTER, "per-backend routed requests"),
    "gateway.*.retries": (
        COUNTER, "per-backend requests re-routed away after a failure"),
    "gateway.*.state": (
        GAUGE, "per-backend health state (2 UP / 1 DRAINING / 0 DOWN)"),
    "master.segment*.decode_ms": (
        HISTOGRAM, "per-segment steady-state forward time"),
    "master.segment*.warmup_ms": (
        GAUGE, "per-segment first-call compile+prefill"),
    "cluster.*.*": (
        GAUGE, "per-worker merged health/traffic fields (ClusterScraper)"),
    "prof.phase_ms.*": (
        HISTOGRAM, "per-phase wall ms inside sampled engine steps "
                   "(admit with admit_launch/admit_land inside it, "
                   "pages/guide/dispatch/sync/sync_counts/emit and the "
                   "spec_* phases) and of the scheduler's pass around "
                   "them (idle_park/sched_admit/deliver/retire/"
                   "pass_rest) — obs/prof.PHASES"),
    "serve.ttft_ms.*": (
        HISTOGRAM, "per-class submit-to-first-token (serve.session "
                   "CLASSES — the SLO rows split interactive from "
                   "batch)"),
    "serve.tpot_ms.*": (
        HISTOGRAM, "per-class inter-token gap"),
}


def is_declared(name: str) -> bool:
    """True if ``name`` — a concrete series name OR a ``*`` pattern
    derived from an f-string — is covered by the catalog."""
    if name in SERIES or name in DYNAMIC:
        return True
    return any(fnmatchcase(name, pat) for pat in DYNAMIC)


def kind_of(name: str) -> str | None:
    """Declared kind for a concrete name (None if undeclared)."""
    if name in SERIES:
        return SERIES[name][0]
    for pat, (kind, _) in DYNAMIC.items():
        if fnmatchcase(name, pat):
            return kind
    return None


def all_names() -> list[str]:
    """Every declared name and pattern (sorted) — the docs/table view."""
    return sorted(SERIES) + sorted(DYNAMIC)
