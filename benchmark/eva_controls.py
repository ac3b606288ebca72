"""The controls of ``evabyte-6p5b-cut``'s ``correct``: what the comparison
has to refuse, through the harness's own comparison (``run.check_reference``
under the configuration's ``margin_tol``), so that every reading in
``bench.margin_tol_why`` can be made again.

    python benchmark/eva_controls.py [--rehearse] [--serve-only | --ids FILE]
    python benchmark/eva_controls.py [--rehearse] --first-token

1. *The program*: the cell's checkpoint served as the cell serves it
   (``run.Server``, ``run.probe``), its ids held to the float32 reference:
   has to be ``correct``.
2. *Controls on the reference's side*: the SAME served ids held to the
   reference with one piece of the mathematics changed, each of
   ``arch/eva_mha.py`` ``WRONG`` in turn (``chosen_logprobs(wrong=)``): each
   has to be NOT ``correct``.
3. *Controls on the program's side* (``PROGRAM_SIDE``), the true reference
   holding a wrong program's ids: ``float8``, the program serving the
   checkpoint with every linear rounded through float8 (``write_rounded``);
   ``window_only``, the program with its summaries left out
   (``eva_control_child.py``, which patches ``cake_tpu.ops.eva`` before
   ``cli.main`` runs). Each has to be NOT ``correct``.

One JSON line a comparison (``side``, ``form``, ``correct``, the worst
margin a probe); the last line says whether every one came out as it has
to, and the exit code is 0 only then. ``--serve-only`` stops after the
three servers and writes their ids (``chiprun_out/pr66_control_ids.json``:
the part that needs the chip); ``--ids FILE`` makes the comparisons from
such a file (numpy alone, a few minutes a comparison at the cell's sizes).

``--first-token`` (a process of its own: it holds the chip itself) asks
whether a bucket's padding moves an answer: for every probe that is a
whole number of windows (the 6144-token one: three windows in a bucket of
8192) the ENGINE's own log-probabilities of the first token (a
``BatchGenerator`` as served, ``logprobs`` = the vocabulary: the admission
program's logits) beside a direct forward of the prompt alone, unpadded,
in the serving type and in float32 at the highest matmul precision; both
vectors go to ``chiprun_out/pr66_first_token.json``.
"""

from __future__ import annotations

import argparse
import json
import shutil
import subprocess
import sys
import types
from pathlib import Path
from unittest import mock

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import run  # noqa: E402

CELL = "evabyte-6p5b-cut.agent-long"
PROGRAM_SIDE = ("float8", "window_only")
IDS = run.ROOT / "chiprun_out" / "pr66_control_ids.json"


class WrongServer(run.Server):
    """``run.Server`` whose child is ``eva_control_child.py <form>``: the
    same command line, the program patched before ``cli.main`` runs."""

    def __init__(self, form: str, *args):
        real = subprocess.Popen

        def child(cmd, **kw):
            return real([cmd[0], str(HERE / "eva_control_child.py"), form,
                         *cmd[2:]], **kw)

        with mock.patch.object(subprocess, "Popen", child):
            super().__init__(*args)


def serve(make, cfg: dict) -> list[dict]:
    """The probes as the server ``make()`` starts answers them."""
    srv = make()
    try:
        srv.wait_ready()
        probes = run.probe(srv, cfg, cfg["vocab_size"])
        srv.stop()
    finally:
        srv.kill()
    return probes


def served_ids(cfg: dict, arch, model_dir: Path, cache: Path,
               rehearse: bool) -> dict[str, list[dict]]:
    """``{"program": probes, "float8": probes, "window_only": probes}``:
    three servers, one after another (a chip belongs to one process)."""
    def run_dir(name: str) -> Path:
        d = cache / "runs" / f"eva-controls-{name}"
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True)
        return d

    rounded = cache / "ckpt" / f"{model_dir.name}-float8"
    if not (rounded / "config.json").exists():
        arch.write_rounded(model_dir, rounded)
    chips = cfg["bench"]["chips"]
    return {
        "program": serve(lambda: run.Server(
            cfg, model_dir, run_dir("program"), chips, rehearse, False), cfg),
        "float8": serve(lambda: run.Server(
            cfg, rounded, run_dir("float8"), chips, rehearse, False), cfg),
        "window_only": serve(lambda: WrongServer(
            "window_only", cfg, model_dir, run_dir("window_only"), chips,
            rehearse, False), cfg),
    }


def held_to(arch, wrong: str | None):
    """``arch`` as ``run.check_reference`` asks it, answering as the
    control ``wrong`` (its answers kept under a key of their own)."""
    if wrong is None:
        return arch
    return types.SimpleNamespace(
        REFERENCE_VERSION=f"{arch.REFERENCE_VERSION}/{wrong}",
        chosen_logprobs=lambda cfg, model_dir, pairs: arch.chosen_logprobs(
            cfg, model_dir, pairs, wrong=wrong))


def first_token(cfg: dict, model_dir: Path) -> list[dict]:
    """The module's last paragraph; imports the program, so no server may
    follow it in this process."""
    import random

    import numpy as np
    sys.path.insert(0, str(run.ROOT))
    import jax
    import jax.numpy as jnp
    import traffic
    from cake_tpu.models import llama
    from cake_tpu.models.config import LlamaConfig
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

    b, vocab = cfg["bench"], cfg["vocab_size"]
    cap, served = b["kv_capacity"], {"bf16": "bfloat16", "f32": "float32"}[
        b["serve_dtype"]]
    rng = random.Random(f"probe/{b['weights']['seed']}")  # run.probe's
    prompts = [traffic.tokens(rng, n, vocab) for n in b["probe_lens"]]
    prompts = [p for p in prompts if len(p) % cfg["window_size"] == 0]

    def load(dtype: str):
        config = LlamaConfig.from_hf_json(model_dir / "config.json",
                                          dtype=dtype, max_seq_len=cap)
        return config, load_llama_params_on_mesh(model_dir, config,
                                                 make_mesh())

    def direct(config, params, prompt, precision):
        with jax.default_matmul_precision(precision):
            logits, _ = jax.jit(
                lambda p, t, c: llama.forward(p, t, c, 0, config))(
                    params, jnp.asarray([prompt], jnp.int32),
                    init_cache(config, batch=1, max_seq=cap))
        return np.asarray(jax.nn.log_softmax(logits[0]), np.float64)

    config, params = load(served)
    engine = BatchGenerator(
        config, params, settings=SamplerSettings(temperature=0.0,
                                                 repeat_penalty=1.0),
        max_seq=cap, block_size=b["decode_block"], logprobs=vocab)
    engine.set_prompts([[cfg["bos_token_id"]]] * 2, stream_ids=[90, 91])
    engine.finish(90), engine.finish(91)
    rows = []
    for sid, prompt in enumerate(prompts):
        engine.enqueue(prompt, sid)
        token = None
        while token is None:
            token = next((t for t, s in zip(engine.step(), engine.streams)
                          if t is not None and s.stream_id == sid), None)
        engine.finish(sid)
        own = np.empty(vocab)
        for i, v in token.logprobs:
            own[i] = v
        rows.append({"prompt_len": len(prompt), "bucket":
                     engine._admission_chunk_for(len(prompt)),
                     "served_token": int(token.id), "engine": own})
    del engine
    for row, prompt in zip(rows, prompts):
        row["direct"] = direct(config, params, prompt, "default")
    del params
    config, params = load("float32")
    for row, prompt in zip(rows, prompts):
        row["direct_f32_highest"] = direct(config, params, prompt, "highest")
    keys = ("engine", "direct", "direct_f32_highest")
    for row in rows:
        best = {k: int(row[k].argmax()) for k in keys}
        top = np.sort(row["engine"])[-2:]
        run.say(phase="first_token", prompt_len=row["prompt_len"],
                bucket=row["bucket"], served_token=row["served_token"],
                argmax=best, engine_top2_gap=float(top[1] - top[0]),
                engine_minus_direct_max=float(
                    np.abs(row["engine"] - row["direct"]).max()),
                direct_minus_f32_max=float(np.abs(
                    row["direct"] - row["direct_f32_highest"]).max()),
                engine_minus_f32_max=float(np.abs(
                    row["engine"] - row["direct_f32_highest"]).max()))
        row.update((k, [float(v) for v in row[k]]) for k in keys)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--serve-only", action="store_true")
    ap.add_argument("--ids", type=Path)
    ap.add_argument("--first-token", action="store_true")
    a = ap.parse_args(argv)
    cell = run.load_cell(CELL)
    cfg, tag = cell["cfg"], cell["cell"]["config"]
    if a.rehearse:
        cfg = run.overlay(cfg, cfg["bench"]["rehearsal"])
    cache = run.CACHE / "rehearsal" if a.rehearse else run.CACHE
    arch = run.load_arch(cfg["bench"]["arch"])
    model_dir, _ = run.ensure_checkpoint(cfg, arch, tag, cache)
    if a.first_token:
        rows = first_token(cfg, model_dir)
        if not a.rehearse:
            IDS.parent.mkdir(exist_ok=True)
            IDS.with_name("pr66_first_token.json").write_text(
                json.dumps(rows))
        return 0
    if a.ids:
        ids = json.loads(a.ids.read_text())
    else:
        ids = served_ids(cfg, arch, model_dir, cache, a.rehearse)
        if not a.rehearse:
            IDS.parent.mkdir(exist_ok=True)
            IDS.write_text(json.dumps(ids))
    if a.serve_only:
        run.say(phase="served", forms=sorted(ids), to=str(IDS))
        return 0
    # (side, form, whose ids, the reference's form, what `correct` owes)
    rows = [("program", None, "program", None, True)]
    rows += [("reference", w, "program", w, False) for w in arch.WRONG]
    rows += [("program", w, w, None, False) for w in PROGRAM_SIDE]
    as_owed = True
    for side, form, whose, wrong, owed in rows:
        ok, worst = run.check_reference(ids[whose], cfg, held_to(arch, wrong),
                                        tag, model_dir, cache)
        as_owed &= ok == owed
        run.say(phase="control", side=side, form=form or "as_published",
                correct=ok, owed=owed, worst_margin=worst,
                tolerance=cfg["bench"]["margin_tol"])
    run.say(phase="controls", as_owed=as_owed)
    return 0 if as_owed else 1


if __name__ == "__main__":
    sys.exit(main())
