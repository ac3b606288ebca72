"""The benchmark's own tests: CPU only, well under half a minute.

    python -m pytest benchmark/test_benchmark.py -q

They live under ``benchmark/`` because the benchmark's ``paths`` may hold
nothing outside its own directories; tier-1 (``pytest tests/``) does not
collect them. Nothing here describes a TPU topology or starts a server
at import time.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import metrics  # noqa: E402
import run  # noqa: E402
import shapes  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
LAYOUTS = ("q8", "bf16")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _timeline():
    return json.loads((HERE / "testdata" / "timeline.json").read_text())


def _config(name: str, rehearsal: bool = False):
    """(configuration file's dict, its architecture module), both found by
    name as a run finds them; ``rehearsal``: at the tiny CPU sizes."""
    conf = {c["name"]: c for c in BENCH["configs"]}[name]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    arch = run.load_arch(cfg["bench"]["arch"])
    if rehearsal:
        cfg = run.overlay(cfg, cfg["bench"]["rehearsal"])
    return cfg, arch


# -- metric arithmetic on a hand-made timeline --------------------------------

def test_tokens_per_s_counts_only_tokens_inside_the_window():
    t = _timeline()
    # 3 of a's tokens, 4 of b's, c's one and 1 of d's arrive inside [10, 20)
    assert metrics.tokens_in_window(t["records"], t["window"]) == 9
    assert metrics.tokens_per_s(t["records"], t["window"]) == pytest.approx(0.9)


def test_ttft_runs_from_the_due_instant_not_the_send():
    t = _timeline()
    b = t["records"][1]  # due 11.0, sent 11.4 (the generator ran late)
    assert metrics.ttft_ms(b, "due") == pytest.approx(1000.0)
    assert metrics.ttft_ms(b, "sent") == pytest.approx(600.0)
    late = metrics.lateness_ms(t["records"])
    assert late["worst_ms"] == pytest.approx(400.0)


def test_tpot_is_per_request_and_the_median_over_requests():
    t = _timeline()
    a, b, c, d = t["records"]
    assert metrics.tpot_ms(a) == pytest.approx(1000.0)   # 4 tokens over 3 s
    assert metrics.tpot_ms(b) == pytest.approx(500.0)
    assert metrics.tpot_ms(c) is None                    # one token: no gap
    assert metrics.tpot_ms(d) == pytest.approx(700.0)
    assert metrics.tpot_p50_ms(t["records"]) == pytest.approx(700.0)


def test_gaps_are_pooled_and_clipped_to_the_window():
    t = _timeline()
    gaps = sorted(metrics.gaps_ms(t["records"], t["window"]))
    # a's first gap ends at 10.0 (inside); d's ends at 20.5 (outside)
    assert gaps == pytest.approx([500.0] * 3 + [1000.0] * 3)


@pytest.mark.parametrize("xs,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(101)), 90, 90.0), ([7.0], 99, 7.0), ([], 50, None)])
def test_percentile(xs, q, want):
    assert metrics.percentile(xs, q) == want


def test_tail_mean_moves_with_every_sample_of_the_tail():
    xs = [0.0] * 875 + [212.0] * 115 + [251.0] * 10
    assert metrics.percentile(xs, 99) == pytest.approx(212.0, abs=1.0)
    assert metrics.tail_mean(xs, 0.01) == pytest.approx(251.0)
    assert metrics.tail_mean(xs[:-1] + [212.0], 0.01) == pytest.approx(247.1)
    assert metrics.tail_mean([5.0], 0.01) == 5.0
    assert metrics.tail_mean([], 0.01) is None


@pytest.mark.parametrize("n,q,beyond", [(100, 90, 10), (100, 95, 5),
                                        (99, 90, 9), (4000, 99, 40)])
def test_sample_rule_counts_what_lies_beyond(n, q, beyond):
    assert metrics.samples_beyond(n, q) == beyond


def test_end_to_end_takes_ttft_from_the_due_instant():
    t = _timeline()
    e2e = metrics.end_to_end(t["records"], t["window"])
    assert set(e2e) == {"tokens_per_s", "tpot_p50_ms", "ttft_mean_ms"}
    due = [metrics.ttft_ms(r, "due") for r in t["records"]]
    assert e2e["ttft_mean_ms"] == pytest.approx(sum(due) / len(due))
    assert e2e["ttft_mean_ms"] != pytest.approx(
        metrics.ttft_mean_ms(t["records"], "sent"))


# -- the schedule ---------------------------------------------------------------

def _mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def test_same_seed_same_requests_other_seed_same_set_other_order():
    mix = _mix("chat-r80")
    one = traffic.Schedule(mix, 3000000001, 45.0, 32000, 8)
    two = traffic.Schedule(mix, 3000000001, 45.0, 32000, 8)
    other = traffic.Schedule(mix, 7, 45.0, 32000, 8)
    reqs = [one.request(k) for k in range(one.count)]
    assert reqs == [two.request(k) for k in range(two.count)]
    shape = lambda s: [(len(s.request(k)["prompt_ids"]),  # noqa: E731
                        s.request(k)["max_tokens"]) for k in range(s.count)]
    gaps = lambda s: [round(b - a, 9) for a, b in zip(  # noqa: E731
        s._due, s._due[1:])]
    # the seed makes the traffic: the same sizes and the same gaps ...
    assert sorted(shape(one)) == sorted(shape(other))
    assert sorted(gaps(one)) == sorted(gaps(other))
    # ... each in an order of its own, and other token ids
    assert shape(one) != shape(other) and gaps(one) != gaps(other)
    assert one.count == round(mix["rate_rps"] * 45.0)
    assert 0.0 == one._due[0] and max(one._due) < 45.0
    assert max(other._due) == pytest.approx(max(one._due))


def test_closed_loop_cycles_through_one_set():
    mix = _mix("decode-full")
    s = traffic.Schedule(mix, 5, 45.0, 32000, 8)
    assert s.clients == 8 and s.count is None
    n = mix["set_size"]
    first = sorted(len(s.request(k)["prompt_ids"]) for k in range(n))
    second = sorted(len(s.request(k)["prompt_ids"]) for k in range(n, 2 * n))
    assert first == second
    lens = [len(s.request(k)["prompt_ids"]) for k in range(n)]
    other = traffic.Schedule(mix, 6, 45.0, 32000, 8)
    assert sorted(lens) == sorted(len(other.request(k)["prompt_ids"])
                                  for k in range(n))
    assert lens != [len(other.request(k)["prompt_ids"]) for k in range(n)]
    assert min(lens) >= 64 and max(lens) <= 512
    assert s.admission_buckets(2048) == sorted(s.admission_buckets(2048))
    assert all(64 <= n <= 512 for n in s.admission_buckets(2048))


def test_shared_prefix_groups_share_their_opening_tokens():
    mix = dict(_mix("decode-full"),
               shared_prefix={"groups": 2, "len": 32, "share": 1.0})
    s = traffic.Schedule(mix, 1, 10.0, 32000, 8)
    opens = {tuple(s.request(k)["prompt_ids"][:32]) for k in range(32)}
    assert len(opens) == 2
    assert all(len(s.request(k)["prompt_ids"]) > 32 for k in range(32))


# -- the trace reduction --------------------------------------------------------

def test_trace_reduction_on_the_recorded_trace():
    dumped = json.loads((HERE / "testdata" / "trace_dump.json").read_text())
    want = json.loads((HERE / "testdata" / "trace_expect.json").read_text())
    got = trace_reduce.reduce(dumped)
    dev = got["devices"][0]
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert dev["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < dev["busy_s"] <= got["window_s"]
    assert dev["ops"][0][0] == want["top_op"]
    for name, m in want["modules"].items():
        assert dev["modules"][name]["count"] == m["count"]
        assert dev["modules"][name]["seconds"] == pytest.approx(m["seconds"])
    assert len(dev["gaps"]) <= trace_reduce.TOP_GAPS


def test_containers_carry_only_their_self_time():
    # a 100 ns while-loop holding two 30 ns fusions, then a lone 10 ns copy
    ev = [("while", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 50, 80),
          ("copy", 120, 130)]
    st = {n: s for n, _, _, s in trace_reduce.self_times(ev)}
    assert st == {"while": 40, "fusion.1": 30, "fusion.2": 30, "copy": 10}
    dev = trace_reduce.reduce_device(
        {"name": "d", "ops": [[n, lo, hi - lo] for n, lo, hi in ev],
         "modules": [["jit_step(7)", 0, 130]]},
        [{"thread": "main", "events": [["Engine.wait", 95, 30]]}], 0, 200)
    assert dev["busy_s"] == pytest.approx(110e-9)
    assert dev["modules"]["jit_step(7)"] == {"seconds": 130e-9, "count": 1}
    assert dev["gaps"][0]["seconds"] == pytest.approx(70e-9)   # 130..200
    assert dev["gaps"][1]["host"] == "Engine.wait"              # 100..120


def test_collective_time_is_the_collectives_self_time():
    dev = trace_reduce.reduce_device(
        {"name": "d", "modules": [],
         "ops": [["fusion.1", 0, 50], ["all-reduce.3", 50, 20],
                 ["collective-permute.1", 80, 10]]}, [], 0, 100)
    assert dev["collective_s"] == pytest.approx(30e-9)


# -- bytes, against the program's own parameter shapes ---------------------------

@pytest.mark.parametrize("config", CONFIGS)
def test_weight_bytes_match_the_programs_parameter_shapes(config):
    import jax

    from cake_tpu.models.config import LlamaConfig
    from cake_tpu.models.llama import init_params, init_params_int8

    cfg, arch = _config(config)
    layout = cfg["bench"]["weights"]["layout"]
    lc = LlamaConfig.from_hf_dict(weights.hf_config(cfg, arch.HF_KEYS),
                                  dtype="bfloat16")
    init = init_params_int8 if layout == "q8" else init_params
    tree = jax.eval_shape(lambda k: init(lc, k), jax.random.PRNGKey(0))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert arch.weight_bytes(cfg, layout) == pytest.approx(held, rel=0.002)
    assert arch.checkpoint_bytes(cfg, layout) >= 0.99 * held


def test_decode_step_reads_routed_experts_and_live_cache_only():
    cfg, arch = _config("mixtral8x7b-cut")
    assert shapes.expected_experts(8, 2, 1) == pytest.approx(2.0)
    assert 7.0 < shapes.expected_experts(8, 2, 8) < 7.3
    one = arch.decode_step_bytes(cfg, "q8", 1, 100)
    full = arch.decode_step_bytes(cfg, "q8", 8, 100)
    assert one < 0.4 * full
    assert arch.kv_bytes(cfg, 100, 8) == 8 * 100 * 7 * 2 * 8 * 128 * 2


@pytest.mark.parametrize("config", CONFIGS)
def test_a_decode_step_reads_no_more_than_the_device_holds(config):
    cfg, arch = _config(config)
    b = cfg["bench"]
    layout, dtype, slots = b["weights"]["layout"], b["serve_dtype"], b["slots"]
    held = arch.weight_bytes(cfg, layout, dtype)
    in_step = arch.weight_bytes(cfg, layout, dtype, slots)
    one = arch.decode_step_bytes(cfg, layout, 1, 100, dtype)
    full = arch.decode_step_bytes(cfg, layout, slots, 100, dtype)
    # more live streams and longer contexts read more, never less ...
    assert 0 < one < full < arch.decode_step_bytes(
        cfg, layout, slots, 200, dtype)
    # ... the weights of a step are among those the device holds, and the
    # rest of a step is the streams' cached state
    assert in_step <= held and full > in_step


# -- the checkpoint writer and the reference -------------------------------------

@pytest.mark.parametrize("config", CONFIGS)
def test_checkpoint_round_trip_and_reference(config, tmp_path):
    import numpy as np

    cfg, arch = _config(config, rehearsal=True)
    linears = []
    for layout in LAYOUTS:
        d = tmp_path / layout
        info = arch.write_checkpoint(cfg, layout, 3, d, workers=2)
        assert info["bytes"] == arch.checkpoint_bytes(cfg, layout)
        again = tmp_path / (layout + "2")
        arch.write_checkpoint(cfg, layout, 3, again, workers=1)
        for f in sorted(p.name for p in d.iterdir()):
            assert (d / f).read_bytes() == (again / f).read_bytes(), f
        assert json.loads((d / "config.json").read_text()) == (
            weights.hf_config(cfg, arch.HF_KEYS))
        ck = weights.Checkpoint(d)
        # the q8 layout says which tensors are linears; bf16 has the same
        linears += [n[:-3] for n in ck.files if n.endswith(".q8")][:4]
        assert linears
        for name in linears:  # std ~ 1/sqrt(fan_in), whatever the layout
            w = ck.f32(name)
            assert w.ndim == 2 and 0.05 < w.std() * w.shape[1] ** 0.5 < 2.0
        out, two = arch.chosen_logprobs(
            cfg, d, [([5, 9, 11, 40], [7, 8, 9]), ([9, 5], [3, 4])])
        alone, = arch.chosen_logprobs(cfg, d, [([9, 5], [3, 4])])
        assert two == alone  # sequences share weights, not positions
        assert len(out["logprob"]) == 3
        assert all(b >= l for b, l in zip(out["best_logprob"], out["logprob"]))
        assert np.isfinite(out["logprob"]).all()


@pytest.mark.parametrize("config", CONFIGS)
def test_correct_holds_the_servers_ids_to_the_references_margin(config,
                                                                tmp_path):
    cfg, arch = _config(config, rehearsal=True)
    d = tmp_path / "ckpt"
    arch.write_checkpoint(cfg, cfg["bench"]["weights"]["layout"], 3, d,
                          workers=1)
    prompt = [5, 9, 11, 40, 7]
    # the reference's own greedy continuation, one token at a time
    ids = []
    for _ in range(3):
        out, = arch.chosen_logprobs(cfg, d, [(prompt, ids + [0])])
        ids.append(out["best"][-1])
    check = lambda probes: run.check_reference(  # noqa: E731
        probes, cfg, arch, "t", d, tmp_path / "c")
    good = [{"prompt": prompt, "ids": ids, "repeat_same": True}]
    ok, worst = check(good)
    assert ok and worst == 0.0
    wrong = [{"prompt": prompt, "ids": ids[:2] + [(ids[2] + 1) % 500]}]
    ok, worst = check(wrong)
    assert not ok and worst > cfg["bench"]["margin_tol"]
    assert not check([dict(good[0], repeat_same=False)])[0]


# -- the seam: an architecture is a module found by name --------------------------

GOLDEN = json.loads((HERE / "testdata" / "arch_golden.json").read_text())


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("config", sorted(GOLDEN["configs"]))
def test_the_move_into_arch_changed_no_byte_and_no_float(config, layout,
                                                         tmp_path):
    """``testdata/arch_golden.json`` was recorded by the parent's code
    (weights.py, reference.py, shapes.py of commit 2121709, before
    ``arch/`` existed): the same checkpoints, the same answers, the same
    counts, held exactly."""
    want_all = GOLDEN["configs"][config]
    want = want_all[layout]
    cfg, arch = _config(config)
    small = run.overlay(cfg, cfg["bench"]["rehearsal"])
    info = arch.write_checkpoint(small, layout, GOLDEN["seed"], tmp_path,
                                 workers=2)
    assert info == want["written"]
    assert {p.name: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in tmp_path.iterdir()} == want["files"]
    assert arch.chosen_logprobs(
        small, tmp_path, GOLDEN["pairs"]) == want["chosen_logprobs"]
    pub = want["published"]
    assert arch.checkpoint_bytes(cfg, layout) == pub["checkpoint_bytes"]
    assert arch.weight_bytes(cfg, layout, "bf16") == pub["weight_bytes_held"]
    assert [arch.weight_bytes(cfg, layout, "bf16", r)
            for r, _ in GOLDEN["points"]] == pub["weight_bytes"]
    assert [arch.decode_step_bytes(cfg, layout, r, c, "bf16")
            for r, c in GOLDEN["points"]] == pub["decode_step_bytes"]
    # a run finds the checkpoint and the answers it wrote before the move
    assert run.checkpoint_key(cfg, arch) == want_all["checkpoint_key"]
    assert arch.REFERENCE_VERSION == want_all["reference_version"]


def test_an_unknown_architecture_fails_with_the_list_of_those_there():
    assert run.load_arch("gqa").HF_KEYS
    with pytest.raises(run.BenchFailure, match=r"no architecture 'mla-x'.*"
                       r"benchmark/arch/ has \[.*'gqa'.*\]"):
        run.load_arch("mla-x")


HARNESS = ["run.py", "counters.py", "serve_counters.py", "metrics.py",
           "traffic.py", "client.py", "trace_reduce.py", "serve_child.py"]
ARCH_API = ["write_checkpoint", "checkpoint_bytes", "chosen_logprobs",
            "weight_bytes", "decode_step_bytes", "HF_KEYS", "WRITER_VERSION",
            "REFERENCE_VERSION"]


def test_the_harness_knows_no_architecture():
    """run.py, what it shares with the readers, and every reader reach a
    decoder's tensors and mathematics only through the module that
    ``load_arch`` found by the configuration's ``bench.arch``."""
    archs = [p.stem for p in (HERE / "arch").glob("*.py")]
    keys = {k for a in archs for k in run.load_arch(a).HF_KEYS}
    keys -= {"vocab_size", "eos_token_id"}
    tensors = ["_proj", "layernorm", "embed_tokens", "lm_head", "self_attn",
               "block_sparse_moe", "mlp.", "model.layers", "model.norm"]
    files = [HERE / f for f in HARNESS] + sorted(
        (HERE / "layer_metrics").glob("*.py"))
    assert len(files) > len(HARNESS)
    for path in files:
        text = path.read_text()
        assert not re.search(r"^\s*(import|from)\s+(reference|shapes|arch)\b",
                             text, re.M), path.name
        for word in archs:
            assert not re.search(rf"arch[./]{re.escape(word)}\b|"
                                 rf"[\"']{re.escape(word)}[\"']", text), (
                path.name, word)
        for word in sorted(keys) + tensors:
            assert word not in text, (path.name, word)
        # what an architecture defines is reached as an attribute of the
        # loaded module and in no other way
        for fn in ARCH_API:
            for m in re.finditer(rf"\b{fn}\b", text):
                assert text[:m.start()].endswith(("arch.", 'ctx["arch"].')), (
                    path.name, fn)


SEAM_ARCH = '''"""A decoder the harness has never seen: gqa's mathematics under
keys of its own."""
from arch import gqa

HF_KEYS = gqa.HF_KEYS
WRITER_VERSION = 7001
REFERENCE_VERSION = 7002
checkpoint_bytes = gqa.checkpoint_bytes
weight_bytes = gqa.weight_bytes
decode_step_bytes = gqa.decode_step_bytes


def write_checkpoint(cfg, layout, seed, model_dir, workers=8):
    info = gqa.write_checkpoint(cfg, layout, seed, model_dir, workers)
    (model_dir / "WRITTEN_BY").write_text(__name__)
    return info


def chosen_logprobs(cfg, model_dir, pairs):
    (model_dir / "JUDGED_BY").write_text(__name__)
    return gqa.chosen_logprobs(cfg, model_dir, pairs)
'''


def _seam_tree(tmp: Path) -> str:
    """A checkout under ``tmp`` that ADDS to the benchmark and edits none
    of its files: the harness as it is, the program (a link), and three
    new files: an architecture no file of the harness names, a
    configuration of it, and a BENCHMARK.json with one cell of that."""
    shutil.copytree(HERE, tmp / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", ".pytest_cache"))
    (tmp / "cake_tpu").symlink_to(ROOT / "cake_tpu")
    (tmp / "benchmark/arch/renamed-decoder.py").write_text(SEAM_ARCH)
    base = BENCH["configs"][0]
    cfg = json.loads((ROOT / base["file"]).read_text())
    cfg["bench"]["arch"] = "renamed-decoder"
    (tmp / "benchmark/configs/seam.json").write_text(json.dumps(cfg))
    was = next(w for w in BENCH["workloads"] if w["config"] == base["name"])
    cell = dict(was, name="seam.cell", config="seam")

    def moved(metric: dict):
        if "workloads" not in metric:
            return metric
        return dict(metric, workloads=["seam.cell"]) if was["name"] in metric[
            "workloads"] else None

    bench = dict(
        BENCH, workloads=[cell],
        configs=[dict(base, name="seam", file="benchmark/configs/seam.json")],
        end_to_end=[m for m in map(moved, BENCH["end_to_end"]) if m],
        per_layer=[m for m in map(moved, BENCH["per_layer"]) if m])
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell["name"]


def _git_status() -> str | None:
    if not (ROOT / ".git").exists():
        return None  # the driver's checkout is no repository
    return subprocess.run(["git", "status", "--porcelain"], cwd=ROOT,
                          capture_output=True, text=True, check=True).stdout


def test_a_new_architecture_comes_from_new_files_alone(tmp_path):
    status = _git_status()
    cell = _seam_tree(tmp_path)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--rehearse",
         "--seed", "3000000011", "--seconds", "2", "--trace", "2"],
        capture_output=True, text=True, timeout=240, cwd=tmp_path,
        env=dict(os.environ, PYTHONDONTWRITEBYTECODE="1"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    assert {"tokens_per_s", "setup_s", "engine.gap_tail1_ms"} <= set(
        last["would_report"])
    # the harness went through the new module: its writer, its key, its
    # reference, all inside the new checkout
    wrote = next(l for l in lines if l.get("phase") == "checkpoint_written")
    model_dir = tmp_path / wrote["dir"]
    assert model_dir.parent == tmp_path / ".bench_cache/rehearsal/ckpt"
    assert model_dir.name.startswith("seam-")
    assert (model_dir / "WRITTEN_BY").read_text() == (
        model_dir / "JUDGED_BY").read_text() == "bench_arch_renamed-decoder"
    assert any(l.get("phase") == "reference_computed" for l in lines)
    # ... and no file the benchmark had was edited, here or in the checkout
    for path in HERE.rglob("*"):
        if path.is_file() and not {"__pycache__", ".pytest_cache"} & set(
                path.parts):
            copy = tmp_path / "benchmark" / path.relative_to(HERE)
            assert copy.read_bytes() == path.read_bytes(), path
    assert _git_status() == status


# -- every cell's files are found by name; names keep to the contract ------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    import run

    c = run.load_cell(cell)
    assert c["cfg"]["bench"]["chips"] == c["cell"]["chips"]
    assert any(m["name"] == "setup_s" for m in c["end_to_end"])
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in c["per_layer"]:
        assert callable(run.load_reader(m["name"])), m["name"]
        assert m["moves"] in {e["name"] for e in c["end_to_end"]}, m["name"]
    conf = {x["name"]: x for x in BENCH["configs"]}[c["cell"]["config"]]
    assert sorted(conf["reduced"]) == sorted(c["cfg"]["reduced"])
    assert conf["source"].split("#")[0].startswith(
        c["cfg"]["source"].split("/blob/")[0])


def test_names_and_units_keep_to_the_contract():
    metrics_ = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics_]
    assert len(set(names)) == len(names)
    for m in metrics_:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for e in BENCH["end_to_end"]:
        assert 0 < e["bound"] <= 0.1 and e["source"] in ("host_clock",
                                                         "device_trace")
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    source = (HERE / "run.py").read_text()
    for word in CELLS + [c["name"] for c in BENCH["configs"]] + sorted(
            {w["traffic"] for w in BENCH["workloads"]}):
        assert f'"{word}"' not in source and f"'{word}'" not in source


def test_unknown_device_kind_is_an_error():
    import run

    assert run.peaks_for("TPU v5 lite")["hbm_gb_per_s"] == 819.0
    with pytest.raises(run.BenchFailure):
        run.peaks_for("TPU v99")


# -- which context a reader gets, and PR 24's readers ----------------------------

@pytest.mark.parametrize("source,got", [
    ("device_trace", "tail"), ("program_span", "tail"),
    ("host_clock", "window"), ("program_counter", "window")])
def test_trace2_hands_a_reader_its_context_by_the_metrics_source(source, got):
    import run

    window, tail = {"is": "window"}, {"is": "tail"}
    assert run.context_for({"source": source}, window, tail)["is"] == got
    # a --trace 1 run has the one context, whatever the source
    assert run.context_for({"source": source}, window, None) is window


def test_every_metric_names_a_source_context_for_knows():
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "host_clock",
                               "program_counter"), m["name"]
    # what needs every step stamped, or the device trace, reads the tail
    tail = {m["name"] for m in BENCH["per_layer"]
            if m["source"] in ("device_trace", "program_span")}
    assert {"engine.host_ms_per_dispatch", "step.decode_device_ms",
            "kernel.decode_hbm_share", "device.idle_share",
            "load.params_s"} <= tail
    assert not {"compile.in_window", "engine.gap_tail1_ms",
                "engine.stall_s", "sched.queue_wait_mean_ms"} & tail


def _recorded_ctx(with_new: bool = True) -> dict:
    """Counters of a server on either side of a window, as ``GET /`` and
    ``GET /debug/prof`` give them (trimmed to what readers read)."""
    def hist(count, total):
        return {"type": "histogram", "count": count, "sum": total}

    before = {"serve.ttft_ms": hist(10, 2000.0)}
    after = {"serve.ttft_ms": hist(110, 27000.0)}
    prof_after = {"compiles": 5, "phases": {}}
    if with_new:
        before.update({"serve.queue_wait_ms": hist(10, 100.0),
                       "serve.admit_to_first_ms": hist(10, 1900.0),
                       "prof.slow_pass_ms": {"type": "counter", "value": 0}})
        after.update({"serve.queue_wait_ms": hist(110, 600.0),
                      "serve.admit_to_first_ms": hist(110, 26400.0),
                      "prof.slow_pass_ms": {"type": "counter",
                                            "value": 2500.0}})
        prof_after["startup"] = {"params_s": 27.5, "engine_s": 1.0,
                                 "warm_s": 4.0, "loaded_s": 33.0}
    return {"before": {"status": {"metrics": before},
                       "prof": {"compiles": 5, "phases": {}}},
            "after": {"status": {"metrics": after}, "prof": prof_after}}


def test_pr24_readers_on_recorded_counters():
    import run

    ctx = _recorded_ctx()
    read = lambda name: run.load_reader(name)(ctx)  # noqa: E731
    assert read("sched.queue_wait_mean_ms") == pytest.approx(5.0)
    assert read("engine.admit_to_first_mean_ms") == pytest.approx(245.0)
    # the .open names share the functions
    assert read("sched.queue_wait_mean_ms.open") == pytest.approx(5.0)
    assert read("engine.admit_to_first_mean_ms.open") == pytest.approx(245.0)
    # the two legs add up to the server's own time to first token
    ttft = (27000.0 - 2000.0) / 100
    assert read("sched.queue_wait_mean_ms") + read(
        "engine.admit_to_first_mean_ms") == pytest.approx(ttft)
    assert read("engine.stall_s") == pytest.approx(2.5)
    assert read("load.params_s") == 27.5


@pytest.mark.parametrize("name", [
    "sched.queue_wait_mean_ms", "sched.queue_wait_mean_ms.open",
    "engine.admit_to_first_mean_ms", "engine.admit_to_first_mean_ms.open",
    "engine.stall_s", "load.params_s"])
def test_pr24_readers_find_nothing_in_an_older_program(name):
    import run

    assert run.load_reader(name)(_recorded_ctx(with_new=False)) is None


def test_a_clean_window_reads_a_stall_of_zero_not_nothing():
    import run

    ctx = _recorded_ctx()
    ctx["after"]["status"]["metrics"]["prof.slow_pass_ms"]["value"] = 0
    assert run.load_reader("engine.stall_s")(ctx) == 0.0


def test_trace_in_run_is_declared_and_trace_1_still_parses():
    import run

    assert BENCH["trace_in_run"] is True
    source = (HERE / "run.py").read_text()
    assert "choices=[0, 1, 2]" in source
    # the launcher opens no profiler of its own any more
    child = (HERE / "serve_child.py").read_text()
    assert "start_trace" not in child and "bench-trace-dir" not in child
    assert callable(run.trace_tail) and callable(run.measure)


# -- one rehearsal, end to end ----------------------------------------------------

def test_rehearsal_of_one_cell_end_to_end():
    out = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "mixtral8x7b-cut.decode-full", "--rehearse", "--seed", "3000000001",
         "--seconds", "2"], capture_output=True, text=True, timeout=240,
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["would_report"]) == {"tokens_per_s", "tpot_p50_ms",
                                         "ttft_mean_ms", "setup_s"}


def test_rehearsal_with_trace_2_ends_in_one_line_with_both_kinds():
    out = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "mistral7b-int8.decode-full", "--rehearse", "--seed", "3000000007",
         "--seconds", "2", "--trace", "2"], capture_output=True, text=True,
        timeout=240, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    would = set(last["would_report"])
    # end-to-end, from the closed window ...
    assert {"tokens_per_s", "tpot_p50_ms", "ttft_mean_ms", "setup_s"} <= would
    # ... and per-layer: counters of the window, spans of the tail
    assert {"compile.in_window", "engine.gap_tail1_ms",
            "engine.host_ms_per_dispatch", "engine.tokens_per_dispatch",
            "sched.queue_wait_mean_ms", "engine.admit_to_first_mean_ms",
            "engine.stall_s", "load.params_s", "load.loaded_s"} <= would
    tail = next(l for l in lines if l.get("phase") == "tail")
    assert tail["failed"] == 0 and tail["capture"]["steps"] > 0
    assert tail["tokens_per_s_traced"] > 0
    order = [l.get("phase") for l in lines[:-1]]
    assert order.index("window") < order.index("tail")
    # the trace is deleted once it is reduced
    assert not Path(tail["capture"]["dir"]).exists()


def test_no_result_without_the_program(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=60, cwd=tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
