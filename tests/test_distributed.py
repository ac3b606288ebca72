"""Master/worker loopback: distributed generation must match local exactly.

The reference was only ever validated by manual multi-node deployment
(SURVEY.md §4); here the whole master<->worker path — wire framing, tensor
codec, worker op loop, per-connection caches, segment coalescing — runs over
localhost and is held to golden-token parity with the all-local generator.
"""

import threading
import time

import jax
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.topology import Topology
from cake_tpu.runtime.master import DistributedGenerator, build_runners
from cake_tpu.runtime.wire import WireError
from cake_tpu.runtime.worker import Worker
from cake_tpu.runtime.generator import LlamaGenerator

CFG = tiny(max_seq_len=64)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(3))


def _loader(params):
    return lambda lo, hi: jax.tree.map(lambda a: a[lo:hi], params["layers"])


def _head_params(params):
    return {k: params[k] for k in ("embed", "norm_f", "lm_head")}


def _start_worker(name, topo, params, port=0):
    # a restart on a GIVEN port waits for it: beside five other xdist
    # workers something else on the host may hold the number for a moment
    # (an outgoing connection's source port), and the bind then fails
    for attempt in range(50):
        try:
            w = Worker(
                name, CFG, topo, _loader(params), address=f"127.0.0.1:{port}",
                max_seq=CFG.max_seq_len,
            )
            break
        except (WireError, OSError):
            if not port or attempt == 49:
                raise
            time.sleep(0.1)
    w.serve_in_background()
    return w


def _local_stream(params, prompt, n, settings):
    g = LlamaGenerator(CFG, params, settings=settings)
    g.set_prompt(prompt)
    return [g.next_token(i).id for i in range(n)]


def test_all_remote_two_workers(params):
    """Master holds no layers; two workers serve [0,2) and [2,4)."""
    w1 = _start_worker("w1", Topology.from_dict(
        {"w1": {"layers": ["model.layers.0-1"]}}), params)
    w2 = _start_worker("w2", Topology.from_dict(
        {"w2": {"layers": ["model.layers.2-3"]}}), params)
    topo = Topology.from_dict({
        "w1": {"host": f"127.0.0.1:{w1.port}", "layers": ["model.layers.0-1"]},
        "w2": {"host": f"127.0.0.1:{w2.port}", "layers": ["model.layers.2-3"]},
    })
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    runners = build_runners(CFG, topo, _loader(params))
    assert [r.ident() for r in runners] == [
        f"127.0.0.1:{w1.port}", f"127.0.0.1:{w2.port}"
    ]
    g = DistributedGenerator(CFG, _head_params(params), runners,
                             settings=settings)
    g.set_prompt([5, 9, 2])
    got = [g.next_token(i).id for i in range(6)]
    assert got == _local_stream(params, [5, 9, 2], 6, settings)
    assert g.tokens_per_sec() is not None
    stats = g.runner_stats()
    assert [s["layers"] for s in stats] == ["0-1", "2-3"]
    # 6 forwards per runner; the first (prefill + compile) is warm-up
    assert all(s["calls"] == 5 and s["avg_ms"] > 0 for s in stats)
    assert all(s["warmup_ms"] > 0 for s in stats)
    assert all("handshake_ms" in s for s in stats)
    g.close()
    w1.shutdown()
    w2.shutdown()


def test_mixed_local_remote(params):
    """Worker serves the middle segment; master runs layers 0 and 3 locally
    (llama.rs:177-193 semantics: per-layer placement by topology)."""
    w = _start_worker("mid", Topology.from_dict(
        {"mid": {"layers": ["model.layers.1-2"]}}), params)
    topo = Topology.from_dict({
        "mid": {"host": f"127.0.0.1:{w.port}", "layers": ["model.layers.1-2"]},
    })
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    runners = build_runners(CFG, topo, _loader(params))
    idents = [r.ident() for r in runners]
    assert idents == ["local", f"127.0.0.1:{w.port}", "local"]
    g = DistributedGenerator(CFG, _head_params(params), runners,
                             settings=settings)
    g.set_prompt([1, 2, 3, 4])
    got = [g.next_token(i).id for i in range(5)]
    assert got == _local_stream(params, [1, 2, 3, 4], 5, settings)
    g.close()
    w.shutdown()


def test_sampled_stream_parity(params):
    """Seeded non-greedy sampling also matches local exactly (same sampler,
    same key schedule)."""
    w = _start_worker("all", Topology.from_dict(
        {"all": {"layers": ["model.layers.0-3"]}}), params)
    topo = Topology.from_dict({
        "all": {"host": f"127.0.0.1:{w.port}", "layers": ["model.layers.0-3"]},
    })
    settings = SamplerSettings(temperature=0.9, top_k=20, seed=77)
    runners = build_runners(CFG, topo, _loader(params))
    g = DistributedGenerator(CFG, _head_params(params), runners,
                             settings=settings)
    g.set_prompt([3, 1, 4])
    got = [g.next_token(i).id for i in range(8)]
    assert got == _local_stream(params, [3, 1, 4], 8, settings)
    g.close()
    w.shutdown()


def test_generator_reuse_reconnects(params):
    """set_prompt on a distributed generator resets worker-side caches via
    reconnect (reference: fresh connection = fresh cache, worker.rs:52-61)."""
    w = _start_worker("all", Topology.from_dict(
        {"all": {"layers": ["model.layers.0-3"]}}), params)
    topo = Topology.from_dict({
        "all": {"host": f"127.0.0.1:{w.port}", "layers": ["model.layers.0-3"]},
    })
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    runners = build_runners(CFG, topo, _loader(params))
    g = DistributedGenerator(CFG, _head_params(params), runners,
                             settings=settings)
    g.set_prompt([9, 8, 7])
    first = [g.next_token(i).id for i in range(4)]
    g.set_prompt([9, 8, 7])
    second = [g.next_token(i).id for i in range(4)]
    assert first == second
    g.close()
    w.shutdown()


def test_worker_int8_kv_serves_deterministically(params):
    """A worker can hold its per-connection KV caches in int8 (half the
    cache HBM on that host); generation is deterministic and per-connection
    isolation still holds (reconnect -> identical stream)."""
    w = Worker(
        "all", CFG, Topology.from_dict({"all": {"layers": ["model.layers.0-3"]}}),
        _loader(params), address="127.0.0.1:0", max_seq=CFG.max_seq_len,
        kv_quant="int8",
    )
    w.serve_in_background()
    topo = Topology.from_dict({
        "all": {"host": f"127.0.0.1:{w.port}", "layers": ["model.layers.0-3"]},
    })
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    runners = build_runners(CFG, topo, _loader(params))
    g = DistributedGenerator(CFG, _head_params(params), runners,
                             settings=settings)
    g.set_prompt([5, 9, 2])
    first = [g.next_token(i).id for i in range(6)]
    g.set_prompt([5, 9, 2])  # reconnect -> fresh int8 caches
    second = [g.next_token(i).id for i in range(6)]
    assert first == second and len(first) == 6
    g.close()
    w.shutdown()


def test_handshake_warns_on_version_skew(params, monkeypatch, caplog):
    """A skewed master/worker pair must not handshake silently
    (proto/message.rs:37-53 carries version for exactly this)."""
    import logging

    import cake_tpu
    from cake_tpu.parallel.runner import RemoteRunner

    w = _start_worker("w", Topology.from_dict(
        {"w": {"layers": ["model.layers.0-3"]}}), params)
    monkeypatch.setattr(cake_tpu, "__version__", "999.0.0")
    with caplog.at_level(logging.WARNING, logger="cake_tpu.runner"):
        r = RemoteRunner(f"127.0.0.1:{w.port}", start=0, stop=4)
    assert any("version skew" in rec.message for rec in caplog.records)
    assert r.info.device_idx >= 0
    r.close()
    w.shutdown()


def test_worker_rejects_unserved_layer(params):
    from cake_tpu.parallel.runner import RemoteRunner

    w = _start_worker("w", Topology.from_dict(
        {"w": {"layers": ["model.layers.0-1"]}}), params)
    with pytest.raises(RuntimeError, match="does not serve"):
        RemoteRunner(f"127.0.0.1:{w.port}", start=2, stop=4)
    w.shutdown()


def test_worker_reports_op_errors(params):
    """A malformed op gets an Error reply, and the connection keeps serving."""
    from cake_tpu.runtime import protocol, wire
    from cake_tpu.runtime.protocol import MsgType

    w = _start_worker("w", Topology.from_dict(
        {"w": {"layers": ["model.layers.0-1"]}}), params)
    conn = wire.connect("127.0.0.1", w.port)
    conn.send(MsgType.HELLO)
    t, payload = conn.recv()
    assert t == MsgType.WORKER_INFO
    x = np.zeros((1, 1, CFG.hidden_size), np.float32)
    conn.send(MsgType.BATCH, protocol.encode_ops(x, [("model.layers.3", 0)]))
    t, payload = conn.recv()
    assert t == MsgType.ERROR
    assert "not served" in protocol.decode_error(payload)
    # connection still alive: valid op succeeds
    conn.send(MsgType.BATCH, protocol.encode_ops(x, [("model.layers.0", 0)]))
    t, payload = conn.recv()
    assert t == MsgType.TENSOR
    conn.close()
    w.shutdown()


def test_worker_requires_assigned_layers(params):
    with pytest.raises(ValueError, match="not present"):
        Worker("ghost", CFG, Topology.from_dict({}), _loader(params))


def test_mid_stream_worker_restart_recovers(params):
    """A worker dying mid-stream does NOT end the generation (unlike the
    reference, client.rs:52-61): the master reconnects and replays the
    context, and the greedy stream is identical to an uninterrupted run."""
    node_topo = Topology.from_dict({"w": {"layers": ["model.layers.1-2"]}})
    w = _start_worker("w", node_topo, params)
    port = w.port
    topo = Topology.from_dict({
        "w": {"host": f"127.0.0.1:{port}", "layers": ["model.layers.1-2"]},
    })
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    g = DistributedGenerator(CFG, _head_params(params),
                             build_runners(CFG, topo, _loader(params)),
                             settings=settings)
    g.set_prompt([5, 9, 2])
    got = [g.next_token(i).id for i in range(3)]
    # kill the worker between tokens, then bring a fresh one up on the port
    w.shutdown()
    w2 = _start_worker("w", node_topo, params, port=port)
    got += [g.next_token(i).id for i in range(3, 7)]
    assert got == _local_stream(params, [5, 9, 2], 7, settings)
    assert g.recoveries >= 1  # the replay path actually ran
    g.close()
    w2.shutdown()


def test_worker_op_error_not_retried(params):
    """A worker-reported op error is deterministic: it must surface
    immediately, NOT trigger reconnect + full-context replay (which would
    re-run the same failing op at prefill cost every token)."""
    from cake_tpu.runtime import protocol

    settings = SamplerSettings(temperature=0.0)
    g = DistributedGenerator(CFG, _head_params(params),
                             build_runners(CFG, Topology.from_dict({}),
                                           _loader(params)),
                             settings=settings)
    g.set_prompt([5, 9, 2])
    g.next_token(0)

    def boom(x, pos):
        raise protocol.WorkerOpError("worker 127.0.0.1:1: bad op")

    # forward_jax is the seam the master's segment walk calls
    g.runners[0].forward_jax = boom
    with pytest.raises(protocol.WorkerOpError):
        g.next_token(1)
    assert g.recoveries == 0
    g.close()


def test_recovery_attempts_capped(params):
    """A permanently failing transport gives up after MAX_CONSEC_RECOVERIES
    instead of replaying the context forever."""
    from cake_tpu.runtime import wire

    settings = SamplerSettings(temperature=0.0)
    g = DistributedGenerator(CFG, _head_params(params),
                             build_runners(CFG, Topology.from_dict({}),
                                           _loader(params)),
                             settings=settings)
    g.set_prompt([5, 9, 2])
    g.next_token(0)

    calls = {"n": 0}
    real_forward = g.runners[0].forward_jax

    def flaky(x, pos):
        calls["n"] += 1
        # single-token decode forwards fail; replay prefills (T>1) succeed
        if np.asarray(x).shape[1] == 1:
            raise wire.WireError("connection reset")
        return real_forward(x, pos)

    g.runners[0].forward_jax = flaky
    # each failing decode step replays successfully and yields a token, but
    # the consecutive-recovery counter never resets; the cap must trip
    with pytest.raises(RuntimeError, match="consecutive recovery"):
        for i in range(1, 10):
            g.next_token(i)
    assert g.recoveries == DistributedGenerator.MAX_CONSEC_RECOVERIES
    g.close()


def test_worker_down_for_good_still_fails(params):
    """If the worker never comes back, recovery raises (reference behavior:
    the run errors out, cake-cli/main.rs:51-55)."""
    node_topo = Topology.from_dict({"w": {"layers": ["model.layers.0-3"]}})
    w = _start_worker("w", node_topo, params)
    topo = Topology.from_dict({
        "w": {"host": f"127.0.0.1:{w.port}", "layers": ["model.layers.0-3"]},
    })
    settings = SamplerSettings(temperature=0.0)
    # short recovery budget: this test asserts the permanent-failure path,
    # not the (default 30s/replica) reconnect patience
    g = DistributedGenerator(CFG, _head_params(params),
                             build_runners(CFG, topo, _loader(params),
                                           recover_deadline_s=0.3),
                             settings=settings)
    g.set_prompt([1, 2, 3])
    g.next_token(0)
    w.shutdown()
    # the in-flight connection may serve one final op before the worker's
    # loop notices the stop flag; within a few steps the failure must
    # surface (reconnect hits the closed listener)
    with pytest.raises(Exception):
        for i in range(1, 5):
            g.next_token(i)
    g.close()
