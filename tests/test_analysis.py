"""cakelint (cake_tpu/analysis): fixture tests per checker + repo self-run.

Every checker gets at least one true-positive fixture (the bug class it
exists for) and negative fixtures (the idioms it must NOT flag — the
false-positive surface is what makes a linter ignorable). The self-run
test is the CI gate's gate: the tree at HEAD, against the committed
baseline, must be clean with no stale entries.
"""

import json
import textwrap
import threading

import pytest

from cake_tpu import analysis
from cake_tpu.analysis import baseline as baseline_mod
from cake_tpu.analysis import core
from cake_tpu.analysis.claims import ClaimChecker
from cake_tpu.analysis.engine_ownership import EngineOwnershipChecker
from cake_tpu.analysis.guarded_by import GuardedByChecker
from cake_tpu.analysis.metrics_catalog import MetricsCatalogChecker
from cake_tpu.analysis.thread_domains import ThreadDomainChecker
from cake_tpu.analysis.trace_purity import TracePurityChecker
from cake_tpu.analysis.wire_safety import WireSafetyChecker


def lint(tmp_path, source, checker, rel="pkg/mod.py"):
    """Run one checker over one snippet in a scratch repo; return
    findings."""
    f = tmp_path / rel
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return core.run_checkers([checker], roots=[str(f)], repo_root=tmp_path)


def lint_full(tmp_path, sources, checker):
    """Full-repo scan over ``{rel: source}`` fixtures (finalize passes
    included — what cross-file checkers need)."""
    for rel, source in sources.items():
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(source))
    return core.run_checkers([checker], roots=[str(tmp_path)],
                             repo_root=tmp_path)


# -- CK-METRIC: metrics catalog ------------------------------------------

class TestMetricsCatalog:
    def test_undeclared_literal_flagged(self, tmp_path):
        out = lint(tmp_path, """
            from cake_tpu.obs import metrics as obs_metrics
            BAD = obs_metrics.counter("wire.byte_out")  # typo'd fork
        """, MetricsCatalogChecker())
        assert len(out) == 1
        assert out[0].checker == "CK-METRIC"
        assert "wire.byte_out" in out[0].message
        assert out[0].key == "wire.byte_out"

    def test_declared_literal_ok(self, tmp_path):
        out = lint(tmp_path, """
            from cake_tpu.obs import metrics as obs_metrics
            OK1 = obs_metrics.counter("wire.bytes_out")
            OK2 = obs_metrics.histogram("serve.ttft_ms")
            OK3 = obs_metrics.Gauge("worker.warmup_ms")
        """, MetricsCatalogChecker())
        assert out == []

    def test_fstring_must_match_declared_pattern(self, tmp_path):
        out = lint(tmp_path, """
            from cake_tpu.obs import metrics as obs_metrics
            def make(i):
                ok = obs_metrics.Histogram(f"master.segment{i}.decode_ms")
                bad = obs_metrics.Histogram(f"master.seg{i}.decode_ms")
                return ok, bad
        """, MetricsCatalogChecker())
        assert len(out) == 1
        assert out[0].key == "master.seg*.decode_ms"

    def test_non_literal_name_flagged(self, tmp_path):
        out = lint(tmp_path, """
            from cake_tpu.obs import metrics as obs_metrics
            def make(name):
                return obs_metrics.gauge(name)
        """, MetricsCatalogChecker())
        assert len(out) == 1
        assert out[0].key == "non-literal:make"

    def test_keyword_name_not_a_bypass(self, tmp_path):
        out = lint(tmp_path, """
            from cake_tpu.obs import metrics as obs_metrics
            BAD = obs_metrics.counter(name="wire.byte_out")
            OK = obs_metrics.Counter(name="wire.bytes_out")
        """, MetricsCatalogChecker())
        assert len(out) == 1
        assert out[0].key == "wire.byte_out"

    def test_foreign_counter_constructor_ignored(self, tmp_path):
        # collections.Counter et al. must not be dragged into scope
        out = lint(tmp_path, """
            from collections import Counter
            c = Counter("hello world no dots".split())
        """, MetricsCatalogChecker())
        assert out == []


# -- CK-ENGINE: single engine owner --------------------------------------

class TestEngineOwnership:
    def test_direct_drive_flagged(self, tmp_path):
        out = lint(tmp_path, """
            from cake_tpu.runtime.batch_generator import BatchGenerator
            gen = BatchGenerator(cfg, params)
            gen.set_prompts([[1]])
            gen.step()
            gen.finish(0)
        """, EngineOwnershipChecker())
        assert {f.key for f in out} == {
            "BatchGenerator.set_prompts", "BatchGenerator.step",
            "BatchGenerator.finish"}

    def test_engine_attribute_flagged(self, tmp_path):
        out = lint(tmp_path, """
            def poke(scheduler):
                scheduler.engine.enqueue([1], 0)  # bypasses the owner
        """, EngineOwnershipChecker())
        assert len(out) == 1
        assert out[0].key == "BatchGenerator.enqueue"

    def test_scheduler_is_allowed(self, tmp_path):
        out = lint(tmp_path, """
            class Scheduler:
                def _run(self):
                    self.engine.step()
        """, EngineOwnershipChecker(), rel="cake_tpu/serve/scheduler.py")
        assert out == []

    def test_unrelated_finish_ok(self, tmp_path):
        out = lint(tmp_path, """
            def flush(stream, sess):
                stream.finish()      # TokenOutputStream, not an engine
                sess.finish("stop")  # Session, not an engine
        """, EngineOwnershipChecker())
        assert out == []


# -- CK-LOCK: _GUARDED_BY discipline -------------------------------------

class TestGuardedBy:
    def test_unlocked_touch_flagged(self, tmp_path):
        out = lint(tmp_path, """
            class Box:
                _GUARDED_BY = {"_items": "_lock"}
                def peek(self):
                    return list(self._items)
        """, GuardedByChecker())
        assert len(out) == 1
        assert out[0].checker == "CK-LOCK"
        assert "Box.peek" in out[0].message

    def test_locked_touch_and_escapes_ok(self, tmp_path):
        out = lint(tmp_path, """
            class Box:
                _GUARDED_BY = {"_items": "_lock"}
                def __init__(self):
                    self._items = []          # construction happens-before
                def add(self, x):
                    with self._lock:
                        self._items.append(x)
                def _clear_locked(self):
                    self._items.clear()       # caller holds the lock
        """, GuardedByChecker())
        assert out == []

    def test_shadowing_local_is_not_the_global(self, tmp_path):
        # a function-local binding that shadows a guarded global is a
        # different variable entirely (no `global` declaration)
        out = lint(tmp_path, """
            import threading
            _LOCK = threading.Lock()
            _cache = None
            _GUARDED_BY = {"_cache": "_LOCK"}

            def local_only():
                _cache = []
                _cache.append(1)
                return _cache

            def param_shadow(_cache):
                return len(_cache)

            def real_touch():
                global _cache
                _cache = []   # BAD: writes the guarded global unlocked
        """, GuardedByChecker())
        assert len(out) == 1
        assert "real_touch" in out[0].message

    def test_module_global_map(self, tmp_path):
        out = lint(tmp_path, """
            import threading
            _LOCK = threading.Lock()
            _cache = None
            _GUARDED_BY = {"_cache": "_LOCK"}

            def good():
                with _LOCK:
                    return _cache

            def bad():
                return _cache
        """, GuardedByChecker())
        assert len(out) == 1
        assert "bad" in out[0].message

    def test_suppression_comment(self, tmp_path):
        out = lint(tmp_path, """
            class Box:
                _GUARDED_BY = {"_n": "_lock"}
                def peek(self):
                    return self._n  # cakelint: ignore[CK-LOCK]
        """, GuardedByChecker())
        assert out == []

    def test_suppression_multi_id_with_spaces(self, tmp_path):
        out = lint(tmp_path, """
            class Box:
                _GUARDED_BY = {"_n": "_lock"}
                def peek(self):
                    return self._n  # cakelint: ignore[CK-WIRE, CK-LOCK]
        """, GuardedByChecker())
        assert out == []


# -- CK-JIT: trace purity -------------------------------------------------

class TestTracePurity:
    def test_time_in_jitted_fn_flagged(self, tmp_path):
        out = lint(tmp_path, """
            import time, jax
            def step(x):
                t = time.perf_counter()
                return x + t
            f = jax.jit(step)
        """, TracePurityChecker())
        assert len(out) == 1
        assert "time.perf_counter" in out[0].message

    def test_partial_and_decorator_resolved(self, tmp_path):
        out = lint(tmp_path, """
            import jax
            from functools import partial

            def inner(x, k):
                print("traced once")
                return x * k
            g = jax.jit(partial(inner, k=2))

            @partial(jax.jit, static_argnums=(0,))
            def decorated(n, x):
                REJECTED.inc()
                return x * n
        """, TracePurityChecker())
        assert {f.key for f in out} == {"inner:print",
                                        "decorated:REJECTED.inc"}

    def test_shard_map_body_checked(self, tmp_path):
        out = lint(tmp_path, """
            import random, jax
            from jax import shard_map
            def stage(x):
                return x * random.random()
            f = jax.jit(shard_map(stage, mesh=None))
        """, TracePurityChecker())
        assert len(out) == 1
        assert "random.random" in out[0].message

    def test_pure_and_host_side_ok(self, tmp_path):
        out = lint(tmp_path, """
            import time, jax
            def pure(x):
                return jax.random.fold_in(x, 1)  # keyed: fine
            f = jax.jit(pure)
            def host_loop(f, x):
                t0 = time.perf_counter()  # not traced: fine
                print(f(x))
        """, TracePurityChecker())
        assert out == []


# -- CK-WIRE: recv deadlines, resources, protocol arms --------------------

class TestWireSafety:
    def test_recv_without_timeout_flagged(self, tmp_path):
        out = lint(tmp_path, """
            def pump(conn):
                t, payload = conn.recv()
        """, WireSafetyChecker())
        assert len(out) == 1
        assert out[0].key == "recv:conn"

    def test_recv_explicit_ok(self, tmp_path):
        out = lint(tmp_path, """
            def pump(conn, sock):
                conn.recv(timeout=5.0)
                conn.recv(timeout=None)  # explicit block-forever decision
                sock.recv(4096)          # raw byte read: framing bounds it
        """, WireSafetyChecker())
        assert out == []

    def test_msgtype_missing_arm_flagged(self, tmp_path):
        repo = tmp_path
        (repo / "proto.py").write_text(textwrap.dedent("""
            from enum import IntEnum
            class MsgType(IntEnum):
                HELLO = 1
                ORPHAN = 2
        """))
        (repo / "peer.py").write_text(textwrap.dedent("""
            from proto import MsgType
            def talk(conn):
                conn.send(MsgType.HELLO)
                conn.send(MsgType.ORPHAN, b"x")
                t, _ = conn.recv(timeout=1)
                if t == MsgType.HELLO:
                    return True
        """))
        out = core.run_checkers([WireSafetyChecker()],
                                roots=[str(repo)], repo_root=repo)
        assert [f.key for f in out] == ["MsgType.ORPHAN:dispatch"]

    def test_msgtype_pass_skipped_on_file_scoped_scan(self):
        """'never sent anywhere' is meaningless when 'anywhere' is one
        file: linting protocol.py alone must not spray bogus MsgType
        findings (the per-module arms still run)."""
        out = core.run_checkers(
            [WireSafetyChecker()],
            roots=["cake_tpu/runtime/protocol.py"])
        assert [f for f in out if f.key.startswith("MsgType.")] == []

    def test_frame_const_missing_arm_flagged(self, tmp_path):
        # the declared XFER_* family is judged tree-wide like MsgType:
        # a constant with a send arm but no dispatch arm (or vice versa)
        # is protocol skew waiting to happen
        out = lint_full(tmp_path, {
            "cake_tpu/disagg/transfer.py": """
                XFER_SNAPSHOT = 32
                XFER_ACK = 33
                XFER_REJECT = 34
                def pump(conn):
                    conn.send(XFER_SNAPSHOT, b"x")
                    conn.send(XFER_ACK)
                    t, _ = conn.recv(timeout=1)
                    if t == XFER_ACK:
                        return True
                    if t == XFER_REJECT:
                        return False
            """,
        }, WireSafetyChecker())
        assert [f.key for f in out] == ["frame:XFER_SNAPSHOT:dispatch",
                                        "frame:XFER_REJECT:send"]

    def test_frame_const_both_arms_ok_cross_module(self, tmp_path):
        # arms may live in different modules (sender here, receiver
        # there) — and re-exported access (transfer.XFER_ACK) counts
        out = lint_full(tmp_path, {
            "cake_tpu/disagg/transfer.py": """
                XFER_SNAPSHOT = 32
                def send(conn):
                    conn.send(XFER_SNAPSHOT, b"x")
            """,
            "cake_tpu/disagg/receiver.py": """
                from cake_tpu.disagg import transfer
                def handle(t):
                    return t == transfer.XFER_SNAPSHOT
            """,
        }, WireSafetyChecker())
        assert out == []


# -- CK-CLAIM: declared acquire/release pairs ------------------------------

class TestClaims:
    # the fd rule (migrated from CK-WIRE arm 2): same shapes, same keys
    def test_leaky_acquisition_flagged(self, tmp_path):
        out = lint(tmp_path, """
            import socket
            def dial(host, port, Connection):
                sock = socket.create_connection((host, port))
                sock.setsockopt(1, 2, 3)   # may raise: sock leaks
                return Connection(sock=sock)
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].checker == "CK-CLAIM"
        assert out[0].key == "res:create_connection:dial:sock"

    def test_protected_and_immediate_ok(self, tmp_path):
        out = lint(tmp_path, """
            import socket
            def good_with(path):
                with open(path) as f:
                    return f.read()
            def good_immediate(host, Connection):
                sock = socket.create_connection((host, 1))
                return Connection(sock=sock)
            def good_protected(host, Connection):
                sock = socket.create_connection((host, 1))
                try:
                    sock.setsockopt(1, 2, 3)
                except Exception:
                    sock.close()
                    raise
                return Connection(sock=sock)
            class Owner:
                def open(self, path):
                    self._fh = open(path, "a")  # ownership moved
        """, ClaimChecker())
        assert out == []

    def test_read_is_not_a_release(self, tmp_path):
        # `data = sock.recv(n)` is a READ; the caller still owns the
        # socket, and the raising parse after it must keep the finding
        out = lint(tmp_path, """
            import socket
            def probe(host, parse):
                s = socket.create_connection((host, 1))
                data = s.recv(100)
                return parse(data)   # may raise: s leaks
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].key == "res:create_connection:probe:s"

    def test_late_try_does_not_cover_early_risk(self, tmp_path):
        # a try/finally that closes the var but starts AFTER a raising
        # statement does not protect the held-bare region before it
        out = lint(tmp_path, """
            import socket
            def serve(host, risky_setup, use):
                s = socket.create_connection((host, 1))
                risky_setup()        # raises -> s leaks
                try:
                    use(s)
                finally:
                    s.close()
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].key == "res:create_connection:serve:s"

    def test_adjacent_try_protects(self, tmp_path):
        # ...but the same try as the VERY NEXT statement does protect,
        # including when the acquisition sits inside its own try (the
        # chaos-proxy shape)
        out = lint(tmp_path, """
            import socket
            def dial(host, use):
                s = socket.create_connection((host, 1))
                try:
                    use(s)
                finally:
                    s.close()
            def dial_nested(host, setup, consume):
                try:
                    s = socket.create_connection((host, 1))
                except OSError:
                    return None
                try:
                    setup(s)
                except OSError:
                    s.close()
                    raise
                return consume(s)
        """, ClaimChecker())
        assert out == []

    def test_store_in_container_is_a_handoff(self, tmp_path):
        # storing a resource in a longer-lived owner transfers ownership
        # — both the bound and the unbound spelling
        out = lint(tmp_path, """
            import socket
            def pool_up(hosts, conns):
                for h in hosts:
                    c = socket.create_connection((h, 1))
                    conns.append(c)
            class Pool:
                def grow(self, path):
                    self.files.append(open(path))
        """, ClaimChecker())
        assert out == []

    def test_guarded_conditional_close_ok(self, tmp_path):
        # the worker accept-loop idiom: the guard test is part of the
        # release decision, not held-bare work
        out = lint(tmp_path, """
            def loop(listener, stop, handle):
                conn = listener.accept()
                if stop.is_set():
                    conn.close()
                    return
                handle(conn)
        """, ClaimChecker())
        assert out == []

    def test_second_acquisition_is_risky(self, tmp_path):
        # a second dial that raises strands the first socket — binding
        # acquires are never excluded from the held-bare risk set
        out = lint(tmp_path, """
            import socket
            def bridge(h1, h2):
                a = socket.create_connection((h1, 1))
                b = socket.create_connection((h2, 1))
                a.close()
                b.close()
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].key == "res:create_connection:bridge:a"

    # kvpool page-claim rules
    def test_pin_handoff_after_dispatch_flagged(self, tmp_path):
        # THE import-land bug class: pins taken in a loop, collected
        # into a list, but the hand-off to the owning record sits after
        # a device dispatch — the day that dispatch raises, the pinned
        # pages leak forever (nothing ever unpins them)
        out = lint(tmp_path, """
            def land(self, rec, staging, need):
                pages = []
                for _ in range(need):
                    pid = self.pool.alloc()
                    self.pool.pin(pid)
                    self.pool.unref(pid)
                    pages.append(pid)
                self.cache = self.scatter(self.cache, staging)  # raises?
                rec["pages"] = pages
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].key == "claim:kvpool.pin:pin:land:pages"

    def test_pin_handoff_before_dispatch_ok(self, tmp_path):
        # the fix shape: the record owns the pins BEFORE anything that
        # can raise — an abort/TTL sweep can always release them
        out = lint(tmp_path, """
            def land(self, rec, staging, need):
                pages = []
                for _ in range(need):
                    pid = self.pool.alloc()
                    self.pool.pin(pid)
                    self.pool.unref(pid)
                    pages.append(pid)
                rec["pages"] = pages
                self.cache = self.scatter(self.cache, staging)
        """, ClaimChecker())
        assert out == []

    def test_ref_loop_needs_protected_release(self, tmp_path):
        # refs over an existing table: work between the ref loop and
        # the unref loop leaks on its exception edge...
        out = lint(tmp_path, """
            def attach_bad(self, table, splice):
                for pid in table:
                    self.pool.ref(pid)
                splice()             # may raise: table's refs leak
                for pid in table:
                    self.pool.unref(pid)
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].key == "claim:kvpool.ref:ref:attach_bad:table"

    def test_ref_loop_protected_or_handed_off_ok(self, tmp_path):
        # ...unless a try releases on the error path, or the table is
        # handed to its owner first
        out = lint(tmp_path, """
            def attach_protected(self, table, splice):
                for pid in table:
                    self.pool.ref(pid)
                try:
                    splice()
                except Exception:
                    for pid in table:
                        self.pool.unref(pid)
                    raise
            def attach_handoff(self, table, splice):
                for pid in table:
                    self.pool.ref(pid)
                self.tables.append(table)
                splice()
        """, ClaimChecker())
        assert out == []

    def test_alloc_leak_on_exception_edge(self, tmp_path):
        # binding style: a fresh page held only by a local while a
        # raising statement sits before the hand-off
        out = lint(tmp_path, """
            def grow(self, splice):
                pid = self.pool.alloc()
                splice()               # may raise: pid leaks
                self.table.append(pid)
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].key == "res:alloc:grow:pid"

    def test_per_iteration_pin_tracked_by_name(self, tmp_path):
        # a loop pin on a plain name with no collecting list tracks the
        # NAME within the iteration: balanced-under-finally is clean,
        # bare work between pin and unpin is a leak on its exception
        # edge (not "untrackable")
        out = lint(tmp_path, """
            def scan_ok(self, streams, work):
                for s in streams:
                    pid = s.pid
                    self.pool.pin(pid)
                    try:
                        work(pid)
                    finally:
                        self.pool.unpin(pid)
        """, ClaimChecker())
        assert out == []
        out = lint(tmp_path, """
            def scan_bad(self, streams, work):
                for s in streams:
                    pid = s.pid
                    self.pool.pin(pid)
                    work(pid)          # may raise: this pin leaks
                    self.pool.unpin(pid)
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].key == "claim:kvpool.pin:pin:scan_bad:pid"
        assert "leak" in out[0].message

    def test_untracked_tokens_get_distinct_keys(self, tmp_path):
        # two different untracked tokens in one function must not share
        # a baseline key — one grandfathered claim cannot cover the other
        out = lint(tmp_path, """
            def hold(self, i, j):
                self.pool.pin(self.slots[i])
                self.pool.pin(self.others[j])
        """, ClaimChecker())
        assert len(out) == 2
        assert len({f.key for f in out}) == 2
        assert all("untracked" in f.key for f in out)

    def test_implementing_module_excluded(self, tmp_path):
        # kvpool/table.py IS the pair's implementation: `pin` calling
        # `ref` internally must not read as an unbalanced claim
        out = lint(tmp_path, """
            class PagePool:
                def pin(self, pid):
                    self.ref(pid)
                    self._pins[pid] += 1
        """, ClaimChecker(), rel="cake_tpu/kvpool/table.py")
        assert out == []

    # disagg transfer-id rule
    def test_import_begin_dropped_flagged(self, tmp_path):
        out = lint(tmp_path, """
            def ingest(self, payload, audit):
                meta = self.engine.import_begin(payload)
                audit(meta["xfer_id"])
        """, ClaimChecker())
        assert len(out) == 1
        assert out[0].key == "res:import_begin:ingest:meta"

    def test_import_begin_returned_or_aborted_ok(self, tmp_path):
        out = lint(tmp_path, """
            def ingest(self, payload):
                meta = self.engine.import_begin(payload)
                return meta
            def probe(self, payload, validate):
                meta = self.engine.import_begin(payload)
                try:
                    validate(meta)
                except ValueError:
                    # releasing through a projection of the claim
                    # (meta["xfer_id"]) releases the claim
                    self.engine.import_abort(meta["xfer_id"])
                    raise
                return meta
        """, ClaimChecker())
        assert out == []


# -- CK-THREAD: declared thread domains ------------------------------------

_ENGINE_MOD = """
    class Engine:
        _THREAD_DOMAIN = "engine"
        _THREAD_ALIASES = ("engine",)
        _THREAD_SAFE = ("_encode",)
        def step(self): pass
        def stats(self): pass
        def _encode(self, p): pass

    class Owner:
        _THREAD_DOMAIN = "engine"
        _THREAD_ALIASES = ("owner",)
        _GUARDED_BY = {"_queue": "_cond"}
        _THREAD_SAFE = ("submit", "snapshot")
        _THREAD_OF = {"start": "engine"}
        def submit(self, sess):
            with self._cond:
                self._queue.append(sess)   # inbox hand-off: the crossing
        def snapshot(self):
            with self._cond:
                return dict(self._cached)
        def start(self):
            self.engine.step()             # engine by _THREAD_OF: fine
        def _run(self):
            self.engine.step()             # engine-domain body: fine
"""


class TestThreadDomains:
    def test_cross_domain_direct_call_flagged(self, tmp_path):
        out = lint_full(tmp_path, {
            "pkg/engine_mod.py": _ENGINE_MOD,
            "pkg/handlers.py": """
                _THREAD_DOMAIN = "handler"
                def handle(owner, prompt):
                    owner._run()                 # BAD: engine-domain method
                def handle_safe(owner, sess):
                    owner.submit(sess)           # declared crossing point
                def tokenize(engine, p):
                    return engine._encode(p)     # _THREAD_SAFE method
            """,
        }, ThreadDomainChecker())
        assert len(out) == 1
        assert out[0].checker == "CK-THREAD"
        assert out[0].key == "Owner._run:handle"
        assert "'engine'" in out[0].message and "handler" in out[0].message

    def test_crossing_point_body_checked_as_any(self, tmp_path):
        # a _THREAD_SAFE method that itself pokes domain state is
        # exactly the bug the declaration exists to catch — the
        # live-stats-walk shape this PR fixed in Scheduler.stats
        out = lint_full(tmp_path, {
            "pkg/engine_mod.py": _ENGINE_MOD,
            "pkg/bad_owner.py": """
                class Front:
                    _THREAD_DOMAIN = "engine"
                    _THREAD_SAFE = ("stats",)
                    def stats(self):
                        return self.engine.stats()   # BAD: any -> engine
            """,
        }, ThreadDomainChecker())
        assert len(out) == 1
        assert out[0].key == "Engine.stats:Front.stats"

    def test_guarded_by_lock_is_a_crossing(self, tmp_path):
        out = lint_full(tmp_path, {
            "pkg/engine_mod.py": _ENGINE_MOD,
            "pkg/locked.py": """
                _THREAD_DOMAIN = "handler"
                _GUARDED_BY = {"shared": "_table_lock"}
                def read(owner, _table_lock):
                    with _table_lock:
                        return owner._run()   # declared lock: allowed
            """,
        }, ThreadDomainChecker())
        assert out == []

    def test_dunder_and_unannotated_callers_exempt(self, tmp_path):
        out = lint_full(tmp_path, {
            "pkg/engine_mod.py": _ENGINE_MOD,
            "pkg/wrapper.py": """
                _THREAD_DOMAIN = "handler"
                class Wrapper:
                    def __init__(self, engine):
                        engine.step()   # construction happens-before
            """,
            "pkg/script.py": """
                def main(engine):
                    engine.step()       # unannotated caller: not checked
            """,
        }, ThreadDomainChecker())
        assert out == []

    def test_constructor_taint_resolves_receivers(self, tmp_path):
        # `eng = Engine()` binds the handle scope-insensitively — the
        # CK-ENGINE philosophy — so a later cross-domain call through
        # that name is caught without alias declarations
        out = lint_full(tmp_path, {
            "pkg/engine_mod.py": _ENGINE_MOD,
            "pkg/boot.py": """
                _THREAD_DOMAIN = "handler"
                from pkg.engine_mod import Engine
                eng = Engine()
                def tick():
                    eng.step()
            """,
        }, ThreadDomainChecker())
        assert len(out) == 1
        assert out[0].key == "Engine.step:tick"

    def test_any_domain_class_imposes_nothing(self, tmp_path):
        out = lint_full(tmp_path, {
            "pkg/shared.py": """
                class Box:
                    _THREAD_DOMAIN = "any"
                    def put(self, x): pass
            """,
            "pkg/handlers.py": """
                _THREAD_DOMAIN = "handler"
                from pkg.shared import Box
                box = Box()
                def handle(x):
                    box.put(x)
            """,
        }, ThreadDomainChecker())
        assert out == []


# -- the CK-THREAD runtime twin (CAKE_THREAD_STRICT) -----------------------

class TestThreadStrictTwin:
    def test_assert_fires_cross_thread_only(self):
        from cake_tpu.runtime import threadcheck

        stamp = threadcheck.DomainStamp("engine")
        prev = threadcheck.set_strict(True)
        try:
            stamp.check("unstamped-is-vacuous")  # no owner yet: passes
            stamp.stamp()
            stamp.check("same-thread-ok")
            err: list[str] = []

            def other():
                try:
                    stamp.check("BatchGenerator.step")
                except RuntimeError as e:
                    err.append(str(e))

            t = threading.Thread(target=other)
            t.start()
            t.join()
            assert len(err) == 1
            assert "BatchGenerator.step" in err[0]
            assert "engine" in err[0]
            stamp.clear()  # owner gone: checks are vacuous again
            t2 = threading.Thread(target=lambda: stamp.check("after-clear"))
            t2.start()
            t2.join()
        finally:
            threadcheck.set_strict(prev)

    def test_disabled_twin_never_raises(self):
        from cake_tpu.runtime import threadcheck

        stamp = threadcheck.DomainStamp("engine")
        prev = threadcheck.set_strict(False)
        try:
            stamp.stamp()
            t = threading.Thread(target=lambda: stamp.check("off"))
            t.start()
            t.join()  # no raise: disabled twin is a bool read
        finally:
            threadcheck.set_strict(prev)

    def test_pagepool_mutators_guarded(self):
        # the real wiring: a pool whose stamp is owned by another thread
        # refuses foreign-thread page claims, message naming the mutator
        from cake_tpu.kvpool.table import PagePool
        from cake_tpu.runtime import threadcheck

        pool = PagePool(8, 4)
        prev = threadcheck.set_strict(True)
        try:
            t = threading.Thread(target=pool._domain_stamp.stamp)
            t.start()
            t.join()
            with pytest.raises(RuntimeError, match="PagePool.alloc"):
                pool.alloc()
            pool._domain_stamp.clear()
            pid = pool.alloc()  # ownerless: single-threaded drive works
            assert pool.refcount(pid) == 1
        finally:
            threadcheck.set_strict(prev)


# -- framework: baseline, suppression, CLI --------------------------------

class TestBaseline:
    def _finding(self, key="BatchGenerator.step", path="examples/x.py",
                 line=10):
        return core.Finding(checker="CK-ENGINE", path=path, line=line,
                            col=0, message="m", key=key)

    def test_suppresses_by_key_not_line(self):
        entry = baseline_mod.BaselineEntry(
            checker="CK-ENGINE", path="examples/x.py",
            key="BatchGenerator.step", justification="demo")
        new, suppressed, stale = baseline_mod.apply(
            [self._finding(line=10), self._finding(line=99)], [entry])
        assert new == [] and len(suppressed) == 2 and stale == []

    def test_stale_entry_reported(self):
        entry = baseline_mod.BaselineEntry(
            checker="CK-ENGINE", path="examples/x.py", key="gone",
            justification="was fixed")
        new, suppressed, stale = baseline_mod.apply(
            [self._finding()], [entry])
        assert len(new) == 1 and stale == [entry]

    def test_stale_respects_run_scope(self):
        # a subset run must not call live out-of-scope entries "fixed"
        entry = baseline_mod.BaselineEntry(
            checker="CK-ENGINE", path="examples/x.py",
            key="BatchGenerator.step", justification="demo")
        _, _, stale = baseline_mod.apply(
            [], [entry], checker_ids={"CK-METRIC"}, paths={"examples/x.py"})
        assert stale == []
        _, _, stale = baseline_mod.apply(
            [], [entry], checker_ids={"CK-ENGINE"}, paths={"other.py"})
        assert stale == []
        _, _, stale = baseline_mod.apply(
            [], [entry], checker_ids={"CK-ENGINE"},
            paths={"examples/x.py"})
        assert stale == [entry]

    def test_justification_required(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"version": 1, "entries": [
            {"checker": "CK-X", "path": "a.py", "key": "k"}]}))
        with pytest.raises(ValueError, match="justification"):
            baseline_mod.load(p)

    def test_roundtrip(self, tmp_path):
        p = tmp_path / "b.json"
        entries = baseline_mod.from_findings([self._finding()], "why")
        baseline_mod.save(p, entries)
        assert baseline_mod.load(p) == entries


class TestUnusedSuppressions:
    def _scan(self, tmp_path, source, checkers):
        f = tmp_path / "mod.py"
        f.write_text(textwrap.dedent(source))
        mods, pf = core.load_modules([str(f)], repo_root=tmp_path)
        unused: list = []
        findings = core.check_modules(mods, checkers, True, pf,
                                      unused_out=unused)
        return findings, unused

    def test_unused_vs_used_ignores(self, tmp_path):
        findings, unused = self._scan(tmp_path, """
            class Box:
                _GUARDED_BY = {"_n": "_lock"}
                def peek(self):
                    return self._n  # cakelint: ignore[CK-LOCK]
                def clean(self):
                    return 1  # cakelint: ignore[CK-LOCK]
        """, [GuardedByChecker()])
        assert findings == []
        # the peek ignore suppressed a live finding; the clean one
        # suppressed nothing and is reported like a stale baseline entry
        assert [(u["line"], u["ids"]) for u in unused] == [
            (7, ["CK-LOCK"])]

    def test_bare_ignore_counts_and_prose_does_not(self, tmp_path):
        findings, unused = self._scan(tmp_path, '''
            """Docs may say cakelint: ignore[CK-LOCK] without meaning it."""
            class Box:
                _GUARDED_BY = {"_n": "_lock"}
                def peek(self):
                    return self._n  # cakelint: ignore
        ''', [GuardedByChecker()])
        # the docstring mention is neither a suppression nor "unused";
        # the bare comment suppresses every checker and counts as used
        assert findings == [] and unused == []

    def test_string_literal_hash_is_not_a_comment(self, tmp_path):
        # a '#' inside a string literal must neither suppress a finding
        # on that line nor read as an (unused) suppression comment —
        # comment detection is token-based, not substring-based
        findings, unused = self._scan(tmp_path, '''
            HINT = "append # cakelint: ignore[CK-LOCK] to the line"
            class Box:
                _GUARDED_BY = {"_n": "_lock"}
                def peek(self):
                    return self._n, "# cakelint: ignore[CK-LOCK]"
        ''', [GuardedByChecker()])
        assert len(findings) == 1  # the peek touch is NOT suppressed
        assert unused == []        # ...and neither string is "unused"

    def test_subset_runs_cannot_judge(self, tmp_path):
        # mirror of stale-baseline scoping: a run without the
        # suppressing checker cannot tell "unused" from "not re-checked"
        # — the CLI only passes unused_out on full all-checker scans
        f = tmp_path / "mod.py"
        f.write_text("X = 1  # cakelint: ignore[CK-LOCK]\n")
        mods, pf = core.load_modules([str(f)], repo_root=tmp_path)
        out = core.check_modules(mods, [MetricsCatalogChecker()], True, pf)
        assert out == []  # no unused_out passed -> nothing judged


class TestCli:
    def test_exit_codes_and_json(self, tmp_path, capsys):
        from cake_tpu.analysis.__main__ import main

        bad = tmp_path / "bad.py"
        bad.write_text("from cake_tpu.obs import metrics as m\n"
                       "c = m.counter('serve.typo_ms')\n")
        assert main([str(bad), "--json"]) == 1
        report = json.loads(capsys.readouterr().out)
        assert report["summary"]["new"] == 1
        assert report["new"][0]["checker"] == "CK-METRIC"

        base = tmp_path / "base.json"
        assert main([str(bad), "--write-baseline", str(base)]) == 0
        # stub justifications must be replaced before load() accepts
        # them — accept the stub here to prove the grandfather path
        data = json.loads(base.read_text())
        for e in data["entries"]:
            e["justification"] = "fixture"
        base.write_text(json.dumps(data))
        assert main([str(bad), "--baseline", str(base)]) == 0

        good = tmp_path / "good.py"
        good.write_text("x = 1\n")
        assert main([str(good)]) == 0

    def test_list_and_unknown_checker(self, capsys):
        from cake_tpu.analysis.__main__ import main

        assert main(["--list"]) == 0
        listed = capsys.readouterr().out
        for cls in analysis.ALL_CHECKERS:
            assert cls.id in listed
        assert main(["--checkers", "CK-NOPE"]) == 2


# -- catalog + strict registry -------------------------------------------

class TestCatalog:
    def test_declarations_well_formed(self):
        from cake_tpu.obs import catalog

        kinds = {catalog.COUNTER, catalog.GAUGE, catalog.HISTOGRAM}
        for name, (kind, help_) in {**catalog.SERIES,
                                    **catalog.DYNAMIC}.items():
            assert kind in kinds, name
            assert help_, name
        assert catalog.is_declared("wire.bytes_out")
        assert catalog.is_declared("master.segment3.decode_ms")
        assert catalog.is_declared("cluster.w0.rtt_ms")
        assert not catalog.is_declared("wire.byte_out")
        assert catalog.kind_of("serve.ttft_ms") == catalog.HISTOGRAM
        assert catalog.kind_of("nope") is None

    def test_strict_registry_enforces_catalog(self):
        from cake_tpu.obs import metrics

        reg = metrics.Registry(enabled=True, strict=True)
        reg.counter("wire.bytes_out")  # declared: fine
        with pytest.raises(ValueError, match="not declared"):
            reg.counter("wire.byte_out")
        with pytest.raises(ValueError, match="not declared"):
            reg.register("serve.nope", metrics.Counter("serve.nope"))

    def test_every_catalog_entry_is_used(self):
        """The reverse check: a declared series nobody emits is a stale
        doc. Scan the tree for series-name literals/patterns and compare
        (the static half only — DYNAMIC families count via patterns)."""
        import ast as ast_mod

        from cake_tpu.obs import catalog

        used: set[str] = set()
        mods, _ = core.load_modules()
        for mod in mods:
            for node in ast_mod.walk(mod.tree):
                if not isinstance(node, ast_mod.Call):
                    continue
                name = core.call_name(node)
                if name.lower() not in ("counter", "gauge", "histogram"):
                    continue
                if not node.args:
                    continue
                lit = core.literal_str(node.args[0])
                pat = core.fstring_pattern(node.args[0])
                if lit:
                    used.add(lit)
                if pat:
                    used.add(pat)
        unused = [n for n in catalog.SERIES if n not in used]
        unused += [p for p in catalog.DYNAMIC if p not in used]
        assert unused == [], f"catalog entries nothing emits: {unused}"


# -- the gate's gate: repo self-run ---------------------------------------

class TestSelfRun:
    def test_repo_clean_at_head(self):
        """The tree + committed baseline = zero new findings, zero stale
        entries, zero unused suppressions. This is exactly what
        `make lint` enforces in CI — CK-CLAIM and CK-THREAD included."""
        mods, parse_findings = core.load_modules()
        unused: list = []
        findings = core.check_modules(mods, analysis.default_checkers(),
                                      True, parse_findings,
                                      unused_out=unused)
        entries = baseline_mod.load(core.REPO_ROOT /
                                    "analysis-baseline.json")
        new, suppressed, stale = baseline_mod.apply(findings, entries)
        assert new == [], "\n".join(f.render() for f in new)
        assert stale == [], [e.match_key for e in stale]
        assert unused == []
        # the baseline is not a dumping ground: only the deliberate
        # direct-drive sites and the protocol-compat member live there
        assert {e.checker for e in entries} <= {"CK-ENGINE", "CK-WIRE"}

    def test_every_checker_registered(self):
        ids = {c.id for c in analysis.default_checkers()}
        assert ids == {"CK-METRIC", "CK-ENGINE", "CK-LOCK", "CK-JIT",
                       "CK-WIRE", "CK-CLAIM", "CK-THREAD"}
