"""Program spans (``repro.utils.spans``): kept only inside a profiler
session, nested by the open-span stack, on the profiler's own clock; the
serve scheduler's and the async engine's spans and intervals; the named
scopes of the compiled programs; and the per-layer readers that take
them. Every profiler session starts and stops inside a test body."""
import glob
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import VFLConfig, get_config, reduced
from repro.configs.paper_mlp import PaperMLPConfig
from repro.core import async_engine, cascade
from repro.core.async_engine import EngineConfig
from repro.data import make_classification, vertical_partition
from repro.federation import Federation, serving
from repro.models import common, tabular
from repro.optim import sgd
from repro.utils import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class session:
    """A profiler session writing to ``logdir``, as the operator's
    ``jax.profiler.trace`` would. A span outside any session goes first,
    as the program's own spans do between captures: it closes the
    buffer, so each test reads its own session alone."""

    def __init__(self, logdir) -> None:
        self.logdir = str(logdir)

    def __enter__(self):
        with spans.span("vfl.test.between_sessions"):
            pass
        jax.profiler.start_trace(self.logdir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


def named(name):
    return [s for s in spans.spans() if s.name == name]


# ------------------------------------------------------------ the module --

def test_nothing_is_kept_without_a_session(tmp_path):
    with session(tmp_path / "a"):
        with spans.span("vfl.test.kept"):
            pass
    assert [s.name for s in spans.spans()] == ["vfl.test.kept"]
    # outside a session: annotations only, the last session's records stay
    with spans.span("vfl.test.dropped", rid=1):
        spans.record("vfl.test.dropped_too", 0, 1)
    assert [s.name for s in spans.spans()] == ["vfl.test.kept"]


def test_records_nest_and_hold_only_the_newest_session(tmp_path):
    with session(tmp_path / "a"):
        with spans.span("vfl.test.old"):
            pass
    with spans.span("vfl.test.between"):         # closes the buffer
        pass
    with session(tmp_path / "b"):
        with spans.span("vfl.test.outer", rid=7):
            with spans.span("vfl.test.inner"):
                spans.record("vfl.test.past", 5, 9, rid=3)
            with spans.span("vfl.test.sibling"):
                pass
        spans.record("vfl.test.top", 1, 2)
    got = {s.name: s for s in spans.spans()}
    assert set(got) == {"vfl.test.outer", "vfl.test.inner", "vfl.test.past",
                        "vfl.test.sibling", "vfl.test.top"}
    assert got["vfl.test.outer"].parent is None
    assert got["vfl.test.outer"].ids == {"rid": 7}
    assert got["vfl.test.inner"].parent == "vfl.test.outer"
    assert got["vfl.test.sibling"].parent == "vfl.test.outer"
    assert got["vfl.test.past"].parent == "vfl.test.inner"
    assert (got["vfl.test.past"].start_ns, got["vfl.test.past"].end_ns,
            got["vfl.test.past"].ids) == (5, 9, {"rid": 3})
    assert got["vfl.test.top"].parent is None
    outer, inner = got["vfl.test.outer"], got["vfl.test.inner"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_spans_share_the_profilers_clock(tmp_path):
    """Each kept span's start, less the capture's ``profile_start_time``,
    is its TraceAnnotation event's start in the written xplane."""
    from jax.profiler import ProfileData
    logdir = tmp_path / "clock"
    with session(logdir):
        with spans.span("vfl.test.clock_a"):
            time.sleep(0.002)
            with spans.span("vfl.test.clock_b"):
                time.sleep(0.002)
        with spans.span("vfl.test.clock_c"):
            time.sleep(0.002)
    kept = {s.name: s for s in spans.spans()}
    path = sorted(glob.glob(str(logdir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    t0, events = None, {}
    for plane in pd.planes:
        if plane.name == "Task Environment":
            t0 = {k: v for k, v in plane.stats}["profile_start_time"]
        for line in plane.lines:
            for ev in line.events:
                if ev.name in kept:
                    events[ev.name] = (ev.start_ns, ev.end_ns)
    assert t0 is not None and set(events) == set(kept)
    for name, s in kept.items():
        start, end = events[name]
        assert abs((s.start_ns - t0) - start) < 50_000, name
        assert abs((s.end_ns - t0) - end) < 50_000, name


# --------------------------------------------------------- the scheduler --

def tiny_lm(**overrides):
    return reduced(get_config("phi3-mini-3.8b"), d_model=64, n_heads=2,
                   n_kv_heads=1, d_ff=128, vocab_size=256, **overrides)


@pytest.fixture(scope="module")
def serve_setup():
    cfg = tiny_lm()
    seq = 12
    fed = Federation.build(cfg, VFLConfig(), EngineConfig(), n_clients=2,
                           seq_len=seq)
    params = common.materialize(fed.model.param_specs, jax.random.key(0))
    key = jax.random.key(1)
    specs = [(4, 8), (4, 5), (4, 6), (3, 4), (4, 3), (3, 7)]
    reqs = [(np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                           (pl,), 0, cfg.vocab_size)),
             gl, jax.random.fold_in(key, 100 + i))
            for i, (pl, gl) in enumerate(specs)]
    return fed, params, reqs


def closed_loop(fed, params, reqs, max_steps=4):
    """Three callers over two slots: each retired request sends the next
    one, ``run(max_steps)`` at a time, as a serving front end would."""
    srv = fed.serve(params, max_batch=2, temperature=0.7)
    todo = list(reqs)
    for _ in range(3):
        p, g, k = todo.pop(0)
        srv.submit(p, g, key=k)
    out = {}
    while srv.pending or srv.active:
        for r in srv.run(max_steps=max_steps):
            out[r.rid] = r
            if todo:
                p, g, k = todo.pop(0)
                srv.submit(p, g, key=k)
    return srv, out


def test_scheduler_spans_in_a_closed_loop(serve_setup, tmp_path):
    fed, params, reqs = serve_setup
    closed_loop(fed, params, reqs)                # compile outside
    with session(tmp_path):
        srv, out = closed_loop(fed, params, reqs)
    assert len(out) == len(reqs)
    queued = named("vfl.sched.queued")
    assert len(queued) == srv.admitted == len(reqs)
    assert sorted(s.ids["rid"] for s in queued) == sorted(out)
    assert all(s.end_ns >= s.start_ns for s in queued)
    assert len(named("vfl.sched.retire_fetch")) == srv.host_transfers > 0
    assert srv.prefill_waves == len(named("vfl.sched.prefill_wave")) > 0
    runs = named("vfl.sched.run")
    drained = sorted(named("vfl.sched.drained"), key=lambda s: s.start_ns)
    assert drained and {s.ids["after"] for s in drained} <= {"entry",
                                                            "retire"}
    assert sum(s.ids["after"] == "entry" for s in drained) == len(runs)
    for a, b in zip(drained, drained[1:]):
        assert a.end_ns <= b.start_ns
    for d in drained:
        assert d.start_ns <= d.end_ns
        assert any(r.start_ns <= d.start_ns and d.end_ns <= r.end_ns
                   for r in runs)
    parents = {s.name: s.parent for s in spans.spans()}
    assert parents["vfl.sched.admit"] == "vfl.sched.run"
    assert parents["vfl.sched.prefill_wave"] == "vfl.sched.admit"
    assert parents["vfl.sched.install"] == "vfl.sched.admit"
    assert parents["vfl.sched.block"] == "vfl.sched.run"
    assert parents["vfl.sched.retire"] == "vfl.sched.run"
    assert parents["vfl.sched.retire_fetch"] == "vfl.sched.retire"
    assert parents["vfl.sched.run"] is None
    blocks = named("vfl.sched.block")
    assert sum(b.ids["k"] for b in blocks) == srv.steps
    assert sum(b.ids["k"] * b.ids["occupancy"] for b in blocks) \
        == srv.generated_tokens


def test_a_session_changes_no_token_ledger_or_snapshot(serve_setup,
                                                       tmp_path):
    fed, params, reqs = serve_setup

    def drain_and_snapshot():
        srv, out = closed_loop(fed, params, reqs)
        # a second scheduler stopped mid-drain, for a snapshot with work
        # in every part of it
        mid = fed.serve(params, max_batch=2, temperature=0.7)
        for p, g, k in reqs:
            mid.submit(p, g, key=k)
        mid.run(max_steps=5)
        return out, mid.snapshot()

    plain_out, plain_snap = drain_and_snapshot()
    with session(tmp_path):
        traced_out, traced_snap = drain_and_snapshot()
    assert named("vfl.sched.run")
    assert set(plain_out) == set(traced_out)
    for rid, a in plain_out.items():
        b = traced_out[rid]
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.ledger.messages == b.ledger.messages
        assert a.status == b.status
    assert json.dumps(plain_snap.meta, sort_keys=True) \
        == json.dumps(traced_snap.meta, sort_keys=True)
    assert plain_snap.flat.keys() == traced_snap.flat.keys()
    for k, v in plain_snap.flat.items():
        np.testing.assert_array_equal(v, traced_snap.flat[k])


# ------------------------------------------------------------ the engine --

@pytest.fixture(scope="module")
def tabular_setup():
    cfg = PaperMLPConfig(n_features=32, n_classes=4, n_clients=4,
                         client_embed=16, server_embed=32)
    X, y = make_classification(0, 256, cfg.n_features, cfg.n_classes)
    Xp = jnp.asarray(vertical_partition(X, cfg.n_clients))
    params = common.materialize(tabular.param_specs(cfg), jax.random.key(0))
    vfl = VFLConfig(mu=1e-3, lr_server=0.05, lr_client=0.05)
    ec = EngineConfig(method="cascaded", steps=20, batch_size=8)
    return Federation.build(cfg, vfl, ec), params, Xp, jnp.asarray(y)


def test_engine_spans_per_run(tabular_setup, tmp_path):
    fed, params, Xp, y = tabular_setup
    fed.run(params, Xp, y)                         # compile outside
    with session(tmp_path):
        for _ in range(2):
            params = fed.run(params, Xp, y).params
    for name in ("vfl.engine.prepare", "vfl.engine.scan",
                 "vfl.engine.collect"):
        assert len(named(name)) == 2, name
    drained = named("vfl.engine.drained")
    assert [s.ids["after"] for s in drained] == ["entry", "collect"] * 2
    recs = sorted((s for s in spans.spans() if s.name in (
        "vfl.engine.prepare", "vfl.engine.scan", "vfl.engine.collect")),
        key=lambda s: s.start_ns)
    assert [s.name.rsplit(".", 1)[1] for s in recs] == [
        "prepare", "scan", "collect"] * 2
    for a, b in zip(recs, recs[1:]):
        assert a.end_ns <= b.start_ns
    prep, coll = named("vfl.engine.prepare"), named("vfl.engine.collect")
    for i in range(2):
        entry, tail = drained[2 * i], drained[2 * i + 1]
        assert entry.start_ns <= prep[i].start_ns
        assert entry.end_ns >= prep[i].end_ns
        assert coll[i].start_ns <= tail.start_ns
        assert tail.end_ns >= coll[i].end_ns


# ------------------------------------------------------ compiled scopes --

def op_names(hlo_text: str) -> str:
    return " ".join(part.split('"', 1)[0]
                    for part in hlo_text.split('op_name="')[1:])


def test_cascaded_step_scopes():
    cfg = PaperMLPConfig(n_features=16, n_classes=3, n_clients=2,
                         client_embed=8, server_embed=16)
    params = common.materialize(tabular.param_specs(cfg), jax.random.key(0))
    X, y = make_classification(0, 16, cfg.n_features, cfg.n_classes)
    batch = {"x_parts": jnp.asarray(vertical_partition(X, cfg.n_clients)),
             "y": jnp.asarray(y)}
    opt = sgd(0.05)
    step = cascade.make_cascaded_step(
        tabular.global_loss, tabular.CLIENT_KEYS,
        VFLConfig(zoo_queries=2), opt)
    text = op_names(jax.jit(step).lower(
        params, opt.init(params), batch, jax.random.key(1)
    ).compile().as_text())
    for scope in ("cascade.client_lanes", "cascade.server_fwd_bwd",
                  "cascade.server_lanes", "cascade.client_update",
                  "cascade.server_update"):
        assert scope in text, scope


def test_async_scan_body_scopes(tabular_setup):
    fed, params, Xp, y = tabular_setup
    runner, args, _, _ = async_engine._prepare(
        fed.adapter, fed.transport, fed.vfl, fed.engine, params, Xp, y,
        None, None)
    text = op_names(runner.lower(*args).compile().as_text())
    for scope in ("engine.client_zoo", "engine.server_step",
                  "engine.table_write"):
        assert scope in text, scope


def test_serve_program_scopes(serve_setup):
    fed, params, reqs = serve_setup
    srv, _ = closed_loop(fed, params, reqs)
    block = op_names(next(iter(srv._block_progs.values())).as_text())
    for scope in ("serve.sample", "serve.client_embed",
                  "serve.server_decode"):
        assert scope in block, scope
    chunk = serving.make_prefill_chunk(srv.adapter, srv.n_clients,
                                       srv.seq_len)
    caches = serving.zero_caches(srv.adapter, serving.MIN_ROWS, srv.seq_len)
    toks = jnp.zeros((serving.MIN_ROWS, srv.span), jnp.int32)
    text = op_names(chunk.lower(srv.params, toks, caches, 0, 0)
                    .compile().as_text())
    assert "serve.prefill" in text


# ------------------------------------------------------------ the readers --

def reader(name):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


@pytest.mark.parametrize("name", ["sched_drained.serve",
                                  "queue_wait_p50_ms.serve",
                                  "engine_drained.tabular"])
def test_reader_reads_nothing_without_a_trace(name, tmp_path):
    with session(tmp_path):
        spans.record("vfl.sched.drained", 0, 10 ** 6, after="entry")
        spans.record("vfl.sched.queued", 0, 10 ** 6, rid=0)
        spans.record("vfl.engine.drained", 0, 10 ** 6, after="entry")
    assert reader(name)({"out": {"trace": None, "e2e": {}},
                         "config": {}}) is None


def test_readers_on_a_synthetic_trace(tmp_path):
    ms = 10 ** 6
    with session(tmp_path):
        spans.record("vfl.sched.drained", 0, 3 * ms, after="entry")
        spans.record("vfl.sched.drained", 10 * ms, 11 * ms, after="retire")
        for rid, wait in enumerate((5, 1, 9)):
            spans.record("vfl.sched.queued", 0, wait * ms, rid=rid)
        spans.record("vfl.engine.drained", 0, 2 * ms, after="entry")
        spans.record("vfl.engine.drained", 7 * ms, 8 * ms, after="collect")
    rec = {"out": {"trace": {"window_s": 0.1, "busy_s": 0.09}, "e2e": {}},
           "config": {}}
    assert reader("sched_drained.serve")(rec) == pytest.approx(4.0)
    assert reader("queue_wait_p50_ms.serve")(rec) == pytest.approx(5.0)
    assert reader("engine_drained.tabular")(rec) == pytest.approx(3.0)
    # a session whose program recorded none of these reads nothing
    with session(tmp_path / "empty"):
        spans.record("vfl.test.other", 0, 1)
    for name in ("sched_drained.serve", "queue_wait_p50_ms.serve",
                 "engine_drained.tabular"):
        assert reader(name)(rec) is None, name
