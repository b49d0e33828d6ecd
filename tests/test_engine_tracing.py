"""What the async engine records about itself: host spans on the profiler's
clock, the roofline's forecast in ``dstats.predicted_s``, per-request stage
stamps on the host clock, and device programs named by kind. Nothing here
times anything."""
import glob
import os
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.core.lookahead import make_superiter_fn
from repro.models import Model
from repro.serving import (AsyncDuetEngine, DuetEngine, EngineConfig,
                           FinishEvent, Request)

KW = dict(max_slots=3, max_len=128, token_budget=48, page_size=8)
STAGES = ("submit", "first_dispatch", "first_fetch", "finish")


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("qwen3-4b"))
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def closed_loop(eng, clients: int, total: int):
    """``clients`` closed-loop clients, each sending its next request at
    ``engine.now`` when its previous one finishes, ``total`` in all."""
    reqs = []

    def send():
        r = Request(rid=len(reqs), arrival=0.0, prompt_len=9 + 7 * len(reqs),
                    output_len=3 + len(reqs) % 4)
        reqs.append(r)
        eng.submit(r, at=eng.now)

    for _ in range(clients):
        send()
    for ev in eng.events():
        if isinstance(ev, FinishEvent) and len(reqs) < total:
            send()
    return reqs


def host_spans(log_dir):
    """``(name, start_ns, end_ns, args)`` of every ``engine.*`` span."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("engine."))
    return out


@pytest.fixture(scope="module")
def traced(small_model, tmp_path_factory):
    """A closed loop served under a profiler session."""
    _, model, params = small_model
    eng = AsyncDuetEngine(model, params, EngineConfig(**KW))
    log_dir = str(tmp_path_factory.mktemp("trace"))
    now0, predicted0 = eng.now, eng.dstats.predicted_s
    jax.profiler.start_trace(log_dir)
    try:
        reqs = closed_loop(eng, clients=3, total=8)
    finally:
        jax.profiler.stop_trace()
    return dict(eng=eng, reqs=reqs, spans=host_spans(log_dir), now0=now0,
                predicted0=predicted0)


def by_name(spans, name):
    return [s for s in spans if s[0] == name]


def test_one_plan_and_one_fetch_per_super_iteration(traced):
    eng, spans = traced["eng"], traced["spans"]
    n = eng.dstats.super_iterations
    assert n > 0
    plans = Counter(s[3]["si"] for s in by_name(spans, "engine.plan"))
    # an idle pass plans with the index of the super-iteration to come
    assert all(plans[si] == 1 for si in range(1, n + 1))
    assert set(plans) <= set(range(1, n + 2))
    fetches = Counter(s[3]["si"] for s in by_name(spans, "engine.fetch"))
    assert sum(fetches.values()) == eng.dstats.host_syncs
    assert all(c == 1 for c in fetches.values())
    assert set(fetches) <= set(range(1, n + 1))
    emits = Counter(s[3]["si"] for s in by_name(spans, "engine.emit"))
    assert emits == fetches


def test_spans_of_a_super_iteration_are_flat_and_in_order(traced):
    """Plan ends before the super-iteration's first dispatch; a fetch
    follows the dispatches of the next super-iteration (the engine runs
    one ahead); no two phase spans overlap."""
    spans = traced["spans"]
    flat = sorted((s for s in spans if s[0] != "engine.compile"),
                  key=lambda s: s[1])
    for a, b in zip(flat, flat[1:]):
        assert a[2] <= b[1], (a[:3], b[:3])
    plan_end = {s[3]["si"]: s[2] for s in by_name(spans, "engine.plan")}
    first_dispatch = {}
    for s in by_name(spans, "engine.dispatch"):
        first_dispatch.setdefault(s[3]["si"], s[1])
    for si, t in first_dispatch.items():
        assert plan_end[si] <= t
    for s in by_name(spans, "engine.fetch"):
        if s[3]["si"] + 1 in first_dispatch:
            assert first_dispatch[s[3]["si"] + 1] <= s[1]


def test_dispatch_spans_carry_the_program_bucket(traced):
    eng, spans = traced["eng"], traced["spans"]
    ds = by_name(spans, "engine.dispatch")
    assert len(ds) == eng.dstats.dispatches
    assert len(by_name(spans, "engine.tiers")) == len(ds)
    for _, _, _, a in ds:
        assert {"si", "kb", "chunk", "duet", "predicted_ms"} <= set(a)
        assert a["kb"] > 0 or a["chunk"] > 0
        assert a["predicted_ms"] > 0
    assert any(a["kb"] > 0 for *_, a in ds)
    assert any(a["chunk"] > 0 for *_, a in ds)
    compiles = by_name(spans, "engine.compile")
    assert len(compiles) == eng.dstats.cache_misses
    for c in compiles:
        assert any(d[1] <= c[1] and c[2] <= d[2] and d[3]["si"] == c[3]["si"]
                   for d in ds)


def test_predicted_s_equals_the_virtual_clock_advance(traced):
    eng = traced["eng"]
    assert eng.dstats.predicted_s - traced["predicted0"] == pytest.approx(
        eng.now - traced["now0"], rel=1e-12)
    assert eng.dstats.predicted_s > 0


@pytest.mark.parametrize("pool", ["roomy", "preempting"])
def test_every_finished_request_has_ordered_stage_stamps(small_model,
                                                         traced, pool):
    if pool == "roomy":
        reqs = traced["reqs"]
    else:
        # a tiny pool forces preemption and recompute: the first dispatch
        # and first fetch stay those of the first attempt
        _, model, params = small_model
        eng = AsyncDuetEngine(model, params, EngineConfig(
            max_slots=2, max_len=64, token_budget=32, page_size=4,
            kv_pool_tokens=56))
        reqs = [Request(rid=i, arrival=0.0, prompt_len=20, output_len=12)
                for i in range(2)]
        eng.submit(reqs)
        eng.run()
        assert sum(r.preemptions for r in reqs) >= 1
    assert all(r.finish_reason == "completed" for r in reqs)
    for r in reqs:
        t = [r.stamps[k] for k in STAGES]
        assert t == sorted(t), (r.rid, r.stamps)


def test_summary_measures_ttft_and_tbt_from_the_stamps(small_model, traced):
    s = traced["eng"].run().summary()
    for stat in ("mean", "p50", "p99"):
        assert s[f"measured_{stat}_ttft_s"] > 0
        assert s[f"measured_{stat}_req_tbt_s"] >= 0
    r = traced["reqs"][0]
    assert r.measured_ttft() == r.stamps["first_fetch"] - r.stamps["submit"]
    # the synchronous engine stamps nothing: its summary is predictions only
    _, model, params = small_model
    eng = DuetEngine(model, params, EngineConfig(**KW))
    eng.submit(Request(rid=0, arrival=0.0, prompt_len=12, output_len=3))
    assert not any(k.startswith("measured_") for k in eng.run().summary())


@pytest.mark.parametrize("kind,kb,chunk,scopes", [
    ("decode", 2, 0, ("decode_step", "sample")),
    ("prefill", 0, 16, ("prefill_chunk", "sample")),
    ("mixed", 2, 16, ("decode_step", "prefill_chunk", "sample")),
    ("duet", 2, 16, ("duet_grid", "decode_step", "sample"))])
def test_superiteration_programs_are_named_by_kind(kind, kb, chunk, scopes):
    """The jitted program carries its kind (the module name a profile's
    ``XLA Modules`` line shows) and its phases' named scopes."""
    cfg = reduced(get_config("qwen3-4b"))
    model = Model(cfg, attn_kernel=True)
    B, W, pages = 3, 4, 16

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = spec((pages, 8, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
    state = jax.eval_shape(lambda: model.init_state_cache(B, jnp.float32))
    args = [params, [(pool, pool)] * cfg.num_layers, state, spec((B, 1)),
            spec((B,)), spec((B, W)), spec((2,), jnp.uint32),
            spec((B,), jnp.bool_), spec((1, max(chunk, 1))), spec((1, W)),
            spec(()), spec(()), spec(())]
    if kind == "duet":
        args.append(spec((B + chunk,)))
    prog = make_superiter_fn(model, kb, paged=True, chunk=chunk,
                             finish=chunk > 0, sample=chunk > 0,
                             duet_kernel=kind == "duet", donate=False)
    text = prog.lower(*args).as_text(debug_info=True)
    assert f"module @jit_superiter_{kind} " in text
    for scope in ("decode_step", "prefill_chunk", "duet_grid", "sample"):
        # a scope is a component of an op's location path, or, inside a
        # scan body, a location of its own
        found = f"/{scope}/" in text or f'loc("{scope}"' in text
        assert found == (scope in scopes), scope
